"""`compiles_in_window`: Backend compilations (cache loads included) between
the window's two `block_until_ready`. Must read 0; anything else makes
`correct` false."""

LAYER = "Step"
UNIT = "count"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_counter"


def read(facts):
    return facts['summary']['window']['compiles_in_window']
