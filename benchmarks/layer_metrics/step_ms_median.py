"""`step_ms_median`: Median host interval between consecutive step completions
as the loop sees them (the loss is ready)."""

LAYER = "Step"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_span"


def read(facts):
    return facts['summary']['window']['step_ms_median']
