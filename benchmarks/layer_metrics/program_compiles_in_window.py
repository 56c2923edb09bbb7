"""`program_compiles_in_window`: `train/compile` events that ended inside the
measured window, from the program's own record. Must read 0 and equal
`compiles_in_window`, the benchmark loop's count of the same events."""

LAYER = "Step"
UNIT = "count"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_counter"


def read(facts):
    from benchmarks.harness import session_timeline

    return session_timeline.program_compiles_in_window(facts)
