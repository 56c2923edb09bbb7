"""`setup_compile_s`: sum of `seconds` over the `train/compile` events (every
backend compile of the train worker, a load from the persistent cache
included) that ended between `train/loop_entered` and the window's first
step."""

LAYER = "Step"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(facts):
    from benchmarks.harness import session_timeline

    return session_timeline.setup_compile_s(facts)
