"""`split_first_task_wait_s`: `data/split` opened -> the first task submitted
under it (same trace) RUNNING on a worker: leases and the pooled worker
processes' start, before any block is made."""

LAYER = "Data"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(facts):
    from benchmarks.harness import session_timeline

    return session_timeline.split_first_task_wait_s(facts)
