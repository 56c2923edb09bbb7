"""`split_tasks_busy_s`: seconds of `data/split` in which at least one of the
tasks submitted under it was running (union of their RUNNING -> EXECUTED /
FINISHED, cut to the span). `shard_datasets_s` less this and less
`split_first_task_wait_s` is the driver waiting with no task of the split
running: between two tasks, and fetching the counts."""

LAYER = "Data"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(facts):
    from benchmarks.harness import session_timeline

    return session_timeline.split_tasks_busy_s(facts)
