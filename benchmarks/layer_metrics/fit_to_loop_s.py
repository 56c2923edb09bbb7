"""`fit_to_loop_s`: the program's own `launch_s` — `train/fit` opened (the
driver, in `DataParallelTrainer.fit()`) -> `train/loop_entered` of rank 0 (the
worker, immediately before the user's loop is called), one trace, one host
clock. Holds the worker group's start, `Dataset.split` and `start_training`."""

LAYER = "Launch"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(facts):
    from benchmarks.harness import session_timeline

    return session_timeline.fit_to_loop_s(facts)
