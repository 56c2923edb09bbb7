"""`worker_start_s`: `raylet/worker_start` of the process that became the
`TrainWorker` — `WorkerPool.start_worker` -> that process registered with the
raylet: interpreter start, importing the package, connecting. The chip is not
in it (the worker's first `jax.devices()` is the user's, in the loop)."""

LAYER = "Launch"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(facts):
    from benchmarks.harness import session_timeline

    return session_timeline.worker_start_s(facts)
