"""`start_training_wait_s`: `train/start_training` — the first `start_training`
submitted -> every rank's call returned: what is left of the worker's start
(process, actor constructor, the shards' refs) once `Dataset.split` no
longer hides it."""

LAYER = "Launch"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(facts):
    from benchmarks.harness import session_timeline

    return session_timeline.span_seconds(facts, "train/start_training")
