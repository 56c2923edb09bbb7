"""`shard_datasets_s`: `train/shard_datasets` — `fit()` splitting every
Dataset it was given (`Dataset.split`: materialise, count rows, cut), the
program's own `dataset_materialize_s`."""

LAYER = "Data"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(facts):
    from benchmarks.harness import session_timeline

    return session_timeline.span_seconds(facts, "train/shard_datasets")
