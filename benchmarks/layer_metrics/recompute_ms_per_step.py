"""`recompute_ms_per_step`: Device time a step under `rematted_computation`
(what `jax.checkpoint` runs a second time), first chip; 0 without remat."""

LAYER = "Model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(facts, "recompute_ms_per_step")
