"""`eva_prep_kv_ms_per_step`: Device time a step under the program's
`eva_prep_kv` scope: the chunk-summary pass (a 16-way softmax and two weighted
sums over k and v; XLA, memory-bound), forward, backward and recompute, first
chip."""

LAYER = "Kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(facts, "scope_ms_per_step.eva_prep_kv")
