"""`sparse_select_ms_per_step`: Device time a step under the program's
`sparse_select` scope (ops/sparse_attention.sparse_select: compressed keys,
their float32 scores, pooling to blocks and `top_k`, all XLA), forward and
recompute (it has no backward), first chip."""

LAYER = "Kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(
        facts, "scope_ms_per_step.sparse_select")
