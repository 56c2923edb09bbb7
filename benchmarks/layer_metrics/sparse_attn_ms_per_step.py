"""`sparse_attn_ms_per_step`: Device time a step under the program's
`sparse_attention` scope (ops/sparse_attention.py: the selection and the
three attention kernels — the mixer's projections, QK-norm, gate and
out-projection around them too), forward, backward and recompute, first
chip."""

LAYER = "Kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(
        facts, "scope_ms_per_step.sparse_attention")
