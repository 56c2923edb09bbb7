"""`eva_attn_ms_per_step`: Device time a step under the program's
`eva_attention` scope (ops/eva_attention.py): both aggregation kernels and the
chunk-summary pass, forward, backward and recompute, first chip."""

LAYER = "Kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(
        facts, "scope_ms_per_step.eva_attention")
