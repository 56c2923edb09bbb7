"""`lightning_attn_ms_per_step`: Device time a step under the program's
`lightning_attn` scope (models/minicpm_sala.py: the lightning mixer's
projections, QK-norm, RoPE, the scan, output norm, gate and out-projection),
forward, backward and recompute, first chip."""

LAYER = "Kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(
        facts, "scope_ms_per_step.lightning_attn")
