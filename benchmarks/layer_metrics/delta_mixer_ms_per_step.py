"""`delta_mixer_ms_per_step`: Device time a step under the program's
`delta_mixer` scope (models/qwen3_next.delta_mixer: the Gated DeltaNet
mixer's fused projections, conv, L2 norms, gates, scan, gated per-head norm
and out-projection), forward, backward and recompute, first chip."""

LAYER = "Kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(facts, "scope_ms_per_step.delta_mixer")
