"""`short_conv_ms_per_step`: Device time a step under the program's
`short_conv` scope (models/lfm2_moe.py: the double-gated short convolution
whole — in-projection, the two gates and the conv, out-projection), forward,
backward and recompute, first chip."""

LAYER = "Kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(facts, "scope_ms_per_step.short_conv")
