"""`mamba_ms_per_step`: Device time a step under the program's
`mamba` scope (ops/mamba2.py: the Mamba-2 mixer's projections, conv, scan,
gated norm and out-projection), forward, backward and recompute, first chip."""

LAYER = "Kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(facts, "scope_ms_per_step.mamba")
