"""`gated_delta_ms_per_step`: Device time a step under the program's
`gated_delta` scope (ops/gated_delta.gated_delta_scan: the delta rule's
kernel pair and whatever XLA prepares for it — the cumulative gates, padding,
the rows' layout —, inside `delta_mixer`), forward, backward and recompute,
first chip."""

LAYER = "Kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(facts, "scope_ms_per_step.gated_delta")
