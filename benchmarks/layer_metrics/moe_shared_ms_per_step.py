"""`moe_shared_ms_per_step`: Device time a step under the program's
`moe_shared` scope (the shared expert beside the routed ones), forward,
backward and recompute, first chip."""

LAYER = "Model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(facts, "scope_ms_per_step.moe_shared")
