"""`optimizer_ms_per_step`: Device time a step under the step factories'
`optimizer` scope (update, apply, global norm), first chip."""

LAYER = "Step"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(facts, "optimizer_ms_per_step")
