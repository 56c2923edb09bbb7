"""`layer_stack_traffic_ms_per_step`: Device time a step of `dynamic_slice` /
`dynamic_update_slice` / `squeeze` directly under the layer scan's
`while/body`, in no program scope: stacked parameters, gradients and residuals
moved through the `lax.scan` carry, first chip. A slice XLA fused into a
matmul counts with the matmul's scope, not here."""

LAYER = "Model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(facts, "layer_stack_traffic_ms_per_step")
