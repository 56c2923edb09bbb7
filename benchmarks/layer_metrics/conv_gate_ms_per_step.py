"""`conv_gate_ms_per_step`: Device time a step under the program's
`conv_gate` scope (ops/short_conv.py, inside `short_conv`: the two gates and
the depthwise causal conv between the operator's two products — elementwise,
what a kernel would replace), forward, backward and recompute, first chip."""

LAYER = "Kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(facts, "scope_ms_per_step.conv_gate")
