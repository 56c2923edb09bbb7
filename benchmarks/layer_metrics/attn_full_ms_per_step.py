"""`attn_full_ms_per_step`: Device time a step under the program's
`attn_full` scope (models/afmoe.attention_operator of a FULL layer, inside
`attn`: the flash pair over the whole causal triangle, no positional signal,
k and v repeated to the query heads, the sigmoid output gate), forward,
recompute and backward, first chip; `attn_window_ms_per_step` reads the
window layers'. Nothing from a program without the scope."""

LAYER = "Model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(facts, "scope_ms_per_step.attn_full")
