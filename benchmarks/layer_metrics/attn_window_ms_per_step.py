"""`attn_window_ms_per_step`: Device time a step under the program's
`attn_window` scope (models/afmoe.attention_operator of a WINDOW layer,
inside `attn`: the flash pair under a causal window of `sliding_window` keys
— both kernels walk the band alone —, k and v repeated to the query heads,
the sigmoid output gate), forward, recompute and backward, first chip. The
full layers run kernels of the same name: `attn_full_ms_per_step` reads
theirs. Nothing from a program without the scope."""

LAYER = "Model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(facts, "scope_ms_per_step.attn_window")
