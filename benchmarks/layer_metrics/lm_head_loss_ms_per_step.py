"""`lm_head_loss_ms_per_step`: Device time a step under the model's
`lm_head_loss` scope (tied LM head + cross-entropy), forward and backward,
first chip."""

LAYER = "Model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(facts, "scope_ms_per_step.lm_head_loss")
