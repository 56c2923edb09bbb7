"""`exit_gate_ms_per_step`: Device time a step under the program's
`exit_gate` scope (models/llama._exit_loss: the gate's product with each
pass's state, the exit distribution over the passes, its entropy and the
step's means of each — everything of the looped objective that is not the
head), forward and backward, first chip."""

LAYER = "Model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(facts, "scope_ms_per_step.exit_gate")
