"""`mtp_ms_per_step`: Device time a step under the program's `mtp`
scope (models/nemotron_h.py: the multi-token-prediction module's projection,
its layers and its pass through the head and loss), forward, backward and
recompute, first chip."""

LAYER = "Model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(facts, "scope_ms_per_step.mtp")
