"""`fwd_ms_per_step`: Device time a step of the instructions whose `op_name`
says forward (`jvp(` and no `transpose(`), first chip."""

LAYER = "Step"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(facts, "fwd_ms_per_step")
