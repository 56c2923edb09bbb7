"""`scope_coverage`: Share of the first chip's busy time spent in instructions
that carry a scope of the program's vocabulary or a named kernel; falls when
a refactor drops a scope."""

LAYER = "Model"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(facts, "scope_coverage")
