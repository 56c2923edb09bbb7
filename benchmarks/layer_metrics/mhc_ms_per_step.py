"""`mhc_ms_per_step`: Device time a step under the program's `mhc` scope
(models/hyper_connections.py through models/deepseek_v2.py: all of the
sublayers' hyper-connection work — the maps, the pre-mix of the n streams,
the write-back to them, the stream's start and end), forward, backward and
recompute, first chip. A program without the scope (a parent of PR 57) reads
nothing."""

LAYER = "Model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(facts, "scope_ms_per_step.mhc")
