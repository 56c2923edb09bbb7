"""`step_dispatch_ms_per_step`: Host time a step of the traced window under
`ray_tpu:train/step` — the program's own span around the jitted call inside
the step its factory returns (`train_step._Step`): the enqueue, in
`.resident` the wait on a full queue. It lies inside the benchmark's
`dispatch_ms_per_step` (a `perf_counter` pair around the same call, measured
window). Nothing from a program whose vocabulary has no such span."""

LAYER = "Step"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_span"
SPAN = "train/step"


def read(facts):
    from benchmarks.harness import program_trace

    names = program_trace.names
    if names is None or SPAN not in getattr(names, "SPANS", ()):
        return None
    return program_trace.host_span_metric(facts, SPAN)
