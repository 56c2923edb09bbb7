"""`report_enqueue_ms_per_step`: Host time a step of the traced window under
`ray_tpu:train/report` (`_Session.report`: the queue put);
`report_ms_per_step` less this is `float(loss)`."""

LAYER = "Train"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_span"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.host_span_metric(facts, "train/report")
