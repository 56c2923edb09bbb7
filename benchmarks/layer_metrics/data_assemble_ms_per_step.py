"""`data_assemble_ms_per_step`: Host time a step of the traced window under
`ray_tpu:data/assemble` (`block_concat` + `block_slice` of a batch)."""

LAYER = "Data"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_span"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.host_span_metric(facts, "data/assemble")
