"""`data_get_block_ms_per_step`: Host time a step of the traced window under
`ray_tpu:data/get_block` (`ray_tpu.get` of a block in the Data iterator)."""

LAYER = "Data"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_span"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.host_span_metric(facts, "data/get_block")
