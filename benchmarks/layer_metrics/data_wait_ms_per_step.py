"""`data_wait_ms_per_step`: Host span around `next()` on the loop's batch
source, per step of the measured window."""

LAYER = "Data"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_span"


def read(facts):
    win = facts['summary']['window']
    return win['span_ms'].get('data_wait', 0.0) / win['steps']
