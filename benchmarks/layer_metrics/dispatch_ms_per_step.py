"""`dispatch_ms_per_step`: Host span around the `step_fn(state, batch)` call
(enqueue only), per step of the measured window."""

LAYER = "Step"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_span"


def read(facts):
    win = facts['summary']['window']
    return win['span_ms']['dispatch'] / win['steps']
