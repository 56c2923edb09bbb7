"""`report_ms_per_step`: Host span around `float(loss)` + `train.report`,
entered once the loss is ready (the wait for the device is the `sync` span,
not this), per step of the measured window."""

LAYER = "Train"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_span"


def read(facts):
    win = facts['summary']['window']
    if 'report' not in win['span_ms']:
        return None
    return win['span_ms']['report'] / win['steps']
