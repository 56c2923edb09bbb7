"""`collective_ms_per_step`: Union of the collective events (all-gather,
reduce-scatter, all-reduce, all-to-all, collective-permute, by HLO name),
per step per device."""

LAYER = "Device"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    trace = facts['trace']
    if not trace or not trace.get('steps') or facts['cell']['chips'] < 2:
        return None
    return trace['collective_ms_per_step']
