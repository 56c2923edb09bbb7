"""`collective_exposed_ms_per_step`: The part of the collective time during
which no other operation runs on that device, per step per device."""

LAYER = "Device"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    trace = facts['trace']
    if not trace or not trace.get('steps') or facts['cell']['chips'] < 2:
        return None
    return trace['collective_exposed_ms_per_step']
