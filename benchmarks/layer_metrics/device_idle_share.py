"""`device_idle_share`: 1 - (union of device-op intervals / traced window),
averaged over the chips used."""

LAYER = "Device"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    trace = facts['trace']
    if not trace or not trace.get('window_s'):
        return None
    return 100.0 * (1.0 - trace['busy_s'] / trace['window_s'])
