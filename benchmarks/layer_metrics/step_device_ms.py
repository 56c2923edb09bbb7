"""`step_device_ms`: Device time of the jitted step's XLA module, per step per
device (trace, `XLA Modules` line)."""

LAYER = "Step"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    trace = facts['trace']
    return trace['step_device_ms'] if trace and trace.get('steps') else None
