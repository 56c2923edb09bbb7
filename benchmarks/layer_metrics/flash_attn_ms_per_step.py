"""`flash_attn_ms_per_step`: Sum of the Mosaic custom-call events (flash
attention forward, its recomputation under remat, backward), per step per
device."""

LAYER = "Kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    trace = facts['trace']
    if not trace or not trace.get('mosaic_calls_per_step'):
        return None
    return trace['mosaic_ms_per_step']
