"""`mfu_device`: Operations one step requires (harness/flops.py: 6N + 12LSd per
token, no recompute) over what the chips could do in the step's DEVICE time.
End-to-end MFU is this times (1 - idle share)."""

LAYER = "Model"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import flops

    trace = facts['trace']
    if not trace or not trace.get('steps'):
        return None
    shapes = facts['summary']['shapes']
    work = flops.train_flops_per_token(shapes) * flops.tokens_per_step(shapes)
    peak = facts['peaks']['bf16_flops_per_s'] * shapes['chips']
    return 100.0 * work / (trace['step_device_ms'] * 1e-3 * peak)
