"""`flash_attn_roofline`: The least time the chip could take for the flash-
attention calls a step makes (harness/flops.py, from shapes: one backward
per layer, the other calls forward) over the time they took. `bound` says
which peak sets that least time."""

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import flops

    trace = facts['trace']
    if not trace or not trace.get('mosaic_calls_per_step'):
        return None
    shapes = facts['summary']['shapes']
    layers = shapes['n_layer']
    forwards = trace['mosaic_calls_per_step'] - layers
    fwd = flops.roofline_seconds(flops.attention_call(shapes, False), facts['peaks'])
    bwd = flops.roofline_seconds(flops.attention_call(shapes, True), facts['peaks'])
    least = forwards * fwd['seconds'] + layers * bwd['seconds']
    facts.setdefault('notes', []).append(
        f"flash_attn_roofline: forward {fwd['bound']}-bound, backward "
        f"{bwd['bound']}-bound; {forwards:g} forward and {layers} backward "
        f"calls a step, least {least * 1e3:.3f} ms")
    return 100.0 * least / (trace['mosaic_ms_per_step'] * 1e-3)
