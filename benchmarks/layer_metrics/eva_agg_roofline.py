"""`eva_agg_roofline`: The least time the chip could take for the EVA
aggregation calls a step makes (the family's `eva_call`, from shapes: one
`eva_agg_bwd` a layer, every other Mosaic call an `eva_agg_fwd`) over the time
the two kernels took. `bound` says which peak sets that least time."""

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"

FWD, BWD = "eva_agg_fwd", "eva_agg_bwd"


def read(facts):
    import importlib

    from benchmarks.harness import flops, program_trace

    family = importlib.import_module(
        f"benchmarks.families.{facts['config']['family']}")
    trace = facts['trace']
    fwd_ms = program_trace.device_metric(facts, f"kernel_ms_per_step.{FWD}")
    bwd_ms = program_trace.device_metric(facts, f"kernel_ms_per_step.{BWD}")
    if (not hasattr(family, "eva_call") or not fwd_ms or not bwd_ms
            or not trace.get('mosaic_calls_per_step')):
        return None
    shapes = facts['summary']['shapes']
    layers = shapes['n_layer']
    forwards = trace['mosaic_calls_per_step'] - layers
    fwd = flops.roofline_seconds(family.eva_call(shapes, FWD), facts['peaks'])
    bwd = flops.roofline_seconds(family.eva_call(shapes, BWD), facts['peaks'])
    least = forwards * fwd['seconds'] + layers * bwd['seconds']
    facts.setdefault('notes', []).append(
        f"eva_agg_roofline: forward {fwd['bound']}-bound (least "
        f"{fwd['seconds'] * 1e3:.3f} ms a call, took {fwd_ms / forwards:.3f}), "
        f"backward {bwd['bound']}-bound (least {bwd['seconds'] * 1e3:.3f}, "
        f"took {bwd_ms / layers:.3f}); {forwards:g} forward and {layers} "
        f"backward calls a step, least {least * 1e3:.3f} ms")
    return 100.0 * least / ((fwd_ms + bwd_ms) * 1e-3)
