"""`gated_delta_roofline`: The least time the chip could take for the
delta-rule scans a step makes (the family's `gated_delta_call`, from shapes:
the chunk form's operations and bytes, a forward and a backward call a
DeltaNet layer, no recompute) over the traced time of the two kernels
(`gated_delta_fwd`, `gated_delta_bwd`), recompute included. `bound` says
which peak sets that least time."""

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    import importlib

    from benchmarks.harness import flops, program_trace

    family = importlib.import_module(
        f"benchmarks.families.{facts['config']['family']}")
    if not hasattr(family, "gated_delta_call") or 'peaks' not in facts:
        return None
    took = [program_trace.device_metric(facts, f"kernel_ms_per_step.{k}")
            for k in ("gated_delta_fwd", "gated_delta_bwd")]
    if not all(took):
        return None
    least = flops.roofline_seconds(
        family.gated_delta_call(facts['summary']['shapes']), facts['peaks'])
    facts.setdefault('notes', []).append(
        f"gated_delta_roofline: {least['bound']}-bound, least "
        f"{least['seconds'] * 1e3:.3f} ms a step, took fwd {took[0]:.3f} + "
        f"bwd {took[1]:.3f}")
    return 100.0 * least['seconds'] / (sum(took) * 1e-3)
