"""`dsv2_experts_roofline`: The least time the chip could take for the held
experts' grouped products a step makes (the family's `experts_call`, from
shapes: the pairs a balanced layer lands on the held experts, three products
forward and six backward a layer, no recompute; the shared expert is a dense
MLP and not counted) over the traced time of the TPU compiler's grouped
kernel (`ragged-dot`, found by name: its instructions carry no scope),
recompute included. `bound` says which peak sets that least time."""

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    import importlib

    from benchmarks.harness import flops, program_trace

    family = importlib.import_module(
        f"benchmarks.families.{facts['config']['family']}")
    took_ms = program_trace.device_metric(
        facts, "kernel_ms_per_step.ragged-dot")
    if (not hasattr(family, "experts_call") or not took_ms
            or 'peaks' not in facts):
        return None
    least = flops.roofline_seconds(
        family.experts_call(facts['summary']['shapes']), facts['peaks'])
    facts.setdefault('notes', []).append(
        f"dsv2_experts_roofline: {least['bound']}-bound, least "
        f"{least['seconds'] * 1e3:.3f} ms a step, took {took_ms:.3f}")
    return 100.0 * least['seconds'] / (took_ms * 1e-3)
