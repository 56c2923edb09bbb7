"""`ssd_scan_roofline`: The least time the chip could take for the state-space
scans a step makes (the family's `ssd_scan_call`, from shapes: forward and
backward of every Mamba-2 layer, no recompute) over the time under the
program's `ssd_scan` scope, recompute included. `bound` says which peak sets
that least time."""

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    import importlib

    from benchmarks.harness import flops, program_trace

    family = importlib.import_module(
        f"benchmarks.families.{facts['config']['family']}")
    took_ms = program_trace.device_metric(facts, "scope_ms_per_step.ssd_scan")
    if not hasattr(family, "ssd_scan_call") or not took_ms:
        return None
    least = flops.roofline_seconds(
        family.ssd_scan_call(facts['summary']['shapes']), facts['peaks'])
    facts.setdefault('notes', []).append(
        f"ssd_scan_roofline: {least['bound']}-bound, least "
        f"{least['seconds'] * 1e3:.3f} ms a step, took {took_ms:.3f}")
    return 100.0 * least['seconds'] / (took_ms * 1e-3)
