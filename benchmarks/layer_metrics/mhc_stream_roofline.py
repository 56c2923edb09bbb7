"""`mhc_stream_roofline`: The least time the chip could take for the
hyper-connections' stream traffic a step makes (the family's `mhc_call`,
from shapes: eight passes over a sublayer's n-stream carry — three forward,
five backward — and the sublayer's own d-wide tensors once each, no
recompute; `mhc_call` says each pass in words) over the traced time under the
program's `mhc` scope, recompute included. It stands for the
hyper-connection's roofline whether XLA's fusions or a kernel implement it.
`bound` says which peak sets that least time."""

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    import importlib

    from benchmarks.harness import flops, program_trace

    family = importlib.import_module(
        f"benchmarks.families.{facts['config']['family']}")
    took_ms = program_trace.device_metric(facts, "scope_ms_per_step.mhc")
    if not hasattr(family, "mhc_call") or not took_ms or 'peaks' not in facts:
        return None
    least = flops.roofline_seconds(
        family.mhc_call(facts['summary']['shapes']), facts['peaks'])
    facts.setdefault('notes', []).append(
        f"mhc_stream_roofline: {least['bound']}-bound, least "
        f"{least['seconds'] * 1e3:.3f} ms a step, took {took_ms:.3f}")
    return 100.0 * least['seconds'] / (took_ms * 1e-3)
