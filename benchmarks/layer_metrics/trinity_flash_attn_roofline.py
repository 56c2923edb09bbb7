"""`trinity_flash_attn_roofline`: The least time the chip could take for the
five layers' flash calls a step makes (the family's `flash_attn_call`, from
shapes: one forward and one backward call a layer over the pairs the layer's
KIND sees — the causal half for the full layer, the 2,048-key BAND for the
four window layers —, k and v read once for their group of eight query
heads, no recompute) over the traced time of the two kernels
(`flash_attention_fwd`, `flash_attention_bwd`), recompute included. The work
is the model's, whatever implements it: kernels that walked the whole
triangle in a window layer would read a quarter of this share. `bound` says
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
    if not hasattr(family, "flash_attn_call") or 'peaks' not in facts:
        return None
    took = [program_trace.device_metric(facts, f"kernel_ms_per_step.{k}")
            for k in ("flash_attention_fwd", "flash_attention_bwd")]
    if not all(took):
        return None
    least = flops.roofline_seconds(
        family.flash_attn_call(facts['summary']['shapes']), facts['peaks'])
    facts.setdefault('notes', []).append(
        f"trinity_flash_attn_roofline: {least['bound']}-bound, least "
        f"{least['seconds'] * 1e3:.3f} ms a step, took fwd {took[0]:.3f} + "
        f"bwd {took[1]:.3f}")
    return 100.0 * least['seconds'] / (sum(took) * 1e-3)
