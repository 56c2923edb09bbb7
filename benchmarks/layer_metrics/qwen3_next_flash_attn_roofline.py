"""`qwen3_next_flash_attn_roofline`: The least time the chip could take for
the attention layer's flash calls a step makes (the family's
`flash_attn_call`, from shapes: one forward and one backward call an
ATTENTION layer — one layer in four here — at head width 256, no recompute)
over the traced time of the two kernels (`flash_attention_fwd`,
`flash_attention_bwd`), recompute included; as `lfm2_flash_attn_roofline`
reads the LFM2 cell's. `bound` says which peak sets that least time."""

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
        f"qwen3_next_flash_attn_roofline: {least['bound']}-bound, least "
        f"{least['seconds'] * 1e3:.3f} ms a step, took fwd {took[0]:.3f} + "
        f"bwd {took[1]:.3f}")
    return 100.0 * least['seconds'] / (sum(took) * 1e-3)
