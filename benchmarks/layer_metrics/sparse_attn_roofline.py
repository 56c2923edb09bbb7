"""`sparse_attn_roofline`: The least time the chip could take for the sparse
layers' attention a step makes (the family's `sparse_attn_call`, from shapes:
the keys a query is GIVEN — min(visible, top_k x block) —, two products
forward and five backward, no recompute, whatever implements it) over the
traced time of the program's three kernels (`sparse_attn_fwd`,
`sparse_attn_bwd_dq`, `sparse_attn_bwd_dkv`), recompute included. `bound` says
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
    if not hasattr(family, "sparse_attn_call") or 'peaks' not in facts:
        return None
    took = [program_trace.device_metric(facts, f"kernel_ms_per_step.{k}")
            for k in ("sparse_attn_fwd", "sparse_attn_bwd_dq",
                      "sparse_attn_bwd_dkv")]
    if not all(took):
        return None
    least = flops.roofline_seconds(
        family.sparse_attn_call(facts['summary']['shapes']), facts['peaks'])
    facts.setdefault('notes', []).append(
        f"sparse_attn_roofline: {least['bound']}-bound, least "
        f"{least['seconds'] * 1e3:.3f} ms a step, took fwd {took[0]:.3f} + "
        f"dq {took[1]:.3f} + dkv {took[2]:.3f}")
    return 100.0 * least['seconds'] / (sum(took) * 1e-3)
