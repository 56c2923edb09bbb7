"""`trinity_mfu_device`: Operations one step requires by the family's own
count (`train_flops_per_token`: 6 a matmul parameter a token meets —
attention's five projections, the shared expert, the routed experts by the
pairs a token is expected to land on held ones —, attention by shape over
the pairs each KIND of layer sees: the causal half for a full layer, the
BAND for a window layer; no recompute) over what the chips could do in the
step's DEVICE time: the share of the whole step's peak. `mfu_device` counts
every held expert for every token and every attention layer over the whole
triangle, so it is not reported here."""

LAYER = "Model"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    import importlib

    from benchmarks.harness import flops

    family = importlib.import_module(
        f"benchmarks.families.{facts['config']['family']}")
    trace = facts.get('trace')
    shapes = facts['summary']['shapes']
    if (not hasattr(family, "train_flops_per_token") or not trace
            or not trace.get('steps') or 'peaks' not in facts):
        return None
    work = family.train_flops_per_token(shapes) * flops.tokens_per_step(shapes)
    peak = facts['peaks']['bf16_flops_per_s'] * shapes['chips']
    return 100.0 * work / (trace['step_device_ms'] * 1e-3 * peak)
