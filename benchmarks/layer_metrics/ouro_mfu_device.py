"""`ouro_mfu_device`: Operations one step requires by the family's own count
(`train_flops_per_token`: 6 a matmul parameter a token meets — a layer's
seven products once an APPLICATION, four passes of eight layers on one set of
weights; the one head and the gate once a pass —, attention over the causal
half by shape at 16 x 128 an application, no recompute) over what the chips
could do in the step's DEVICE time: the share of the whole step's peak.
`mfu_device` counts a parameter once a step and attention uncut by causality,
so it is not reported here."""

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
