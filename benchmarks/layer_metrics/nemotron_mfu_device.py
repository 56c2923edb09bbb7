"""`nemotron_mfu_device`: Operations one step requires by the family's own
count (`train_flops_per_token`: 6 a matmul parameter a token meets, the routed
experts by the expected pairs on held ones, attention and the state-space scan
by shape, no recompute) over what the chips could do in the step's DEVICE
time. `mfu_device` counts 6 a parameter — every held expert for every token —
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
    trace = facts['trace']
    shapes = facts['summary']['shapes']
    if (not hasattr(family, "train_flops_per_token") or not trace
            or not trace.get('steps')):
        return None
    work = family.train_flops_per_token(shapes) * flops.tokens_per_step(shapes)
    peak = facts['peaks']['bf16_flops_per_s'] * shapes['chips']
    return 100.0 * work / (trace['step_device_ms'] * 1e-3 * peak)
