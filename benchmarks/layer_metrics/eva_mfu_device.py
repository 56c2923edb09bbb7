"""`eva_mfu_device`: Operations one step requires by the family's own count
(`train_flops_per_token`: 6 a matmul parameter, attention as the EVA mask cuts
it, no recompute) over what the chips could do in the step's DEVICE time.
`mfu_device` counts 12·L·S·d a token for attention — GPT-2's full-causal
convention, 16 times this model's work at 32,768 — so it is not reported
here."""

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
    if (not hasattr(family, "train_flops_per_token") or not trace
            or not trace.get('steps')):
        return None
    shapes = facts['summary']['shapes']
    work = family.train_flops_per_token(shapes) * flops.tokens_per_step(shapes)
    peak = facts['peaks']['bf16_flops_per_s'] * shapes['chips']
    return 100.0 * work / (trace['step_device_ms'] * 1e-3 * peak)
