"""`moe_aux_ms_per_step`: Device time a step under the program's `moe_aux`
scope (ops/moe.balance_loss: an expert layer's sequence-wise balance loss —
the per-row counts of who chose whom, the rows' mean probabilities, their
product — and its addition to the loss), forward, backward and recompute,
first chip. A program without the scope (a parent of PR 55) reads nothing."""

LAYER = "Kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(facts, "scope_ms_per_step.moe_aux")
