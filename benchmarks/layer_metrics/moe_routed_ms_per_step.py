"""`moe_routed_ms_per_step`: Device time a step of the routed experts
(ops/moe.routed_experts): what runs under the program's `moe_routed` scope
(router, top-k, sort, gather, masks, combine: `moe_dispatch` is all of that)
plus the two grouped products, forward, backward and recompute, first chip.
The grouped products are the TPU compiler's own kernel (`lax.ragged_dot`), whose
instructions carry no scope: they are found by their name (`ragged-dot`,
tracing/names.RAGGED_DOT_KERNEL)."""

LAYER = "Kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    scoped = program_trace.device_metric(facts, "scope_ms_per_step.moe_routed")
    if scoped is None:
        return None
    grouped = program_trace.device_metric(
        facts, "kernel_ms_per_step.ragged-dot")
    return scoped + (grouped or 0.0)
