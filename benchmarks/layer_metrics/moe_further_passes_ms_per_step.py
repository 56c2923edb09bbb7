"""`moe_further_passes_ms_per_step`: Device time a step under the program's
`moe_further_passes` scope (ops/moe._further_passes, inside `moe_routed`):
what an expert layer runs when its batch's pairs fill MORE than one pass over
the row buffer — the loop over the passes after the first, its carried state
and the float32 sums of the experts' weight gradients, forward, backward and
recompute, first chip; the grouped kernel's own instructions carry no scope
and are not in it.
0.0 where every expert layer of the traced steps took the one-pass path;
nothing from a program whose vocabulary has no such scope."""

LAYER = "Kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"
SCOPE = "moe_further_passes"


def read(facts):
    from benchmarks.harness import program_trace

    if SCOPE not in program_trace.SCOPES:
        return None
    # no trace, or a step that routes nothing: nothing to say
    if program_trace.device_metric(
            facts, "scope_ms_per_step.moe_routed") is None:
        return None
    return program_trace.device_metric(
        facts, "scope_ms_per_step." + SCOPE) or 0.0
