"""`mhc_maps_ms_per_step`: Device time a step under the program's `mhc_maps`
scope, inside `mhc` (models/hyper_connections.maps: the flattened stream's
RMS, the product with Phi, the sigmoids and the Sinkhorn rounds), forward,
backward and recompute, first chip. A program without the scope (a parent of
PR 57) reads nothing."""

LAYER = "Model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(facts, "scope_ms_per_step.mhc_maps")
