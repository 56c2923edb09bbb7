"""`ssd_scan_ms_per_step`: Device time a step under the program's
`ssd_scan` scope (ops/mamba2.ssd_scan: the chunked state-space scan alone,
inside `mamba`), forward, backward and recompute, first chip."""

LAYER = "Kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(facts, "scope_ms_per_step.ssd_scan")
