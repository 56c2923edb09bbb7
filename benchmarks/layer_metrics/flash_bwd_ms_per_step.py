"""`flash_bwd_ms_per_step`: Device time a step of the Mosaic kernel named
`flash_attention_bwd`, first chip."""

LAYER = "Kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(
        facts, "kernel_ms_per_step.flash_attention_bwd")
