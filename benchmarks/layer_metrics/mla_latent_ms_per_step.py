"""`mla_latent_ms_per_step`: Device time a step under the program's
`mla_latent` scope (models/deepseek_v2.py: everything of latent attention
between the norm and the flash kernel that is not the kernel — the q
projection, the joint projection to the latent and the one k_pe, the latent's
norm, the up-projection to k_nope and v, RoPE on the rotary channels, k put
together), forward, backward and recompute, first chip. A program without
the scope (a parent of PR 55) reads nothing."""

LAYER = "Kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(facts):
    from benchmarks.harness import program_trace

    return program_trace.device_metric(facts, "scope_ms_per_step.mla_latent")
