"""`program_backend_init_s`: rank 0's `train/backend_init` — the first
initialisation of a JAX backend in the train worker's process, JAX's own
`_init_backend` from its first line to its last, observed by the program at
the loop's own first `jax.devices()`. `backend_init_s` is the benchmark's
clock round that call; the difference is what the call does outside
`_init_backend` (plugin discovery)."""

LAYER = "Launch"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(facts):
    from benchmarks.harness import session_record

    return session_record.program_backend_init_s(facts)
