"""`backend_init_s`: The worker's first `jax.devices()`: the process reaching
its chip(s)."""

LAYER = "Launch"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(facts):
    return facts['summary']['setup_parts_s']['backend_init']
