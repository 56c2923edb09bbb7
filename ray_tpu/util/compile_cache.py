"""Where JAX keeps compiled programs between processes.

One rule, for every process that compiles (TrainWorker, the RLlib learner):
the cache is placed from OUTSIDE through ``JAX_COMPILATION_CACHE_DIR``
when that is set — JAX reads the variable itself, worker environments descend
from the driver's, and no code here overrides it. Only when it is unset does
the program choose, and then it chooses one fixed directory inside the
checkout: a path derived from a tempdir, a pid, a session id or a timestamp
would give every process its own empty cache.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache (git-ignored), beside the ray_tpu package
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Call before the first compile. Returns the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
