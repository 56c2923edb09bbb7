"""The double-gated short convolution of the LFM2 family's ``conv`` layers.

Between the operator's two products — ``[B̃ | C̃ | x̃] = u · W_in`` before it,
``· W_out`` after it (models/lfm2_moe.py) — there is elementwise work alone:

    z = B̃ ⊙ x̃;   c_t = Σ_j w_j ⊙ z_{t−(K−1)+j}  (depthwise, causal, z = 0
    before the row's start, no bias, no activation);   y = C̃ ⊙ c

over ``[B, S, 3·D]`` in and ``[B, S, D]`` out: three reads and one write a
token and channel, ~2·K + 2 operations — bandwidth-bound on any chip. It is
written as XLA's shifted multiply-adds (the Mamba-2 mixer's
``mamba2.causal_conv`` is the same conv under a SiLU, with a bias, at kernel
4): one fusion forward, and a backward that is the mirror image (the taps
shifted the other way). A row is one document: no state is reset inside it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.tracing import names as scopes


@jax.named_scope(scopes.CONV_GATE)
def gated_short_conv(bcx: jax.Array, w: jax.Array) -> jax.Array:
    """bcx [B, S, 3·D] (the in-projection's output: B̃, C̃, x̃ in that order),
    w [K, D] (the last tap is the current token) → C̃ ⊙ conv(B̃ ⊙ x̃)
    [B, S, D] in bcx's dtype; the arithmetic in float32."""
    K, D = w.shape
    S = bcx.shape[1]
    f = jnp.float32
    b, c, x = (bcx[..., i * D:(i + 1) * D].astype(f) for i in range(3))
    z = jnp.pad(b * x, ((0, 0), (K - 1, 0), (0, 0)))
    wf = w.astype(f)
    conv = sum(z[:, tap:tap + S] * wf[tap] for tap in range(K))
    return (c * conv).astype(bcx.dtype)
