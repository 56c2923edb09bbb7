"""Mamba-2 mixer: projections, causal depthwise conv, the chunked state-space
(SSD) scan, the gated group norm and the out-projection, in plain XLA.

One row's recurrence, a head h of P channels over a state of N (``B``, ``C``
shared by the H/G heads of a group):

    Δ_t = softplus(dt_t + dt_bias)        a_t = exp(Δ_t · A),  A = −exp(A_log)
    h_t = a_t · h_{t−1} + Δ_t · x_t ⊗ B_t                       h_0 = 0
    y_t = h_t · C_t + D · x_t

``ssd_scan`` computes it in chunks of Q tokens: inside a chunk the quadratic
form ``(C Bᵀ ∘ L) · (Δx)`` with ``L_ij = ∏_{j<k≤i} a_k`` — three matmuls over
``[chunks, Q, …]`` — and between chunks the state, a ``lax.scan`` over the
S/Q chunk summaries. Δ, a, the cumulative log-decays and the state are
float32; the matmuls take bf16 operands and accumulate in float32. The
backward is AD's; the scan's chunk states, the projections' outputs and the
scan's output carry residual names (tracing/names.py) for the remat rule.

A chip that holds ``heads`` of the model's heads and ``groups`` of its groups
computes exactly their part: the conv is depthwise, ``B`` and ``C`` belong to
a group, the gated norm is over a group's channels, and the out-projection is
linear — the shares' outputs add up to the whole layer's
(tests/test_nemotron_h.py).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.tracing import names as scopes


def mamba2_init(rng: jax.Array, n_layers: int, d_model: int, heads: int,
                head_dim: int, groups: int, state: int, conv_kernel: int,
                std: float, out_std: float, param_dtype=jnp.float32
                ) -> Dict[str, Any]:
    """``n_layers`` stacked mixers. ``in_proj`` is kept as its three parts
    (z, xBC, dt): each is one matmul whose output is used whole. ``A_log`` is
    log of uniform [1, 16]; ``dt_bias`` the inverse softplus of a step drawn
    log-uniform in [0.001, 0.1] (floor 1e-4), as the published family's
    initialisation places it."""
    inner = heads * head_dim
    conv_dim = inner + 2 * groups * state
    k = iter(jax.random.split(rng, 7))
    L = n_layers

    def normal(key, shape, s):
        return (jax.random.normal(key, shape) * s).astype(param_dtype)

    dt = jnp.exp(jax.random.uniform(next(k), (L, heads))
                 * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
    dt = jnp.maximum(dt, 1e-4)
    return {
        "w_z": normal(next(k), (L, d_model, inner), std),
        "w_xbc": normal(next(k), (L, d_model, conv_dim), std),
        "w_dt": normal(next(k), (L, d_model, heads), std),
        "conv_w": normal(next(k), (L, conv_kernel, conv_dim),
                         1.0 / conv_kernel),
        "conv_b": jnp.zeros((L, conv_dim), param_dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(param_dtype),
        "A_log": jnp.log(jax.random.uniform(
            next(k), (L, heads), minval=1.0, maxval=16.0)).astype(param_dtype),
        "D": jnp.ones((L, heads), param_dtype),
        "gate_norm": jnp.ones((L, inner), param_dtype),
        "w_out": normal(next(k), (L, inner, d_model), out_std),
    }


def mamba2_logical_axes() -> Dict[str, Any]:
    return {
        "w_z": ("layers", "embed", "mlp"),
        "w_xbc": ("layers", "embed", None),
        "w_dt": ("layers", "embed", None),
        "conv_w": ("layers", None, None),
        "conv_b": ("layers", None),
        "dt_bias": ("layers", None),
        "A_log": ("layers", None),
        "D": ("layers", None),
        "gate_norm": ("layers", "mlp"),
        "w_out": ("layers", "mlp", "embed"),
    }


MATMUL_WEIGHTS = ("w_z", "w_xbc", "w_dt", "w_out")


def causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv over the sequence: xbc [B, S, C], w [K, C] (the
    last tap is the current token), b [C] → float32 [B, S, C]."""
    K, S = w.shape[0], xbc.shape[1]
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    wf = w.astype(jnp.float32)
    out = b.astype(jnp.float32)
    for tap in range(K):
        out = out + padded[:, tap:tap + S].astype(jnp.float32) * wf[tap]
    return out


@jax.named_scope(scopes.SSD_SCAN)
def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
             Cm: jax.Array, chunk: int) -> jax.Array:
    """The recurrence above without its ``D·x`` term. x [B, S, H, P] in the
    compute dtype, dt [B, S, H] float32 (after softplus), A [H] float32
    (negative), Bm / Cm [B, S, G, N] → y [B, S, H, P] float32. A row shorter
    than a chunk, or not a whole number of them, is padded with Δ = 0 steps
    (a = 1, nothing added to the state) that are cut off again."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    Q = min(chunk, S)
    pad = -S % Q
    if pad:
        x, dt, Bm, Cm = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                         for t in (x, dt, Bm, Cm))
    nc = (S + pad) // Q
    dtype = x.dtype
    # heads of a group side by side: [B, nc, Q, G, H/G, ...]
    hg = H // G
    xc = x.reshape(Bsz, nc, Q, G, hg, P)
    dtc = dt.reshape(Bsz, nc, Q, G, hg)
    Bc = Bm.reshape(Bsz, nc, Q, G, N)
    Cc = Cm.reshape(Bsz, nc, Q, G, N)
    # cumulative log-decay inside a chunk, inclusive: cum_i = Σ_{k≤i} Δ_k·A
    cum = jnp.cumsum(dtc * A.reshape(G, hg), axis=2)          # [B,nc,Q,G,hg]
    total = cum[:, :, -1]                                     # [B,nc,G,hg]
    # Δ·x, the recurrence's input
    dx = (xc.astype(jnp.float32) * dtc[..., None]).astype(dtype)

    # inside a chunk: y_i = Σ_{j≤i} (C_i·B_j) · exp(cum_i − cum_j) · Δ_j x_j
    cb = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc,
                    preferred_element_type=jnp.float32)       # [B,nc,G,Q,Q]
    ci = jnp.moveaxis(cum, 2, -1)                             # [B,nc,G,hg,Q]
    decay = ci[..., :, None] - ci[..., None, :]               # i − j
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    # masked before the exp: above the diagonal the difference is positive
    # and may overflow
    L = jnp.exp(jnp.where(causal, decay, -jnp.inf))           # [B,nc,G,hg,Q,Q]
    m = (cb[:, :, :, None] * L).astype(dtype)
    y = jnp.einsum("bcghij,bcjghp->bcighp", m, dx,
                   preferred_element_type=jnp.float32)

    # what a chunk adds to the state by its end:
    # Σ_j exp(total − cum_j) · Δ_j x_j ⊗ B_j
    to_end = jnp.exp(total[:, :, None] - cum)                 # [B,nc,Q,G,hg]
    dx_end = (dx.astype(jnp.float32) * to_end[..., None]).astype(dtype)
    added = jnp.einsum("bcjghp,bcjgn->bcghpn", dx_end, Bc,
                       preferred_element_type=jnp.float32)    # [B,nc,G,hg,P,N]

    # between chunks: the state each chunk starts from
    def step(h, xs):
        add, tot = xs
        return h * jnp.exp(tot)[..., None, None] + add, h

    h0 = jnp.zeros((Bsz, G, hg, P, N), jnp.float32)
    _, starts = lax.scan(step, h0, (jnp.moveaxis(added, 1, 0),
                                    jnp.moveaxis(total, 1, 0)))
    starts = checkpoint_name(jnp.moveaxis(starts, 0, 1),
                             scopes.RES_SSD_STATES)           # [B,nc,G,hg,P,N]
    # the carried state's part: y_i += exp(cum_i) · C_i · h_start
    y_state = jnp.einsum("bcign,bcghpn->bcighp", Cc, starts.astype(dtype),
                         preferred_element_type=jnp.float32)
    y = y + y_state * jnp.exp(cum)[..., None]
    return y.reshape(Bsz, nc * Q, H, P)[:, :S]


def _gated_group_norm(y, z, g, groups: int, eps: float):
    """RMSNorm over each group's channels of y ⊙ silu(z), scaled by g:
    y, z [B, S, inner] → [B, S, inner] in z's dtype."""
    yf = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    shape = yf.shape
    yg = yf.reshape(shape[:-1] + (groups, shape[-1] // groups))
    yg = yg * lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + eps)
    return (yg.reshape(shape) * g.astype(jnp.float32)).astype(z.dtype)


@jax.named_scope(scopes.MAMBA)
def mamba2_mixer(u: jax.Array, p: Dict[str, Any], *, heads: int, head_dim: int,
                 groups: int, state: int, chunk: int, eps: float) -> jax.Array:
    """u [B, S, D] (normed, compute dtype) → the mixer's output [B, S, D] in
    float32 (the out-projection's accumulator; the caller adds the residual).
    ``p`` holds one layer's tensors, the matmul weights in the compute dtype."""
    Bsz, S, _ = u.shape
    inner, gn = heads * head_dim, groups * state
    z = checkpoint_name(jnp.einsum("bsd,de->bse", u, p["w_z"]),
                        scopes.RES_MAMBA_Z)
    xbc = checkpoint_name(jnp.einsum("bsd,de->bse", u, p["w_xbc"]),
                          scopes.RES_MAMBA_XBC)
    dt = checkpoint_name(jnp.einsum("bsd,dh->bsh", u, p["w_dt"],
                                    preferred_element_type=jnp.float32),
                         scopes.RES_MAMBA_DT)
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"])).astype(u.dtype)
    x = xbc[..., :inner].reshape(Bsz, S, heads, head_dim)
    Bm = xbc[..., inner:inner + gn].reshape(Bsz, S, groups, state)
    Cm = xbc[..., inner + gn:].reshape(Bsz, S, groups, state)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y = ssd_scan(x, dt, A, Bm, Cm, chunk)
    y = y + x.astype(jnp.float32) * p["D"].astype(jnp.float32)[:, None]
    y = checkpoint_name(y.astype(u.dtype).reshape(Bsz, S, inner),
                        scopes.RES_SSD_Y)
    y = _gated_group_norm(y, z, p["gate_norm"], groups, eps)
    return jnp.einsum("bse,ed->bsd", y, p["w_out"],
                      preferred_element_type=jnp.float32)
