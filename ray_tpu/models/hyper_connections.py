"""Manifold-constrained hyper-connections: a residual path of ``n`` streams.

A layer's carry is not one stream ``x + F(norm(x))`` but ``n`` of them, and
every sublayer F reads one mix of the streams and writes to all of them
through maps that are functions of the token's own streams (mHC,
arXiv:2512.24880, on Hyper-Connections, arXiv:2409.19606). Three pieces any
family's layer wraps a sublayer with, and nothing of any one model:

    maps      v = vec(x) [n·C];  m = (v·Φ) / sqrt(mean(v²) + ε)     [n² + 2n]
              H_pre  = σ(α_pre · m[0:n] + b_pre)                     [n]
              H_post = 2 · σ(α_post · m[n:2n] + b_post)              [n]
              H_res  = SK(clip(α_res · mat(m[2n:]) + b_res, ∓clamp)) [n, n]
              SK: M = exp(·); ``rounds`` times M ← M / (rowsum(M) + eps),
              M ← M / (colsum(M) + eps): doubly stochastic to the rounds'
              tolerance, so mixing the streams neither grows nor shrinks them
    pre_mix   u = Σ_i H_pre[i] · x[i]                                [C]
              … y = F(norm(u)), the caller's sublayer with its own pre-norm …
    write_back x'[i] = Σ_j H_res[i, j] · x[j] + H_post[i] · y         [n·C]

The stream is ONE array ``[B, S, n·C]`` in the compute dtype — stream i the
channels ``i·C … (i+1)·C − 1`` — so the maps' product is a plain matmul on the
carry as it stands, a stream is a slice at whole lane tiles (C a multiple of
128), and ``blocks.run_pattern`` carries it as any other array. The maps are
float32 planes with the TOKENS minor, ``[n, B, S]`` and ``[n, n, B, S]``: the
rounds are elementwise work on full lanes, where ``[B, S, n, n]`` would put
four numbers on a tile's 128. Φ enters the product in the compute dtype
(cast in the layer loop as every matmul weight) with a float32 accumulator;
the norm's statistic, the sigmoids, the rounds and both mixes' sums are
float32, the mixes' results rounded to the stream's dtype once.

``mixed`` and ``joined`` are what a layer calls around a sublayer: the kernel
pairs of ``ops/hyper_connections.py``, which make the values of the three
plain functions above in one pass over x each way (PR 58). ``maps``,
``pre_mix`` and ``write_back`` stay as the definition the tests hold the
kernels to; no layer calls them.

``expand`` starts a stream (every stream a copy of the embedding) and
``collapse`` ends one (the sum over the streams, float32). A model whose
``n`` is 1 calls none of this: its layers are the plain residual's, with no
map and no parameter.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops import hyper_connections as kernels
from ray_tpu.tracing import get_buffer, names as scopes

# a sublayer's tensors, each under its sublayer's prefix; PHI alone is a
# matmul weight (what a layer casts inside its loop)
PHI, ALPHA, BIAS = "phi", "alpha", "bias"
# the initial values: a layer starts as the plain residual layer. α small, so
# the maps start static; b_pre so that σ = 1 / n (u starts as the streams'
# mean — the embedding, since the streams start equal); b_post 0 (2σ = 1:
# every stream takes y whole); b_res a diagonal large enough that H_res
# starts within 1e-2 of the identity (off-diagonal 1 / (e⁶ + n − 1) = 2.5e-3
# at n = 4)
ALPHA_INIT = 0.01
RES_DIAGONAL_INIT = 6.0

_decisions: Dict[tuple, Dict[str, Any]] = {}


class HyperConnection(NamedTuple):
    """What a model's config states of its residual path."""
    n: int              # streams (the expansion rate; hc_mult)
    rounds: int         # Sinkhorn rounds (hc_sinkhorn_iters)
    eps: float          # added to the rounds' denominators (hc_eps)
    clamp: float        # H̃_res is clipped to ∓ this before the exp
    norm_eps: float     # the ε of the flattened stream's RMS

    @property
    def outputs(self) -> int:
        return self.n * self.n + 2 * self.n


class Maps(NamedTuple):
    pre: jax.Array      # [n, B, S] float32
    post: jax.Array     # [n, B, S] float32
    res: jax.Array      # [n, n, B, S] float32: [i, j] takes stream j to i


def init(rng: jax.Array, n_layers: int, hc: HyperConnection, width: int,
         std: float, param_dtype, prefix: str) -> Dict[str, jax.Array]:
    """One sublayer's tensors for ``n_layers`` stacked layers, under
    ``prefix``: Φ normal ``std``, the three α, the biases as the module
    constants say."""
    n = hc.n
    bias = jnp.concatenate([
        jnp.full((n,), -math.log(n - 1.0)), jnp.zeros((n,)),
        (RES_DIAGONAL_INIT * jnp.eye(n)).reshape(-1)])
    return {
        prefix + PHI: (jax.random.normal(rng, (n_layers, n * width, hc.outputs))
                       * std).astype(param_dtype),
        prefix + ALPHA: jnp.full((n_layers, 3), ALPHA_INIT, param_dtype),
        prefix + BIAS: jnp.tile(bias, (n_layers, 1)).astype(param_dtype)}


def logical_axes(prefix: str) -> Dict[str, Tuple]:
    return {prefix + PHI: ("layers", None, None),
            prefix + ALPHA: ("layers", None),
            prefix + BIAS: ("layers", None)}


def sinkhorn(logits: jax.Array, rounds: int, eps: float) -> jax.Array:
    """logits [n, n, ...] float32 → ``exp`` of them after ``rounds`` rounds
    of dividing every row (over axis 1) and then every column (over axis 0)
    by its sum + ``eps``. Unrolled: elementwise work on 2 · n² planes a
    round, which one fusion holds."""
    m = jnp.exp(logits)
    for _ in range(rounds):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
    return m


def _maps_of(m: jax.Array, p: Dict[str, jax.Array], prefix: str,
             hc: HyperConnection) -> Maps:
    """The three maps from the normalised logits m [n² + 2n, B, S] float32."""
    n = hc.n
    alpha = p[prefix + ALPHA].astype(jnp.float32)
    bias = p[prefix + BIAS].astype(jnp.float32)[:, None, None]
    pre = jax.nn.sigmoid(alpha[0] * m[:n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * m[n:2 * n] + bias[n:2 * n])
    res = (alpha[2] * m[2 * n:] + bias[2 * n:]).reshape((n, n) + m.shape[1:])
    return Maps(pre, post, sinkhorn(jnp.clip(res, -hc.clamp, hc.clamp),
                                    hc.rounds, hc.eps))


@jax.named_scope(scopes.MHC_MAPS)
def maps(x: jax.Array, p: Dict[str, jax.Array], prefix: str,
         hc: HyperConnection) -> Maps:
    """x [B, S, n·C] → the token's three maps (module docstring). ``p``
    holds the sublayer's tensors under ``prefix``, Φ in the compute dtype."""
    xf = x.astype(jnp.float32)
    inv_rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1) + hc.norm_eps)  # [B, S]
    m = jnp.moveaxis(jnp.einsum("bsk,km->bsm", x, p[prefix + PHI],
                                preferred_element_type=jnp.float32), -1, 0
                     ) * inv_rms                                 # [n² + 2n, B, S]
    return _maps_of(m, p, prefix, hc)


def _streams(x: jax.Array, n: int):
    width = x.shape[-1] // n
    return [x[..., i * width:(i + 1) * width].astype(jnp.float32)
            for i in range(n)]


def pre_mix(x: jax.Array, h: Maps) -> jax.Array:
    """x [B, S, n·C] → u [B, S, C] = Σ_i H_pre[i] · x[i], in x's dtype."""
    xs = _streams(x, h.pre.shape[0])
    return sum(h.pre[i][..., None] * xi for i, xi in enumerate(xs)
               ).astype(x.dtype)


def write_back(x: jax.Array, y: jax.Array, h: Maps) -> jax.Array:
    """x [B, S, n·C], y [B, S, C] (the sublayer's float32 output) → x'
    [B, S, n·C], x'[i] = Σ_j H_res[i, j] · x[j] + H_post[i] · y: float32
    sums, the stream stored in x's dtype (parts.residual_add's rule)."""
    n = h.pre.shape[0]
    xs, yf = _streams(x, n), y.astype(jnp.float32)
    return jnp.concatenate([
        (sum(h.res[i, j][..., None] * xs[j] for j in range(n))
         + h.post[i][..., None] * yf).astype(x.dtype)
        for i in range(n)], axis=-1)


def mixed(x: jax.Array, p: Dict[str, jax.Array], prefix: str,
          hc: HyperConnection) -> Tuple[jax.Array, jax.Array, Maps]:
    """What a sublayer takes of the carry x [B, S, n·C]: (x for ``joined`` to
    read, the pre-mix u [B, S, C], the token's maps) — ``maps`` and
    ``pre_mix`` in one pass over x (ops/hyper_connections.mix: the logits,
    the RMS and u from one read, x handed through so that its cotangent comes
    back once), the other maps XLA's on the logits' planes."""
    with jax.named_scope(scopes.MHC_MAPS):
        x, u, m = kernels.mix(x, p[prefix + PHI], p[prefix + ALPHA][0],
                              p[prefix + BIAS][:hc.n], hc.norm_eps)
        return x, u, _maps_of(m, p, prefix, hc)


def joined(x: jax.Array, y: jax.Array, h: Maps) -> jax.Array:
    """``write_back`` as one kernel: each stream of x' written in place."""
    return kernels.write_back(x, y, h.post, h.res)


def expand(x: jax.Array, n: int) -> jax.Array:
    """x [B, S, C] → the stream [B, S, n·C] whose every stream is x."""
    return jnp.concatenate([x] * n, axis=-1)


def collapse(x: jax.Array, n: int) -> jax.Array:
    """The stream [B, S, n·C] → Σ_i x[i] [B, S, C], a float32 sum in x's
    dtype."""
    return sum(_streams(x, n)).astype(x.dtype)


def record_decision(hc: HyperConnection, width: int, dtype) -> None:
    """The ``model/hyper_connection`` event of a model whose residual path is
    this: once per distinct decision, at trace time."""
    dtype = jnp.dtype(dtype)
    key = (hc, width, dtype.name)
    if key in _decisions:
        return
    _decisions[key] = dict(zip(scopes.HYPER_CONNECTION_ARGS, (
        hc.n, hc.rounds, dtype.name, hc.n * width * dtype.itemsize)))
    component, name = scopes.HYPER_CONNECTION.split("/")
    get_buffer().record_profile(name, component=component,
                                args=_decisions[key])


def decisions():
    """Every distinct hyper-connection this process has traced a model with,
    as the ``model/hyper_connection`` events carry them."""
    return list(_decisions.values())
