"""Ring attention: exact causal attention over a context-parallel mesh axis.

Sequence parallelism for long contexts — each device holds an S/cp slice of the
sequence; K/V chunks rotate around the `cp` ring via `lax.ppermute` while every
device's queries stay put. After cp steps each query has attended to the full
(causal) sequence. Communication rides the ICI ring; compute per step is the
Pallas flash kernel over one (q-chunk, kv-chunk) pair.

Numerics: per-step partial outputs are merged with the standard logsumexp
reweighting (m = max(lse1, lse2); o = o1·e^(lse1−m) + o2·e^(lse2−m), scaled by
the combined denominator) — the same math `tests/test_flash_attention.py`
validates against the monolithic kernel. The backward pass rotates (k, v) a
second time with f32 (dk, dv) accumulators traveling alongside, so after cp
rotations each gradient chunk lands back on its owner; dq accumulates locally.
Chunk-level backward uses the GLOBAL lse and delta = rowsum(do·o) (flash
attention's decomposition is exact over kv chunks).

The reference has no sequence-parallel story at all (SURVEY.md §2.10 — grep
for ring/sequence/context parallelism matches nothing); this is new TPU-native
work. Offsets/lse plumbing provided by ops/attention.py
(`flash_attention_with_lse`, `mha_backward_chunk`).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.attention import (
    batch_head_axes,
    flash_attention_with_lse,
    mha_backward_chunk,
    resolve_attention,
)

_NEG_INF = -1e30  # matches ops/attention.py's mask value


def _merge(o1, lse1, o2, lse2):
    """Combine two attention partials by logsumexp weights.

    o*: [B, S, H, hd] (f32), lse*: [B, H, S] (f32). Rows where both partials
    are empty (lse == -1e30, ring steps fully in the causal future) stay zero.
    """
    m = jnp.maximum(lse1, lse2)
    e1 = jnp.exp(lse1 - m)
    e2 = jnp.exp(lse2 - m)
    denom = e1 + e2
    lse = m + jnp.log(denom)
    # [B, H, S] → [B, S, H, 1] to weight the [B, S, H, hd] outputs
    w1 = jnp.swapaxes(e1 / denom, 1, 2)[..., None]
    w2 = jnp.swapaxes(e2 / denom, 1, 2)[..., None]
    return o1 * w1 + o2 * w2, lse


def _rotate(arrays, axis_name, perm):
    return tuple(lax.ppermute(a, axis_name, perm) for a in arrays)


def _ring_forward(
    q, k, v, axis_name, causal, scale, block_q, block_k, interpret
) -> Tuple[jax.Array, jax.Array]:
    """Inside shard_map: local q/k/v [B, S_local, H, hd] → (o f32, lse f32)."""
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    B, S, H, _ = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    o = jnp.zeros(q.shape, jnp.float32)
    lse = jnp.full((B, H, S), _NEG_INF, jnp.float32)
    kk, vv = k, v
    for step in range(n):
        # kv chunk currently held: rotated right `step` times → origin idx-step
        src = (idx - step) % n
        o_c, lse_c = flash_attention_with_lse(
            q, kk, vv,
            q_offset=idx * S, kv_offset=src * S,
            causal=causal, scale=scale,
            block_q=block_q, block_k=block_k, interpret=interpret,
        )
        o, lse = _merge(o, lse, o_c.astype(jnp.float32), lse_c)
        if step != n - 1:
            kk, vv = _rotate((kk, vv), axis_name, perm)
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring(q, k, v, axis_name, causal, scale, block_q, block_k, interpret):
    o, _ = _ring_forward(
        q, k, v, axis_name, causal, scale, block_q, block_k, interpret
    )
    return o.astype(q.dtype)


def _ring_fwd(q, k, v, axis_name, causal, scale, block_q, block_k, interpret):
    o, lse = _ring_forward(
        q, k, v, axis_name, causal, scale, block_q, block_k, interpret
    )
    o = o.astype(q.dtype)
    return o, (q, k, v, o, lse)


def _ring_bwd(axis_name, causal, scale, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    S = q.shape[1]
    perm = [(i, (i + 1) % n) for i in range(n)]

    dq = jnp.zeros(q.shape, jnp.float32)
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    kk, vv = k, v
    for step in range(n):
        src = (idx - step) % n
        dq_c, dk_c, dv_c = mha_backward_chunk(
            q, kk, vv, o, lse, do,
            q_offset=idx * S, kv_offset=src * S,
            causal=causal, scale=scale,
            block_q=block_q, block_k=block_k, interpret=interpret,
        )
        dq = dq + dq_c.astype(jnp.float32)
        dk = dk + dk_c.astype(jnp.float32)
        dv = dv + dv_c.astype(jnp.float32)
        # (dk, dv) travel with their kv chunk; the final rotation returns each
        # chunk's gradient to its owning device — k/v themselves don't need it.
        if step != n - 1:
            kk, vv, dk, dv = _rotate((kk, vv, dk, dv), axis_name, perm)
        else:
            dk, dv = _rotate((dk, dv), axis_name, perm)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring.defvjp(_ring_fwd, _ring_bwd)


def _refuse_window(window: Optional[int]) -> None:
    """The ring carries WHOLE rows of kv round every device, a step a chunk:
    under a window most of those steps would carry keys no query of the
    device sees, and skipping them is a schedule this ring does not have."""
    if window is not None:
        raise NotImplementedError(
            f"window={window}: ring attention (a cp axis) has no windowed "
            "schedule — the ring carries whole rows of kv; use a cp=1 mesh "
            "(ops/attention.flash_attention takes the window)")


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = "cp",
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Ring attention over `axis_name`. Must run where the axis is bound
    (inside shard_map/pmap); q, k, v are the LOCAL sequence shards
    [B, S_local, H, hd]. Differentiable (custom VJP, ring backward). A
    ``window`` is refused (_refuse_window)."""
    _refuse_window(window)
    if interpret is None:
        _, interpret = resolve_attention()
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _ring(q, k, v, axis_name, causal, scale, block_q, block_k, interpret)


def ring_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    axis_name: str = "cp",
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Ring attention for callers under jit/GSPMD (the GPT-2 forward): wraps
    the ring in a shard_map over `mesh` with batch on (dp, fsdp), sequence on
    `axis_name`, heads on tp (ops/attention.batch_head_axes). GLOBAL-length
    q/k/v in, global out. A ``window`` is refused (_refuse_window)."""
    _refuse_window(window)
    if interpret is None:
        _, interpret = resolve_attention(mesh=mesh)
    cp = mesh.shape.get(axis_name, 1)
    if q.shape[1] % cp:
        raise ValueError(
            f"sequence length {q.shape[1]} not divisible by {axis_name} axis "
            f"size {cp}; pad the sequence or change the mesh"
        )
    batch_axes, head_ax = batch_head_axes(mesh, q.shape[0], q.shape[2])
    spec = P(batch_axes, axis_name, head_ax, None)
    fn = jax.shard_map(
        functools.partial(
            ring_attention,
            axis_name=axis_name, causal=causal, scale=scale,
            block_q=block_q, block_k=block_k, interpret=interpret,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
