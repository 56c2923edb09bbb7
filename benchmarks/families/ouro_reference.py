"""Ouro (a looped language model), forward and objective, in straight
``jax.numpy`` and float32.

The benchmark's plain reference for family ``ouro`` (Ouro-2.6B,
https://huggingface.co/ByteDance/Ouro-2.6B; "Scaling Latent Reasoning via
Looped Language Models", arXiv:2510.25741): no kernel, no sharding rule, no
mixed precision, nothing imported from ``ray_tpu``. The caller sets
``jax.default_matmul_precision("highest")``. Written from the equations of the
configuration's file (``assumed`` (a) - (f)), not from the program.

The equations (``sizes`` holds eps, theta, the passes T and beta):

- ``norm(x; g) = x · rsqrt(mean(x²) + eps) · g``.
- x⁰ = wte[tokens]. For pass t = 1 … T, for layer l = 1 … L — the SAME L
  weight sets in every pass —:
  ``a = x + norm(Wo · Attn(rope(Wq n), rope(Wk n), Wv n); g_in2)``, ``n =
  norm(x; g_in)``; ``x' = a + norm(Wd · (silu(Wg m) ⊙ (Wu m)); g_post2)``,
  ``m = norm(a; g_post)``. No biases. Attn: causal softmax at scale hd^-1/2 a
  head; RoPE in the rotate-half convention over the whole head at positions
  0 … S−1, the same in every pass.
- after layer L of pass t: ``h_t = norm(x; g_f)`` and ``x ← h_t`` — the
  NORMED state is what pass t + 1 starts from.
- gate: ``λ_t = sigmoid(h_t · w + b)`` a token; exit distribution ``p_t =
  λ_t · Π_{j<t} (1 − λ_j)`` for t < T, ``p_T = Π_{j<T} (1 − λ_j)``.
- ``nll_t(i) = −log softmax(h_t(i) · W_head)[y_i]``: ONE head for all passes.
- ``loss = 1/N · Σ_i [ Σ_t p_t(i) · nll_t(i) − beta · H(p(i)) ]`` over the N
  valid targets, ``H(p) = −Σ_t p_t log p_t`` (log p summed from log λ and
  log(1 − λ), each a ``log_sigmoid``: float32 holds no log of a product that
  has rounded to 0).

It reads the program's parameter tree as the program lays it out (layers
stacked on a leading axis; ``wq`` as ``[L, D, H, hd]``, ``wo`` as
``[L, H, hd, D]``, ``lm_head`` as ``[D, V]``, the gate as ``exit_w`` [D] and
``exit_b`` [1]), because the comparison is on the program's own seeded weights.

Departures from the published description, each for room beside the step's
state at the published widths (the same numbers in another order): every
layer application stands under ``jax.checkpoint``; attention takes
QUERY_BLOCK query rows at a time, the MLP MLP_BLOCK rows, the head HEAD_BLOCK
rows, each block under a ``checkpoint`` of its own; the layers of a pass are
walked by a ``lax.scan`` and so are the passes (a Python loop over 4 x L
applications is the same arithmetic and a program 4 x L times the size: its
compile alone would outlast the cell's set-up).

Switches, for the tests and for the readings a limit must refuse — never for
what the model is:

- ``untied``: ``params["blocks"]`` holds T parameter sets ``[T, L, ...]``,
  pass t reads set t (the gradient of a shared layer must be the sum over
  the passes of these);
- ``operand_dtype``: every forward matmul's operands rounded to it, one scale
  a tensor; ``fewer_passes``: run so many passes fewer than T; ``drop_pass``:
  the shared weights' gradient without that pass's contribution (its forward
  as it is); ``beta``: the entropy's weight; ``carry_normed=False``: pass
  t + 1 starts from the un-normed x (the head and the gate still read h_t).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024        # query rows the attention takes at a time
MLP_BLOCK = 2048          # rows of the sequence the MLP takes at a time
HEAD_BLOCK = 1024         # rows of the sequence the head takes at a time


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rounded(x, dtype):
    """x as ``dtype`` holds it (one scale a tensor: the largest magnitude at
    the top of the type's binades), its gradient passed on. By
    ``lax.reduce_precision``: a cast there and back is one the TPU compiler
    may leave out."""
    if dtype is None:
        return x
    info = jnp.finfo(dtype)
    scale = jnp.max(jnp.abs(x)) / 2.0 ** (2 ** (info.nexp - 1) - 1)
    q = jax.lax.reduce_precision(x / scale, exponent_bits=info.nexp,
                                 mantissa_bits=info.nmant) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec, a, b, sizes):
    dtype = sizes.get("operand_dtype")
    return jnp.einsum(spec, _rounded(a, dtype), _rounded(b, dtype))


def _rope(x, theta):
    """x [B, H, S, hd]; rotate-half over the whole head, positions 0 … S−1."""
    seq, hd = x.shape[-2], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _in_blocks(fn, x, axis: int, block: int):
    """``fn`` over blocks of ``block`` along ``axis`` of x (the whole axis
    where it does not divide), each under ``jax.checkpoint``; ``fn(block of
    x, index of its first element)``'s results joined along that axis."""
    size = x.shape[axis]
    if size % block:
        block = size
    moved = jnp.moveaxis(x, axis, 0)
    cut = moved.reshape((size // block, block) + moved.shape[1:])
    starts = jnp.arange(size // block) * block

    def one(args):
        part, start = args
        return jnp.moveaxis(fn(jnp.moveaxis(part, 0, axis), start), axis, 0)

    out = jax.lax.map(jax.checkpoint(one), (cut, starts))
    return jnp.moveaxis(out.reshape((size,) + out.shape[2:]), 0, axis)


def _attention(q, k, v, sizes):
    """q, k, v [B, H, S, hd] (q and k rotated) → [B, H, S, hd]: causal
    softmax at scale hd^-1/2, QUERY_BLOCK query rows at a time."""
    hd, keys = q.shape[-1], jnp.arange(k.shape[2])

    def rows(q_rows, start):
        logits = _mm("bhqd,bhkd->bhqk", q_rows, k, sizes) / math.sqrt(hd)
        at = start + jnp.arange(q_rows.shape[2])
        logits = jnp.where(keys[None, :] <= at[:, None], logits, -jnp.inf)
        return _mm("bhqk,bhkd->bhqd", jax.nn.softmax(logits, axis=-1), v,
                   sizes)

    return _in_blocks(rows, q, 2, QUERY_BLOCK)


def _layer(x, p, sizes):
    """One layer application: x [B, S, D] → x'."""
    eps, theta = sizes["eps"], sizes["theta"]
    n = _norm(x, p["attn_norm"], eps)
    q = _rope(_mm("bsd,dhk->bhsk", n, p["wq"], sizes), theta)
    k = _rope(_mm("bsd,dhk->bhsk", n, p["wk"], sizes), theta)
    v = _mm("bsd,dhk->bhsk", n, p["wv"], sizes)
    attn = _mm("bhsk,hkd->bsd", _attention(q, k, v, sizes), p["wo"], sizes)
    a = x + _norm(attn, p["attn_out_norm"], eps)

    def mlp(rows, _):
        m = _norm(rows, p["mlp_norm"], eps)
        hidden = (jax.nn.silu(_mm("bsd,df->bsf", m, p["w_gate"], sizes))
                  * _mm("bsd,df->bsf", m, p["w_up"], sizes))
        y = _mm("bsf,fd->bsd", hidden, p["w_down"], sizes)
        return rows + _norm(y, p["mlp_out_norm"], eps)

    return _in_blocks(mlp, a, 1, MLP_BLOCK)


def states(params, tokens, sizes):
    """tokens [B, S] int32 → the passes' loop-end states h_t, [T, B, S, D]."""
    f32 = lambda tree: jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)
    passes = sizes["ut_steps"] - sizes.get("fewer_passes", 0)
    untied = sizes.get("untied", False)
    drop = sizes.get("drop_pass")
    blocks, g_f = f32(params["blocks"]), f32(params["final_norm"])
    x = f32(params["wte"])[tokens]

    def one_pass(x, of_pass):
        t, own = of_pass

        @jax.checkpoint
        def layer(x, p):
            if drop is not None:
                # the same numbers, no gradient through pass `drop`'s use
                p = jax.tree.map(lambda a: jnp.where(
                    t == drop, jax.lax.stop_gradient(a), a), p)
            return _layer(x, p, sizes), None

        x, _ = jax.lax.scan(layer, x, own if untied else blocks)
        h = _norm(x, g_f, sizes["eps"])
        return (h if sizes.get("carry_normed", True) else x), h

    own = blocks if untied else None
    if untied:
        own = jax.tree.map(lambda a: a[:passes], own)
    _, hs = jax.lax.scan(one_pass, x, (jnp.arange(passes), own))
    return hs


def exit_distribution(hs, params):
    """h_t [T, B, S, D] → (p, log p), each [T, B, S]."""
    w = jnp.asarray(params["exit_w"], jnp.float32)
    b = jnp.asarray(params["exit_b"], jnp.float32)
    logit = jnp.einsum("tbsd,d->tbs", hs, w) + b
    log_lam, log_stay = jax.nn.log_sigmoid(logit), jax.nn.log_sigmoid(-logit)
    T = hs.shape[0]
    log_p = []
    for t in range(T):
        before = sum(log_stay[j] for j in range(t)) if t else 0.0
        log_p.append(before + (log_lam[t] if t < T - 1 else 0.0)
                     + jnp.zeros_like(logit[0]))
    log_p = jnp.stack(log_p)
    return jnp.exp(log_p), log_p


def _nll(hs, targets, lm_head, sizes):
    """−log softmax(h · W_head)[y] a pass and token, [T, B, S] (0 where the
    target is < 0), HEAD_BLOCK rows of the sequence at a time."""
    safe = jnp.where(targets >= 0, targets, 0)

    def rows(h_rows, start):
        logp = jax.nn.log_softmax(
            _mm("tbsd,dv->tbsv", h_rows, lm_head, sizes), axis=-1)
        y = jax.lax.dynamic_slice_in_dim(safe, start, h_rows.shape[2], axis=1)
        return -jnp.take_along_axis(
            logp, jnp.broadcast_to(y, logp.shape[:3])[..., None], axis=-1)

    nll = _in_blocks(rows, hs, 2, HEAD_BLOCK)[..., 0]
    return jnp.where(targets >= 0, nll, 0.0)


def loss_parts(params, tokens, targets, sizes):
    """(the objective, the task term, the entropy's mean, the mean exit
    distribution [T])."""
    hs = states(params, tokens, sizes)
    p, log_p = exit_distribution(hs, params)
    nll = _nll(hs, targets, jnp.asarray(params["lm_head"], jnp.float32), sizes)
    valid = targets >= 0
    n = jnp.maximum(jnp.sum(valid), 1)
    task = jnp.sum(jnp.where(valid, jnp.sum(p * nll, axis=0), 0.0)) / n
    entropy = jnp.sum(jnp.where(valid, -jnp.sum(p * log_p, axis=0), 0.0)) / n
    mean_p = jnp.sum(jnp.where(valid, p, 0.0), axis=(1, 2)) / n
    return task - sizes["beta"] * entropy, task, entropy, mean_p


def loss(params, tokens, targets, sizes):
    return loss_parts(params, tokens, targets, sizes)[0]
