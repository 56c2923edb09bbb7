"""EvaByte, forward and loss, in straight ``jax.numpy`` and float32.

The benchmark's plain reference for family ``evabyte`` (EvaByte 6.5B,
https://huggingface.co/EvaByte/EvaByte; EVA attention: Zheng et al.,
"Efficient Attention via Control Variates", arXiv:2302.04542): no kernel, no
cache, no sharding rule, no mixed precision, nothing imported from
``ray_tpu``. The caller sets ``jax.default_matmul_precision("highest")``.

The equations (``sizes`` holds n_head, eps, theta, window w, chunk c, heads P):

- ``norm(x; g) = x · rsqrt(mean(x²) + eps) · (1 + g)``.
- block: ``x += Wo · EVA(rope(Wq n), rope(Wk n), Wv n)``, ``n = norm(x; g1)``;
  ``x += Wd · (silu(Wg m) ⊙ (Wu m))``, ``m = norm(x; g2)``. No biases. RoPE in
  the rotate-half convention.
- EVA, per head with learned phi, mu and s = hd^-1/2. Chunk j over positions
  T_j = {c·j … c·j + c − 1}: ``alpha = softmax_{m in T_j}(s · phi·k_m)``,
  ``kt_j = Σ alpha_m k_m + mu``, ``vt_j = Σ alpha_m v_m``. Query t in window
  i = t // w: local keys {m : i·w <= m <= t}, remote summaries
  {j : j < i·w/c}; one softmax over both, values v_m and vt_j.
- loss: after ``norm(x; g_f)`` head p gives ``logits_p = W_p x_t`` and predicts
  byte t + 1 + p: ``targets_p[t] = targets[t + p]``, ignored (−1) past the
  row's end; the mean over p of the mean over valid t of the cross-entropy.

It reads the program's parameter tree as the program lays it out (layers
stacked on a leading axis; ``wq`` as ``[L, D, H, hd]``, ``wo`` as
``[L, H, hd, D]``, ``lm_head`` as ``[D, P · V]``, head p in columns
``p·V … (p+1)·V``), because the comparison is on the program's own seeded
weights. So that it fits beside the step's state at published widths the
attention runs a window of eight heads at a time, the MLP in blocks of the
sequence and each layer under ``jax.checkpoint``: the same numbers in another
order.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

MLP_BLOCK = 4096          # rows of the sequence the MLP takes at a time
HEAD_BLOCK = 8            # heads the attention takes at a time


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + g)


def _rope(x, theta):
    """x [B, H, S, hd]; rotate-half."""
    seq, hd = x.shape[-2], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def summaries(k, v, phi, mu, chunk):
    """k, v [B, H, S, hd]; phi, mu [H, hd] -> kt, vt [B, H, S/chunk, hd]."""
    b, h, s, hd = k.shape
    kc = k.reshape(b, h, s // chunk, chunk, hd)
    vc = v.reshape(b, h, s // chunk, chunk, hd)
    alpha = jax.nn.softmax(
        jnp.einsum("bhjcd,hd->bhjc", kc, phi) / math.sqrt(hd), axis=-1)
    kt = jnp.einsum("bhjc,bhjcd->bhjd", alpha, kc) + mu[None, :, None, :]
    vt = jnp.einsum("bhjc,bhjcd->bhjd", alpha, vc)
    return kt, vt


def eva_attention(q, k, v, phi, mu, window, chunk, with_summaries=True):
    """q, k, v [B, H, S, hd] (rotated) -> [B, H, S, hd]. One window of
    HEAD_BLOCK heads at a time; ``with_summaries=False`` drops the remote
    term (what a tolerance must catch, never what the model is)."""
    b, h, s, hd = q.shape
    n_win, per_win = s // window, window // chunk
    hb = HEAD_BLOCK if h % HEAD_BLOCK == 0 else h
    kt, vt = summaries(k, v, phi, mu, chunk)
    n_sum = kt.shape[2]
    causal = jnp.tril(jnp.ones((window, window), bool))

    def one(index):
        g, i = index // n_win, index % n_win
        heads = lambda x: jax.lax.dynamic_slice_in_dim(x, g * hb, hb, axis=1)
        at = lambda x: jax.lax.dynamic_slice_in_dim(heads(x), i * window, window, axis=2)
        qw, kw, vw = at(q), at(k), at(v)
        local = jnp.einsum("bhqd,bhkd->bhqk", qw, kw) / math.sqrt(hd)
        local = jnp.where(causal, local, -jnp.inf)
        remote = jnp.einsum("bhqd,bhjd->bhqj", qw, heads(kt)) / math.sqrt(hd)
        seen = (jnp.arange(n_sum) < i * per_win) & with_summaries
        remote = jnp.where(seen[None, None, None, :], remote, -jnp.inf)
        probs = jax.nn.softmax(jnp.concatenate([remote, local], axis=-1), axis=-1)
        return (jnp.einsum("bhqj,bhjd->bhqd", probs[..., :n_sum], heads(vt))
                + jnp.einsum("bhqk,bhkd->bhqd", probs[..., n_sum:], vw))

    out = jax.lax.map(jax.checkpoint(one), jnp.arange(h // hb * n_win))
    out = out.reshape(h // hb, n_win, b, hb, window, hd)      # [G, W, B, hb, w, hd]
    return out.transpose(2, 0, 3, 1, 4, 5).reshape(b, h, s, hd)


def _mlp(m, wg, wu, wd):
    """silu(m Wg) ⊙ (m Wu) through Wd, MLP_BLOCK rows of the sequence at a time."""
    b, s, d = m.shape
    block = MLP_BLOCK if s % MLP_BLOCK == 0 else s

    def one(mb):
        return jnp.matmul(jax.nn.silu(jnp.matmul(mb, wg)) * jnp.matmul(mb, wu), wd)

    out = jax.lax.map(jax.checkpoint(one),
                      m.reshape(b, s // block, block, d).swapaxes(0, 1))
    return out.swapaxes(0, 1).reshape(b, s, d)


def hidden(params, tokens, sizes, with_summaries=True):
    """tokens [B, S] int32 -> the final norm's output [B, S, D], float32."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    eps, theta = sizes["eps"], sizes["theta"]
    x = f32(params["wte"])[tokens]

    @jax.checkpoint
    def block(x, p):
        p = {k: f32(v) for k, v in p.items()}
        n = _norm(x, p["attn_norm"], eps)
        q = _rope(jnp.einsum("bsd,dhk->bhsk", n, p["wq"]), theta)
        k = _rope(jnp.einsum("bsd,dhk->bhsk", n, p["wk"]), theta)
        v = jnp.einsum("bsd,dhk->bhsk", n, p["wv"])
        attn = eva_attention(q, k, v, p["eva_phi"], p["eva_mu"],
                             sizes["window"], sizes["chunk"], with_summaries)
        x = x + jnp.einsum("bhsk,hkd->bsd", attn, p["wo"])
        m = _norm(x, p["mlp_norm"], eps)
        return x + _mlp(m, p["w_gate"], p["w_up"], p["w_down"]), None

    x, _ = jax.lax.scan(block, x, params["blocks"])
    return _norm(x, f32(params["final_norm"]), eps)


def head_targets(targets, n_heads):
    """targets [B, S] (next byte, -1 = ignore) -> [P, B, S]: head p's target at
    t is targets[t + p], -1 past the row's end."""
    s = targets.shape[1]
    padded = jnp.pad(targets, ((0, 0), (0, n_heads)), constant_values=-1)
    return jnp.stack([padded[:, p:p + s] for p in range(n_heads)])


def loss(params, tokens, targets, sizes, with_summaries=True):
    """The mean over the heads of each head's mean cross-entropy."""
    x = hidden(params, tokens, sizes, with_summaries)
    n_heads = sizes["n_pred_heads"]
    b, s, _ = x.shape
    logits = jnp.matmul(x, jnp.asarray(params["lm_head"], jnp.float32)).reshape(
        b, s, n_heads, -1)
    logp = jax.nn.log_softmax(logits, axis=-1)
    tp = jnp.moveaxis(head_targets(targets, n_heads), 0, 2)      # [B, S, P]
    valid = tp >= 0
    picked = jnp.take_along_axis(
        logp, jnp.where(valid, tp, 0)[..., None], axis=-1)[..., 0]
    per_head = (-jnp.sum(jnp.where(valid, picked, 0.0), axis=(0, 1))
                / jnp.maximum(jnp.sum(valid, axis=(0, 1)), 1))
    return jnp.mean(per_head)
