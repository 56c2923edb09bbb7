"""Nemotron-H (Mamba-2 / LatentMoE / attention hybrid with one multi-token-
prediction module), forward and both losses, in straight ``jax.numpy`` and
float32.

The benchmark's plain reference for family ``nemotron_h``
(NVIDIA-Nemotron-3-Super-120B-A12B, ``model_type: nemotron_h``;
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16; Mamba-2:
Dao & Gu, "Transformers are SSMs", arXiv:2405.21060): no kernel, no chunked
scan, no sort or grouped product, no mixed precision, nothing imported from
``ray_tpu``. The caller sets ``jax.default_matmul_precision("highest")``.

Every layer is ``x ← x + f_kind(norm(x; g))``, ``norm(x; g) = x ·
rsqrt(mean(x²) + eps) · g``, the kind from ``sizes["pattern"]``:

- ``M`` (Mamba-2; H heads of P, state N, G groups): ``z = u·W_z``, ``xBC =
  silu(conv(u·W_xbc) + b)`` (causal, depthwise, the last tap the current
  token), split into x [H, P], B, C [G, N]; ``Δ = softplus(u·W_dt + dt_bias)``,
  ``a = exp(Δ·A)``, ``A = −exp(A_log)``; **token by token**
  ``h_t = a_t·h_{t−1} + Δ_t·x_t ⊗ B_t`` (h_0 = 0 at the row's start),
  ``y_t = h_t·C_t + D·x_t``; ``y ← norm_per_group(y ⊙ silu(z))·g_n``;
  ``f = y·W_out``.
- ``E`` (LatentMoE): ``s = sigmoid(u·W_r)``; the top_k largest of ``s + b``
  are chosen (b chooses only); ``w_e = scaling · s_e / Σ_chosen s``;
  ``ℓ = u·W_down``; ``r = Σ_{e chosen and held} w_e · relu(ℓ·W1_e)² · W2_e``
  — every held expert on every token, the gates the mask; ``f = r·W_up +
  relu(u·S1)²·S2``.
- ``*``: ``q, k, v = u·W_q, u·W_k, u·W_v`` (a key-value head shared by
  n_head / n_kv_head query heads), causal ``softmax(q kᵀ/√hd)·v``, ``f =
  o·W_o``; no positional encoding, no bias.
- end: ``norm`` → head, cross-entropy. MTP: ``h′_t = W_eh·[norm(emb(tok_{t+1});
  g_e); norm(x_t; g_h)]`` (x the trunk's stream before its final norm), the
  module's layers, the SAME final norm and head, predicting ``tok_{t+2}``;
  ``loss = CE_trunk + mtp_weight · CE_mtp``, positions with no target ignored.

Departures from a whole model, the same in the program: only the experts
``held_first … held_first + held_count − 1`` and the heads whose weights are
in the tree are computed — what absent experts and heads would add is left
out, and the out-projections' partial sums go on as they are. ``b`` is a
buffer, the expert layer's ``router_bias``: no gradient reaches it.

It reads the program's parameter tree as the program lays it out (one entry
a run of a repeated sub-pattern, ``_groups``; a kind's layers of the run
stacked on a leading axis in the order they come), and walks a run with a
``lax.scan`` over its repeats — one copy of the sub-pattern's layers in the
compiled reference, not one a layer. Each layer runs under
``jax.checkpoint`` so that a 4,096-token row and its gradient fit beside the
step's state: the same numbers, made twice.

Three switches exist for the readings a tolerance must catch, never for what
the model is: ``drop_routed`` (the routed experts left out), ``mtp_weight``
(0: the second loss left out), ``operand_dtype`` (the forward matmuls'
operands rounded to a narrower type, one scale a tensor).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

SCAN_BLOCK = 128          # tokens of the recurrence under one jax.checkpoint


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rounded(x, dtype):
    """x as ``dtype`` holds it (one scale a tensor), its gradient passed on."""
    if dtype is None:
        return x
    scale = jnp.max(jnp.abs(x)) / float(jnp.finfo(dtype).max)
    q = (x / scale).astype(dtype).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec, a, b, sizes):
    dtype = sizes.get("operand_dtype")
    return jnp.einsum(spec, _rounded(a, dtype), _rounded(b, dtype))


def mamba(u, p, sizes):
    """u [B, S, D] → [B, S, D]: the recurrence, one token at a time."""
    b, s, _ = u.shape
    heads, groups = p["A_log"].shape[0], sizes["mamba_groups"]
    inner = p["w_z"].shape[1]
    hd = inner // heads
    n = (p["w_xbc"].shape[1] - inner) // (2 * groups)
    z = _mm("bsd,de->bse", u, p["w_z"], sizes)
    xbc = _mm("bsd,de->bse", u, p["w_xbc"], sizes)
    dt = _mm("bsd,dh->bsh", u, p["w_dt"], sizes)
    taps = p["conv_w"].shape[0]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = p["conv_b"] + sum(padded[:, k:k + s] * p["conv_w"][k]
                             for k in range(taps))
    xbc = jax.nn.silu(conv)
    x = xbc[..., :inner].reshape(b, s, groups, heads // groups, hd)
    bm = xbc[..., inner:inner + groups * n].reshape(b, s, groups, n)
    cm = xbc[..., inner + groups * n:].reshape(b, s, groups, n)
    delta = jax.nn.softplus(dt + p["dt_bias"]).reshape(
        b, s, groups, heads // groups)
    decay = jnp.exp(delta * -jnp.exp(p["A_log"]).reshape(groups, -1))

    def step(h, t):
        x_t, b_t, c_t, a_t, d_t = t
        h = (a_t[..., None, None] * h
             + (d_t[..., None] * x_t)[..., None] * b_t[:, :, None, None, :])
        return h, jnp.einsum("bghpn,bgn->bghp", h, c_t)

    # still one token at a time; the row goes in blocks of SCAN_BLOCK tokens,
    # each under jax.checkpoint, so that the backward keeps one state a block
    # and not one a token (2 GB a layer at 4,096 tokens)
    blk = math.gcd(s, SCAN_BLOCK)

    @jax.checkpoint
    def block(h, ts):
        return jax.lax.scan(step, h, ts)

    h0 = jnp.zeros((b, groups, heads // groups, hd, n), jnp.float32)
    _, y = jax.lax.scan(block, h0, tuple(
        jnp.moveaxis(t, 1, 0).reshape((s // blk, blk) + t.shape[:1] + t.shape[2:])
        for t in (x, bm, cm, decay, delta)))
    y = jnp.moveaxis(y.reshape((s,) + y.shape[2:]), 0, 1)
    y = y + x * p["D"].reshape(groups, -1)[..., None]
    y = y.reshape(b, s, groups, inner // groups) * jax.nn.silu(z).reshape(
        b, s, groups, inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + sizes["eps"])
    y = y.reshape(b, s, inner) * p["gate_norm"]
    return _mm("bse,ed->bsd", y, p["w_out"], sizes)


def routed_weights(u, router_w, bias, sizes):
    """u [T, D] → w [T, n_experts]: a token's gate on each expert it chose,
    0 on the others (float32 throughout, whatever ``operand_dtype``)."""
    s = jax.nn.sigmoid(u @ router_w)
    _, idx = jax.lax.top_k(s + bias, sizes["top_k"])
    chosen = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=s.dtype), axis=1)
    return sizes["scaling"] * s * chosen / jnp.sum(s * chosen, -1, keepdims=True)


def latent_moe(u, p, sizes):
    b, s, d = u.shape
    ut = u.reshape(b * s, d)
    out = _mm("tf,fd->td", jnp.square(jax.nn.relu(
        _mm("td,df->tf", ut, p["shared_w1"], sizes))), p["shared_w2"], sizes)
    if not sizes.get("drop_routed"):
        w = routed_weights(ut, p["router_w"], p["router_bias"], sizes)
        held = w[:, sizes["held_first"]:sizes["held_first"] + p["w1"].shape[0]]
        ell = _mm("td,dl->tl", ut, p["w_down"], sizes)
        # every held expert on every token, the gates (0 where the token did
        # not choose it) the mask
        h = jnp.square(jax.nn.relu(_mm("tl,elf->etf", ell, p["w1"], sizes)))
        r = jnp.einsum("etl,te->tl", _mm("etf,efl->etl", h, p["w2"], sizes),
                       held)
        out = out + _mm("tl,ld->td", r, p["w_up"], sizes)
    return out.reshape(b, s, d)


def attention(u, p, sizes):
    q = _mm("bsd,dhk->bhsk", u, p["wq"], sizes)
    k = _mm("bsd,dhk->bhsk", u, p["wk"], sizes)
    v = _mm("bsd,dhk->bhsk", u, p["wv"], sizes)
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = q.shape[2]
    logits = _mm("bhqd,bhkd->bhqk", q, k, sizes) / math.sqrt(q.shape[-1])
    logits = jnp.where(jnp.tril(jnp.ones((s, s), bool)), logits, -jnp.inf)
    o = _mm("bhqk,bhkd->bhqd", jax.nn.softmax(logits, axis=-1), v, sizes)
    return _mm("bhsk,hkd->bsd", o, p["wo"], sizes)


def _groups(pattern):
    """A pattern as runs of a repeated sub-pattern, as the program stacks its
    layers (greedy from the left: the repeat that covers most layers, of
    equal ones the shortest sub-pattern): ``"MEMEMEMEM*E"`` →
    ``[("ME", 4), ("M", 1), ("*", 1), ("E", 1)]``."""
    groups, i = [], 0
    while i < len(pattern):
        best = (pattern[i], 1)
        for width in range(1, (len(pattern) - i) // 2 + 1):
            sub, reps = pattern[i:i + width], 1
            while pattern.startswith(sub, i + reps * width):
                reps += 1
            if reps > 1 and reps * width > best[1] * len(best[0]):
                best = (sub, reps)
        groups.append(best)
        i += best[1] * len(best[0])
    return groups


def _layer(x, p, kind, sizes):
    u = _norm(x, p["norm"], sizes["eps"])
    f = {"M": mamba, "E": latent_moe, "*": attention}[kind]
    return x + f(u, p, sizes)


def layers(x, pattern, stacks, sizes):
    """x through ``pattern``'s layers, one at a time, each under
    ``jax.checkpoint``; ``stacks[g][kind]`` stacks run g's layers of a kind,
    and the run's repeats are the steps of one ``lax.scan``."""
    for (sub, reps), group in zip(_groups(pattern), stacks, strict=True):
        def repeat(x, of_kind, sub=sub):
            seen = dict.fromkeys(of_kind, 0)
            for kind in sub:
                p = jax.tree.map(lambda t: t[seen[kind]], of_kind[kind])
                seen[kind] += 1
                x = jax.checkpoint(
                    functools.partial(_layer, kind=kind, sizes=sizes))(x, p)
            return x, None

        x, _ = jax.lax.scan(repeat, x, {
            kind: jax.tree.map(
                lambda t: t.reshape((reps, -1) + t.shape[1:]), stack)
            for kind, stack in group.items()})
    return x


def _cross_entropy(x, targets, params, sizes):
    x = _norm(x, params["final_norm"], sizes["eps"])
    logp = jax.nn.log_softmax(_mm("bsd,dv->bsv", x, params["lm_head"], sizes))
    mask = targets >= 0
    nll = -jnp.take_along_axis(
        logp, jnp.where(mask, targets, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)


def losses(params, tokens, targets, sizes):
    """(CE_trunk, CE_mtp) of tokens / targets [B, S] (targets: the next
    token, −1 = none)."""
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    x = layers(params["wte"][tokens], sizes["pattern"], params["blocks"], sizes)
    trunk = _cross_entropy(x, targets, params, sizes)
    if not sizes["mtp_pattern"]:
        return trunk, jnp.zeros((), jnp.float32)
    mtp = params["mtp"]
    has_next = targets >= 0
    later = jnp.concatenate(
        [targets[:, 1:], jnp.full_like(targets[:, :1], -1)], axis=1)
    mtp_targets = jnp.where(has_next, later, -1)
    e = params["wte"][jnp.where(has_next, targets, 0)]
    both = jnp.concatenate([_norm(e, mtp["enorm"], sizes["eps"]),
                            _norm(x, mtp["hnorm"], sizes["eps"])], axis=-1)
    h = layers(_mm("bse,ed->bsd", both, mtp["eh_proj"], sizes),
               sizes["mtp_pattern"], mtp["blocks"], sizes)
    return trunk, _cross_entropy(h, mtp_targets, params, sizes)


def loss(params, tokens, targets, sizes):
    trunk, mtp = losses(params, tokens, targets, sizes)
    return trunk + sizes["mtp_weight"] * mtp
