"""LFM2-MoE (gated short-convolution / grouped-query attention hybrid with
dense and mixture-of-experts feed-forward halves), forward, loss and what the
routers chose, in straight ``jax.numpy`` and float32.

The benchmark's plain reference for family ``lfm2_moe`` (LiquidAI
LFM2-24B-A2B, ``model_type: lfm2_moe``;
https://huggingface.co/LiquidAI/LFM2-24B-A2B; modeling_lfm2_moe.py of
HuggingFace transformers is the published code): no kernel, no sort, no row
buffer or grouped product, no layer scan, no mixed precision, nothing
imported from ``ray_tpu``. The caller sets
``jax.default_matmul_precision("highest")``.

``norm(x; g) = g ⊙ x · rsqrt(mean(x²) + eps)``. Every layer ℓ:
``h = x + Op(norm(x; g_op))``, ``x' = h + FF(norm(h; g_ffn))``, the pair from
``sizes["pattern"]`` (``D``: conv + dense, ``A``: attention + experts, ``C``:
conv + experts):

- **conv**: ``[B̃ | C̃ | x̃] = u·W_in`` (three of D, in that order);
  ``z = B̃ ⊙ x̃``; ``c_t = Σ_j w_j ⊙ z_{t−(K−1)+j}`` as K shifted products
  (depthwise, causal, z = 0 before the row's start, no bias, no activation);
  ``Op = (C̃ ⊙ c)·W_out``.
- **attention**: ``q, k, v = u·W_q, u·W_k, u·W_v``; q and k ← ``norm`` over
  each head's width (one gain vector each), THEN RoPE (rotate-half pairing
  over the whole head, ``θ`` from sizes); a masked softmax of ``q kᵀ/√hd``,
  a key-value head shared by n_head / n_kv_head query heads; ``· W_o``.
- **dense**: ``(silu(u·W₁) ⊙ u·W₃)·W₂``.
- **experts**: ``s = sigmoid(u·W_r)``; the top_k largest of ``s + b`` are
  chosen (b chooses only); ``g_e = scaling · s_e / (Σ_chosen s + route_eps)``;
  ``FF = Σ_{e chosen and held} g_e · (silu(u·W₁ᵉ) ⊙ u·W₃ᵉ)·W₂ᵉ`` — a loop over
  the held experts, each on every token, the gates (0 where the token did not
  choose it) the mask.
- end: ``norm`` → the embedding's transpose (tied head), cross-entropy over
  the positions with a target.

Departures from a whole model, the same in the program: only the experts
``held_first … held_first + held − 1`` (those whose weights are in the tree)
are computed — what absent experts would add is left out — and the
embedding holds the vocabulary's first rows. ``b`` is a buffer, the expert
layer's ``router_bias``: no gradient reaches it.

**What the routers chose.** A token whose 4th and 5th biased scores lie
closer than the bf16 stream resolves chooses another set in the program than
here, and its experts' gradients then differ by whole tokens, not by
rounding. So the reference can be GIVEN the sets the program chose
(``chosen``: one [B, S, n_experts] bool an expert layer) and gates by them;
it reports, a layer, the tokens whose own set differs (``differ``) and how
far below its own last chosen biased score a given-but-not-own expert lies
at worst (``worst_margin``, in units of a score): a near-tie flipped reads
1e-3, a wrong rule reads the scores' spread.

It reads the program's parameter tree as the program lays it out (one entry
a run of a repeated sub-pattern, ``_groups``; a kind's layers of the run
stacked on a leading axis in the order they come) and walks the layers one
at a time. A row is worked alone — rows meet in the loss's mean only — under
``jax.checkpoint``, each layer under one of its own: a batch of 8 × 4,096
tokens and its gradient then take one row's hidden tensors beside the step's
state. The same numbers, made more than once.

Two switches exist for the readings a tolerance must catch, never for what
the model is: ``drop_routed`` (the expert layers' output left out),
``operand_dtype`` (the forward matmuls' operands rounded to a narrower type,
one scale a tensor).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

CONV_OPERATOR = {"D": True, "A": False, "C": True}
EXPERTS = {"D": False, "A": True, "C": True}


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


def conv_operator(u, p, sizes):
    """u [S, D] → [S, D]: the double-gated short convolution."""
    s, d = u.shape
    bcx = _mm("sd,de->se", u, p["w_in"], sizes)
    b, c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    taps = p["conv_w"].shape[0]
    z = jnp.pad(b * x, ((taps - 1, 0), (0, 0)))
    conv = sum(z[j:j + s] * p["conv_w"][j] for j in range(taps))
    return _mm("sd,de->se", c * conv, p["w_out"], sizes)


def _rope(x, theta):
    """x [H, S, hd] rotated: pairs (i, i + hd/2), angle position · θ^(−2i/hd)."""
    s, hd = x.shape[-2:]
    half = hd // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention_operator(u, p, sizes):
    """u [S, D] → [S, D]: QK-norm, then RoPE, grouped heads, masked softmax."""
    eps, theta = sizes["eps"], sizes["theta"]
    q = _rope(_norm(_mm("sd,dhk->hsk", u, p["wq"], sizes), p["q_norm"], eps),
              theta)
    k = _rope(_norm(_mm("sd,dhk->hsk", u, p["wk"], sizes), p["k_norm"], eps),
              theta)
    v = _mm("sd,dhk->hsk", u, p["wv"], sizes)
    rep = q.shape[0] // k.shape[0]
    k, v = jnp.repeat(k, rep, axis=0), jnp.repeat(v, rep, axis=0)
    s = q.shape[1]
    logits = _mm("hqd,hkd->hqk", q, k, sizes) / math.sqrt(q.shape[-1])
    logits = jnp.where(jnp.tril(jnp.ones((s, s), bool)), logits, -jnp.inf)
    o = _mm("hqk,hkd->hqd", jax.nn.softmax(logits, axis=-1), v, sizes)
    return _mm("hsk,hkd->sd", o, p["wo"], sizes)


def _swiglu(u, w1, w3, w2, sizes):
    return _mm("sf,fd->sd", jax.nn.silu(_mm("sd,df->sf", u, w1, sizes))
               * _mm("sd,df->sf", u, w3, sizes), w2, sizes)


def routed_gates(u, p, sizes, given=None):
    """u [S, D] → (g [S, n_experts]: a token's gate on each expert of its
    set, 0 on the others — float32 throughout, whatever ``operand_dtype`` —,
    the report on ``given``). The set is ``given`` [S, n_experts] bool where
    one is given, else the router's own."""
    s = jax.nn.sigmoid(u @ p["router_w"])
    biased = s + p["router_bias"]
    top, idx = jax.lax.top_k(biased, sizes["top_k"])
    own = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=s.dtype), axis=1) > 0
    chosen = own if given is None else given
    # how far below the own set's last biased score a given-but-not-own lies
    short = jnp.where(chosen & ~own, top[:, -1:] - biased, 0.0)
    report = {"differ": jnp.sum(jnp.any(chosen != own, axis=-1)),
              "worst_margin": jnp.max(short), "own": own}
    mine = jnp.where(chosen, s, 0.0)
    gates = sizes["scaling"] * mine / (
        jnp.sum(mine, axis=-1, keepdims=True) + sizes["route_eps"])
    return gates, report


def experts(u, p, sizes, given=None):
    """u [S, D] → (the held experts' part of the layer [S, D], the report)."""
    gates, report = routed_gates(u, p, sizes, given)
    out = jnp.zeros_like(u)
    if not sizes.get("drop_routed"):
        for e in range(p["w1"].shape[0]):        # every held expert, masked
            g = gates[:, sizes["held_first"] + e]
            out = out + g[:, None] * _swiglu(
                u, p["w1"][e], p["w3"][e], p["w2"][e], sizes)
    return out, report


def _groups(pattern):
    """A pattern as runs of a repeated sub-pattern, as the program stacks its
    layers (greedy from the left: the repeat that covers most layers, of
    equal ones the shortest sub-pattern): ``"DACCC"`` → ``[("D", 1),
    ("A", 1), ("C", 3)]``."""
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


def layer_params(pattern, stacks):
    """[(kind, that layer's tensors)] in the layers' order."""
    out = []
    for (sub, reps), group in zip(_groups(pattern), stacks, strict=True):
        seen = dict.fromkeys(sub, 0)
        for kind in sub * reps:
            out.append((kind, jax.tree.map(lambda t: t[seen[kind]],
                                           group[kind])))
            seen[kind] += 1
    return out


def layer(x, p, given, kind, sizes):
    """One layer on one row, x [S, D] → (x', the router's report or None)."""
    u = _norm(x, p["op_norm"], sizes["eps"])
    op = conv_operator if CONV_OPERATOR[kind] else attention_operator
    h = x + op(u, p, sizes)
    u = _norm(h, p["ffn_norm"], sizes["eps"])
    if not EXPERTS[kind]:
        return h + _swiglu(u, p["w_gate"], p["w_up"], p["w_down"], sizes), None
    f, report = experts(u, p, sizes, given)
    return h + f, report


def _row(params, tokens, targets, chosen, sizes):
    """One row [S] → (its summed negative log-likelihood, its targets, the
    expert layers' reports in order)."""
    x = params["wte"][tokens]
    given, reports = iter(chosen or ()), []
    for kind, p in layer_params(sizes["pattern"], params["blocks"]):
        g = next(given, None) if EXPERTS[kind] else None
        x, report = jax.checkpoint(
            functools.partial(layer, kind=kind, sizes=sizes))(x, p, g)
        if report is not None:
            reports.append(report)
    x = _norm(x, params["final_norm"], sizes["eps"])
    logp = jax.nn.log_softmax(_mm("sd,vd->sv", x, params["wte"], sizes))
    mask = targets >= 0
    nll = -jnp.take_along_axis(
        logp, jnp.where(mask, targets, 0)[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * mask), jnp.sum(mask), reports


def loss_and_routing(params, tokens, targets, sizes, chosen=None):
    """tokens / targets [B, S] (targets: the next token, −1 = none) → (the
    mean cross-entropy, one report an expert layer: ``differ`` summed and
    ``worst_margin`` the largest over the rows, ``own`` the router's own
    sets, [B, S, n_experts] bool). ``chosen``: None, or the sets to gate by,
    as ``own`` has them, one an expert layer."""
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    row = jax.checkpoint(lambda t: _row(params, *t, sizes))
    nll, count, reports = jax.lax.map(row, (tokens, targets, chosen))
    reports = [{"differ": jnp.sum(r["differ"]),
                "worst_margin": jnp.max(r["worst_margin"]), "own": r["own"]}
               for r in reports]
    return jnp.sum(nll) / jnp.maximum(jnp.sum(count), 1), reports


def loss(params, tokens, targets, sizes, chosen=None):
    return loss_and_routing(params, tokens, targets, sizes, chosen)[0]
