"""AFMoE (Arcee Trinity: window and full gated grouped-query attention mixed
3 : 1, sandwich norms, sigmoid-routed experts beside a shared one), forward,
loss and what the routers chose, in straight ``jax.numpy`` and float32.

The benchmark's plain reference for family ``afmoe`` (Trinity-Mini,
``model_type: afmoe``; https://huggingface.co/arcee-ai/Trinity-Mini;
``modeling_afmoe.py`` of ``transformers`` is the published code): no kernel,
no band walk, no sort, no row buffer or grouped product, no mixed precision,
nothing imported from ``ray_tpu``. The caller sets
``jax.default_matmul_precision("highest")``.

``norm(x; g) = g ⊙ x · rsqrt(mean(x²) + eps)``. ``x⁰ = wte[tokens] · √d``
(``mup_enabled``). Every layer, the kind from ``sizes["pattern"]`` (``D``:
window + dense MLP, ``W``: window + experts, ``F``: full + experts):
``h = x + norm(Attn(norm(x; g_in)); g_post_attn)``,
``x' = h + norm(FF(norm(h; g_pre_mlp)); g_post_mlp)``.

- **Attn** (u [S, D], H query heads on KH key-value heads of hd):
  ``q = norm_hd(u·W_q; g_q)``, ``k = norm_hd(u·W_k; g_k)``, ``v = u·W_v``,
  ``γ = sigmoid(u·W_g)`` [S, H·hd]. A WINDOW layer rotates q and k (RoPE θ,
  rotate-half over all hd channels — pairs (i, i + hd/2), the published
  order —, positions 0 … S − 1, after the norm); a FULL layer has no
  positional signal (NoPE). ``a_i = Σ_j softmax_j(q_i·k_j / √hd + m_ij) v_j``,
  query head n reading key-value head n // (H / KH), ``m_ij = 0`` where
  j ≤ i and, on a window layer only, i − j < window; −∞ elsewhere — an
  EXPLICIT mask over every key, a block of query rows at a time.
  ``Attn = (a ⊙ γ)·W_o``.
- **dense**: ``(silu(u·W₁) ⊙ u·W₃)·W₂``.
- **experts**: ``s = sigmoid(u·W_r)`` over all n_experts, float32; the top_k
  largest of ``s + b`` are chosen (``b`` chooses only); ``g_e = scaling ·
  s_e / (Σ_chosen s + 1e-20)``; ``FF = (silu(u·S₁) ⊙ u·S₃)·S₂ + Σ_{e chosen
  and held} g_e · (silu(u·W₁ᵉ) ⊙ u·W₃ᵉ)·W₂ᵉ`` — a loop over the held experts,
  each on every token, the gates (0 where the token did not choose it) the
  mask; the shared expert on every token.
- end: ``norm`` → the untied head, mean cross-entropy over the positions
  with a target.

Departures from a whole model, the same in the program: only the experts
``held_first … held_first + held − 1`` (those whose weights are in the tree)
are computed — what absent experts would add is left out —, and embedding
and head hold the vocabulary's first rows / columns.

**What the routers chose.** A token whose 8th and 9th biased scores lie
closer than the bf16 stream resolves chooses another set in the program than
here, and its experts' gradients then differ by whole tokens, not by
rounding. So the reference can be GIVEN the sets the program chose
(``chosen``: one [B, S, n_experts] bool an expert layer) and gates by them;
it reports, a layer, the tokens whose own set differs (``differ``) and how
far below its own last chosen biased score a given-but-not-own expert lies
at worst (``worst_margin``: a near-tie flipped reads 1e-3, a wrong rule the
scores' spread).

It reads the program's parameter tree as the program lays it out (one entry
a run of a repeated sub-pattern, ``_groups``; a kind's layers of the run
stacked on a leading axis) and walks the layers one at a time, each under
``jax.checkpoint``. A row is worked alone — rows meet in the loss's mean
only — under one more, attention a block of QUERY_BLOCK query rows under one
more, and what is a function of one token — a feed-forward half, the head's
loss — TOKEN_BLOCK tokens at a time: memory, not meaning.

Switches for the readings a limit must REFUSE, never for what the model is:
``operand_dtype`` (the forward matmuls' operands rounded, one scale a tensor),
``window_ignored`` (every layer sees every key before the query),
``rope_on_full`` (a full layer rotates too), ``attn_gate_dropped`` (γ = 1),
``post_norms_dropped`` (no norm on a sublayer's output), ``route_scale_one``
(scaling 1), ``embed_unscaled`` (x⁰ = wte[tokens]).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

WINDOWED = {"D": True, "W": True, "F": False}
EXPERTS = {"D": False, "W": True, "F": True}
QUERY_BLOCK = 256
TOKEN_BLOCK = 2048


def _by_tokens(fn, *per_token):
    """``fn`` (arrays [block, ...] → a tree of [block, ...]) over the leading
    axis of ``per_token`` in blocks of TOKEN_BLOCK, each under
    ``jax.checkpoint``; the blocks' results joined along that axis."""
    s = per_token[0].shape[0]
    block = min(TOKEN_BLOCK, s)
    cut = [x.reshape((s // block, block) + x.shape[1:]) for x in per_token]
    out = jax.lax.map(jax.checkpoint(lambda xs: fn(*xs)), cut)
    return jax.tree.map(lambda y: y.reshape((s,) + y.shape[2:]), out)


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


def _rope(x, theta):
    """x [..., S, hd] rotated: pairs (i, i + hd/2), angle position ·
    θ^(−2i/hd) (rotate-half, the published order)."""
    s, d = x.shape[-2:]
    inv_freq = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(u, p, kind, sizes):
    """u [S, D] → [S, D]: gated grouped-query attention on one row, under
    the kind's mask."""
    s = u.shape[0]
    eps, hd = sizes["eps"], p["wq"].shape[-1]
    q = _norm(_mm("sd,dhk->hsk", u, p["wq"], sizes), p["q_norm"], eps)
    k = _norm(_mm("sd,dhk->hsk", u, p["wk"], sizes), p["k_norm"], eps)
    v = _mm("sd,dhk->hsk", u, p["wv"], sizes)
    gate = jax.nn.sigmoid(_mm("sd,dhk->hsk", u, p["wg"], sizes))
    windowed = WINDOWED[kind] and not sizes.get("window_ignored")
    if WINDOWED[kind] or sizes.get("rope_on_full"):
        q, k = _rope(q, sizes["theta"]), _rope(k, sizes["theta"])
    heads, kv_heads = q.shape[0], k.shape[0]
    group = heads // kv_heads
    block = min(QUERY_BLOCK, s)
    cols = jnp.arange(s)

    def rows_of(args):
        """A block of query rows [KH, G, block, hd] against every key."""
        qb, first = args
        logits = _mm("kgqd,ksd->kgqs", qb, k, sizes) / math.sqrt(hd)
        rows = (first + jnp.arange(block))[:, None]
        visible = cols[None, :] <= rows
        if windowed:
            visible &= rows - cols[None, :] < sizes["window"]
        probs = jax.nn.softmax(jnp.where(visible, logits, -jnp.inf), axis=-1)
        return _mm("kgqs,ksd->kgqd", probs, v, sizes)

    blocks = q.reshape(kv_heads, group, s // block, block, hd)
    blocks = jnp.moveaxis(blocks, 2, 0)
    o = jax.lax.map(jax.checkpoint(rows_of),
                    (blocks, jnp.arange(s // block) * block))
    o = jnp.moveaxis(o, 0, 2).reshape(heads, s, hd)
    if not sizes.get("attn_gate_dropped"):
        o = o * gate
    return _mm("hsk,hkd->sd", o, p["wo"], sizes)


def _swiglu(u, w1, w3, w2, sizes):
    return _mm("sf,fd->sd", jax.nn.silu(_mm("sd,df->sf", u, w1, sizes))
               * _mm("sd,df->sf", u, w3, sizes), w2, sizes)


def routed_gates(u, p, sizes, given=None):
    """u [S, D] → (g [S, n_experts]: a token's gate on each expert of its
    set, 0 on the others; the report on ``given``). The set is ``given`` [S,
    n_experts] bool where one is given, else the router's own. Float32
    throughout, whatever ``operand_dtype``."""
    n = p["router_w"].shape[-1]
    scores = jax.nn.sigmoid(u @ p["router_w"])
    biased = scores + p["router_bias"]
    top, idx = jax.lax.top_k(biased, sizes["top_k"])
    own = jnp.sum(jax.nn.one_hot(idx, n, dtype=scores.dtype), axis=1) > 0
    chosen = own if given is None else given
    # how far below the own set's last biased score a given-but-not-own lies
    short = jnp.where(chosen & ~own, top[:, -1:] - biased, 0.0)
    report = {"differ": jnp.sum(jnp.any(chosen != own, axis=-1)),
              "worst_margin": jnp.max(short), "own": own}
    picked = jnp.where(chosen, scores, 0.0)
    scaling = 1.0 if sizes.get("route_scale_one") else sizes["scaling"]
    gates = scaling * picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return gates, report


def experts(u, p, sizes, given=None):
    """u [S, D] → (the half's output [S, D]: the shared expert and the held
    experts' part; the report)."""
    gates, report = routed_gates(u, p, sizes, given)

    def feed_forward(u, gates):
        out = _swiglu(u, p["shared_w1"], p["shared_w3"], p["shared_w2"], sizes)
        if sizes.get("drop_shared"):
            out = jnp.zeros_like(u)

        def add_expert(out, held):          # every held expert, masked
            w1, w3, w2, g = held
            return out + g[:, None] * _swiglu(u, w1, w3, w2, sizes), None

        first = sizes["held_first"]
        mine = gates[:, first:first + p["w1"].shape[0]].T      # [held, block]
        return jax.lax.scan(add_expert, out,
                            (p["w1"], p["w3"], p["w2"], mine))[0]

    return _by_tokens(feed_forward, u, gates), report


def _groups(pattern):
    """A pattern as runs of a repeated sub-pattern, as the program stacks its
    layers (greedy from the left: the repeat that covers most layers, of
    equal ones the shortest sub-pattern): ``"DWFWW"`` → ``[("D", 1),
    ("W", 1), ("F", 1), ("W", 2)]``."""
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
    eps = sizes["eps"]

    def post(y, g):
        return y if sizes.get("post_norms_dropped") else _norm(y, g, eps)

    a = attention(_norm(x, p["attn_norm"], eps), p, kind, sizes)
    h = x + post(a, p["attn_post_norm"])
    u = _norm(h, p["ffn_norm"], eps)
    if not EXPERTS[kind]:
        f, report = _by_tokens(lambda u: _swiglu(
            u, p["w_gate"], p["w_up"], p["w_down"], sizes), u), None
    else:
        f, report = experts(u, p, sizes, given)
    return h + post(f, p["ffn_post_norm"]), report


def _embed(params, tokens, sizes):
    """tokens [S] → x⁰ [S, D]: the embedding's rows, times √d."""
    x = params["wte"][tokens]
    return x if sizes.get("embed_unscaled") else x * sizes["embed_scale"]


def _row(params, tokens, targets, chosen, sizes):
    """One row [S] (``chosen``: None, or the row's sets, [expert layers, S,
    n_experts]) → (its summed negative log-likelihood, its targets, the
    expert layers' reports stacked in order or None)."""
    x = _embed(params, tokens, sizes)
    reports, seen = [], 0
    for kind, p in layer_params(sizes["pattern"], params["blocks"]):
        given = None
        if EXPERTS[kind] and chosen is not None:
            given, seen = chosen[seen], seen + 1
        x, report = jax.checkpoint(
            lambda x, p, given, kind=kind: layer(x, p, given, kind, sizes))(
            x, p, given)
        if report is not None:
            reports.append(report)
    x = _norm(x, params["final_norm"], sizes["eps"])

    def nll_of(x, targets):
        logp = jax.nn.log_softmax(
            _mm("sd,dv->sv", x, params["lm_head"], sizes))
        mask = targets >= 0
        nll = -jnp.take_along_axis(
            logp, jnp.where(mask, targets, 0)[:, None], axis=-1)[:, 0]
        return nll * mask

    reports = (jax.tree.map(lambda *r: jnp.stack(r), *reports)
               if reports else None)
    return (jnp.sum(_by_tokens(nll_of, x, targets)), jnp.sum(targets >= 0),
            reports)


def loss_and_routing(params, tokens, targets, sizes, chosen=None):
    """tokens / targets [B, S] (targets: the next token, −1 = none) → (the
    mean cross-entropy; one report an expert layer — ``differ`` summed and
    ``worst_margin`` the largest over the rows, ``own`` the router's own
    sets, [B, S, n_experts] bool). ``chosen``: None, or the sets to gate by,
    as ``own`` has them, one an expert layer."""
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    # a row's sets: [B, expert layers, S, n_experts]
    sets = jnp.stack(list(chosen), axis=1) if chosen else None
    row = jax.checkpoint(lambda t: _row(params, *t, sizes))
    nll, count, reports = jax.lax.map(row, (tokens, targets, sets))
    layers = 0 if reports is None else reports["differ"].shape[1]
    reports = [{"differ": jnp.sum(reports["differ"][:, i]),
                "worst_margin": jnp.max(reports["worst_margin"][:, i]),
                "own": reports["own"][:, i]} for i in range(layers)]
    return jnp.sum(nll) / jnp.maximum(jnp.sum(count), 1), reports


def logits(params, tokens, sizes):
    """tokens [B, S] → the head's logits [B, S, vocab] (small sizes: every
    row's at once)."""
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)

    def one(row):
        x = _embed(params, row, sizes)
        for kind, p in layer_params(sizes["pattern"], params["blocks"]):
            x, _ = layer(x, p, None, kind, sizes)
        return _norm(x, params["final_norm"], sizes["eps"]) @ params["lm_head"]

    return jax.lax.map(one, tokens)


def loss(params, tokens, targets, sizes, chosen=None):
    return loss_and_routing(params, tokens, targets, sizes, chosen)[0]
