"""Xing4.0 (latent attention with query compression over a dense or a shared +
routed-experts feed-forward half, every sublayer inside a four-stream
manifold-constrained hyper-connection, one multi-token-prediction module),
forward, loss and what the routers chose, in straight ``jax.numpy`` and
float32.

The benchmark's plain reference for family ``xing4`` (``model_type: xing4_0``;
https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json —
a DeepSeek-V3-shaped config, arXiv:2412.19437, whose residual keys are named
after mHC, arXiv:2512.24880, on Hyper-Connections, arXiv:2409.19606): no
kernel, no sort, no row buffer or grouped product, no mixed precision,
nothing imported from ``ray_tpu`` or from another family's reference. The
caller sets ``jax.default_matmul_precision("highest")``. What the config's
keys do not fix is marked *assumed*; the configuration's file lists each.

``norm(x; g) = g ⊙ x · rsqrt(mean(x²) + eps)``. A token's residual state is
``x ∈ R^{n×C}`` (n = hc_mult streams of C = hidden_size); the stream starts
as n copies of the token's embedding (*assumed*) and ends as ``Σ_i x[i]``
(*assumed*). A layer is two hyper-connected sublayers, F₁ = MLA, F₂ = a dense
SwiGLU (``D``) or experts (``E``), each with its own Φ, α, b:

    v = vec(x) [n·C];  r = sqrt(mean(v²) + eps)            eps = rms_norm_eps (*assumed*)
    m = (v·Φ) / r                                          [n² + 2n]
    H_pre  = σ(α_pre · m[0:n] + b_pre)                     [n]
    H_post = 2 · σ(α_post · m[n:2n] + b_post)              [n]
    H_res  = SK(clip(α_res · mat(m[2n:]) + b_res, clamp_min, clamp_max))   [n, n]
      SK: M = exp(·); hc_sinkhorn_iters times: M ← M / (rowsum(M) + hc_eps);
          M ← M / (colsum(M) + hc_eps)                     (order, eps in the denominators: *assumed*)
    u = Σ_i H_pre[i] · x[i];  y = F(norm(u; g))
    x'[i] = Σ_j H_res[i, j] · x[j] + H_post[i] · y

- **MLA** (u [S, C], H heads): ``c_q = norm(u·W_qa; g_q)`` over q_lora_rank;
  ``q = c_q·W_qb`` → [S, H, nope + rope]; ``[c | k_pe] = u·W_kva``;
  ``c ← norm(c; g_kv)``; ``[k_nope | v] = c·W_kvb``; ``k_pe`` is ONE head
  for all H. RoPE on ``q_pe`` and ``k_pe`` only, YaRN's frequencies made here
  from the formulas (``yarn_inv_freq``); pairs (i, i + rope/2): the weights'
  rotary columns arrive de-interleaved (``rope_pairing`` half: the program's
  tree; the published checkpoint's (2i, 2i + 1) order is a permutation of
  those columns, the same for q and k — *assumed*, as the DeepSeek
  configuration's (b)). ``o = softmax(q·kᵀ · s + causal)·v``, ``s = (nope +
  rope)^−½ · mscale(factor, mscale_all_dim)²``; ``MLA = o·W_o``.
- **dense**: ``(silu(u·W₁) ⊙ u·W₃)·W₂``.
- **experts**: ``s = σ(u·W_g)`` over all n_experts; the top_k largest of
  ``s + bias`` are chosen (the bias chooses only); ``g = scaling · s_chosen /
  (Σ s_chosen + 1e-20)``; ``FF = Σ_{e chosen and held} g_e · E_e(u) +
  E_shared(u)``, every E a SwiGLU — a loop over the held experts, each on
  every token, the gates (0 where the token did not choose it) the mask.
- **MTP** (one module): with h_t the trunk's summed stream BEFORE the final
  norm, ``h'_t = [norm(embed(tok_{t+1}); g_e) ; norm(h_t; g_h)]·W_eh`` (the
  embedding's half first: *assumed*), a stream started from h'_t, one expert
  layer with its own hyper-connections, the streams summed, a final norm of
  its own, the SHARED embedding and head, targets shifted one further.
- end: ``norm`` → the untied head; ``loss = CE_trunk + λ · CE_mtp``, each the
  mean cross-entropy over the positions with a target.

Departures from a whole model, the same in the program: only the experts
``held_first … held_first + held − 1`` (those whose weights are in the tree)
are computed — what absent experts would add is left out —, and embedding
and head hold the vocabulary's first rows / columns.

**What the routers chose.** A token whose 4th and 5th biased scores lie
closer than the bf16 stream resolves chooses another set in the program than
here, and its experts' gradients then differ by whole tokens, not by
rounding. So the reference can be GIVEN the sets the program chose
(``chosen``: one [B, S, n_experts] bool an expert layer, the MTP module's
last) and gates by them; it reports, a layer, the tokens whose own set
differs (``differ``) and how far below its own last chosen biased score a
given-but-not-own expert lies at worst (``worst_margin``, in units of a
score: a near-tie flipped reads 1e-3, a wrong rule the scores' spread).

It reads the program's parameter tree as the program lays it out (one entry a
run of a repeated sub-pattern; a kind's layers of the run stacked on a
leading axis; the stream's n·C channels stream-major) and walks the layers
one at a time — a ``lax.scan`` over a run's stack, each layer under
``jax.checkpoint`` —, a row alone, attention a block of QUERY_BLOCK query
rows at a time, what is a function of one token TOKEN_BLOCK tokens at a
time: memory, not meaning.

Switches for the readings a tolerance must catch, never for what the model
is: ``operand_dtype`` (the forward matmuls' operands rounded, one scale a
tensor), ``maps_dtype`` (the hyper-connection's maps — the Φ product's
result, the sigmoids, every Sinkhorn round — rounded to a narrower type).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

EXPERTS = {"D": False, "E": True}
QUERY_BLOCK = 256
TOKEN_BLOCK = 2048
HC_ATTN, HC_FFN = "hc_attn_", "hc_ffn_"


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


def _as_maps(x, sizes):
    """x as ``maps_dtype`` holds it element for element (no scale), its
    gradient passed on. By ``lax.reduce_precision``: a cast there and back
    is one the TPU compiler may leave out."""
    dtype = sizes.get("maps_dtype")
    if dtype is None:
        return x
    info = jnp.finfo(dtype)
    rounded = jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                       mantissa_bits=info.nmant)
    return x + jax.lax.stop_gradient(rounded - x)


def _mm(spec, a, b, sizes):
    dtype = sizes.get("operand_dtype")
    return jnp.einsum(spec, _rounded(a, dtype), _rounded(b, dtype))


# ----------------------------------------------------------------- the maps
def sinkhorn(logits, sizes):
    """logits [..., n, n] → exp of them after ``hc_sinkhorn_iters`` rounds of
    rows, then columns, each divided by its sum + ``hc_eps``."""
    m = _as_maps(jnp.exp(logits), sizes)
    for _ in range(sizes["hc_sinkhorn_iters"]):
        m = _as_maps(m / (jnp.sum(m, axis=-1, keepdims=True)
                          + sizes["hc_eps"]), sizes)
        m = _as_maps(m / (jnp.sum(m, axis=-2, keepdims=True)
                          + sizes["hc_eps"]), sizes)
    return m


def hyper_maps(x, p, prefix, sizes):
    """x [S, n, C] → (H_pre [S, n], H_post [S, n], H_res [S, n, n])."""
    n = sizes["hc_mult"]
    v = x.reshape(x.shape[0], -1)
    r = jnp.sqrt(jnp.mean(v * v, axis=-1, keepdims=True) + sizes["eps"])
    m = _as_maps(_mm("sk,km->sm", v, p[prefix + "phi"], sizes) / r, sizes)
    alpha, bias = p[prefix + "alpha"], p[prefix + "bias"]
    pre = _as_maps(jax.nn.sigmoid(alpha[0] * m[:, :n] + bias[:n]), sizes)
    post = _as_maps(2.0 * jax.nn.sigmoid(
        alpha[1] * m[:, n:2 * n] + bias[n:2 * n]), sizes)
    res = jnp.clip(alpha[2] * m[:, 2 * n:] + bias[2 * n:],
                   sizes["hc_clamp_min"], sizes["hc_clamp_max"])
    return pre, post, sinkhorn(res.reshape(-1, n, n), sizes)


def hyper_sublayer(x, p, prefix, norm_gain, f, sizes):
    """x [S, n, C] → (x' [S, n, C], what ``f`` returns beside y): ``f`` maps
    the normed pre-mix [S, C] to (y [S, C], its report)."""
    pre, post, res = hyper_maps(x, p, prefix, sizes)
    u = jnp.einsum("sn,snc->sc", pre, x)
    y, report = f(_norm(u, norm_gain, sizes["eps"]))
    return (jnp.einsum("sij,sjc->sic", res, x)
            + post[:, :, None] * y[:, None, :]), report


# ------------------------------------------------------------------ rotary
def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(sizes):
    """The rope / 2 frequencies: with ``f_i = θ^(−2i/rope)``, ``low`` /
    ``high`` the correction range for ``beta_fast`` / ``beta_slow`` over
    ``original_len`` positions (floored, ceiled), ``ramp_i = clip((i − low) /
    (high − low), 0, 1)``: ``inv_freq_i = f_i / factor · ramp_i + f_i · (1 −
    ramp_i)``."""
    dim, theta, factor = sizes["rope"], sizes["theta"], sizes["rope_factor"]
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / dim)
    if factor <= 1:
        return f

    def correction(beta):
        return (dim * math.log(sizes["rope_original_len"]
                               / (2 * math.pi * beta))
                / (2 * math.log(theta)))

    low = max(math.floor(correction(sizes["rope_beta_fast"])), 0)
    high = min(math.ceil(correction(sizes["rope_beta_slow"])), dim // 2 - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return f / factor * ramp + f * (1.0 - ramp)


def _rope(x, sizes):
    """x [..., S, rope] rotated: pairs (i, i + rope/2), angle position ·
    inv_freq_i, cos and sin times mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)."""
    s, d = x.shape[-2:]
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * yarn_inv_freq(sizes)
    scale = (_mscale(sizes["rope_factor"], sizes["rope_mscale"])
             / _mscale(sizes["rope_factor"], sizes["rope_mscale_all_dim"]))
    cos, sin = jnp.cos(angles) * scale, jnp.sin(angles) * scale
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def softmax_scale(sizes):
    m = _mscale(sizes["rope_factor"], sizes["rope_mscale_all_dim"])
    return m * m / math.sqrt(sizes["nope"] + sizes["rope"])


# ------------------------------------------------------------- the sublayers
def mla(u, p, sizes):
    """u [S, C] → [S, C]: latent attention with query compression, one row."""
    nope, rope, rank = sizes["nope"], sizes["rope"], sizes["rank"]
    s = u.shape[0]
    c_q = _norm(_mm("sd,dr->sr", u, p["wq_a"], sizes), p["q_norm"],
                sizes["eps"])
    q = _mm("sr,rhk->hsk", c_q, p["wq_b"], sizes)           # [H, S, 192]
    ckpe = _mm("sd,dc->sc", u, p["wkv_a"], sizes)           # [S, 512 + 64]
    c = _norm(ckpe[:, :rank], p["kv_norm"], sizes["eps"])
    kv = _mm("sc,chk->hsk", c, p["wkv_b"], sizes)           # [H, S, 128+128]
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], sizes)], axis=-1)
    k_pe = _rope(ckpe[:, rank:], sizes)                     # [S, 64]: one head
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (k_nope.shape[0], s, rope))], axis=-1)
    scale = softmax_scale(sizes)
    block = min(QUERY_BLOCK, s)
    cols = jnp.arange(s)

    def rows_of(args):
        """A block of query rows [H, block, 192] against every key."""
        qb, first = args
        logits = _mm("hqd,hkd->hqk", qb, k, sizes) * scale
        visible = cols[None, :] <= (first + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(visible, logits, -jnp.inf), axis=-1)
        return _mm("hqk,hkd->hqd", probs, v, sizes)         # [H, block, 128]

    blocks = q.reshape(q.shape[0], s // block, block, -1).swapaxes(0, 1)
    o = jax.lax.map(jax.checkpoint(rows_of),
                    (blocks, jnp.arange(s // block) * block))
    o = o.swapaxes(0, 1).reshape(q.shape[0], s, -1)
    return _mm("hsk,hkd->sd", o, p["wo"], sizes)


def _swiglu(u, w1, w3, w2, sizes):
    return _mm("sf,fd->sd", jax.nn.silu(_mm("sd,df->sf", u, w1, sizes))
               * _mm("sd,df->sf", u, w3, sizes), w2, sizes)


def routed_gates(u, p, sizes, given=None):
    """u [S, C] → (g [S, n_experts]: a token's gate on each expert of its
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
    gates = sizes["scaling"] * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return gates, report


def experts(u, p, sizes, given=None):
    """u [S, C] → (the half's output [S, C]: the held experts' part and the
    shared expert; the report)."""
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


def layer(x, p, given, kind, sizes):
    """One layer on one row, x [S, n, C] → (x', the router's report or
    None)."""
    x, _ = hyper_sublayer(x, p, HC_ATTN, p["attn_norm"],
                          lambda u: (mla(u, p, sizes), None), sizes)
    if EXPERTS[kind]:
        return hyper_sublayer(x, p, HC_FFN, p["ffn_norm"],
                              lambda u: experts(u, p, sizes, given), sizes)

    def dense(u):
        return _by_tokens(lambda u: _swiglu(
            u, p["w_gate"], p["w_up"], p["w_down"], sizes), u), None

    return hyper_sublayer(x, p, HC_FFN, p["ffn_norm"], dense, sizes)


def _groups(pattern):
    """A pattern as runs of a repeated sub-pattern, as the program stacks its
    layers (greedy from the left: the repeat that covers most layers, of
    equal ones the shortest sub-pattern): ``"DEEEE"`` → ``[("D", 1),
    ("E", 4)]``."""
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


def _layers(h, pattern, stacks, chosen, sizes):
    """h [S, C] through ``pattern``'s layers as ONE stream's life: n copies
    at the start, the streams' sum at the end → ([S, C], the expert layers'
    reports in order). ``chosen``: None, or [expert layers, S, n_experts]. A
    run of layers of one kind is a ``scan`` over the run's stacked tensors,
    each layer under ``jax.checkpoint``."""
    n = sizes["hc_mult"]
    x = jnp.broadcast_to(h[:, None, :], (h.shape[0], n, h.shape[1]))
    reports, seen = [], 0
    for (sub, reps), group in zip(_groups(pattern), stacks, strict=True):
        if len(sub) != 1:
            raise ValueError(f"a run of mixed kinds {sub!r}: this family's "
                             "patterns are dense layers, then expert layers")
        sets = None
        if EXPERTS[sub] and chosen is not None:
            sets, seen = chosen[seen:seen + reps], seen + reps

        def one_layer(x, layer_in, kind=sub):
            p, given = layer_in
            return layer(x, p, given, kind, sizes)

        x, report = jax.lax.scan(jax.checkpoint(one_layer), x,
                                 (group[sub], sets))
        if EXPERTS[sub]:
            reports.append(report)
    return jnp.sum(x, axis=1), reports


def _nll(x, targets, lm_head, sizes):
    """x [S, C] (normed), targets [S] → (the summed negative log-likelihood
    over the positions with a target, their count)."""
    def nll_of(x, targets):
        logp = jax.nn.log_softmax(_mm("sd,dv->sv", x, lm_head, sizes))
        mask = targets >= 0
        nll = -jnp.take_along_axis(
            logp, jnp.where(mask, targets, 0)[:, None], axis=-1)[:, 0]
        return nll * mask

    return jnp.sum(_by_tokens(nll_of, x, targets)), jnp.sum(targets >= 0)


def _row(params, tokens, targets, chosen, sizes):
    """One row [S] (``chosen``: None, or the row's sets, [expert layers, S,
    n_experts], the MTP module's last) → ((the trunk's summed negative
    log-likelihood, its targets), the MTP module's two or zeros, the expert
    layers' reports stacked in order)."""
    eps, wte = sizes["eps"], params["wte"]
    trunk_layers = sum(EXPERTS[k] for k in sizes["pattern"])
    h, reports = _layers(wte[tokens], sizes["pattern"], params["blocks"],
                         None if chosen is None else chosen[:trunk_layers],
                         sizes)
    trunk = _nll(_norm(h, params["final_norm"], eps), targets,
                 params["lm_head"], sizes)
    mtp = (jnp.zeros(()), jnp.zeros((), jnp.int32))
    if sizes["mtp_pattern"]:
        m = params["mtp"]
        has_next = targets >= 0
        later = jnp.concatenate([targets[1:], -jnp.ones((1,), targets.dtype)])
        e = wte[jnp.where(has_next, targets, 0)]
        joined = _mm("se,ed->sd", jnp.concatenate(
            [_norm(e, m["enorm"], eps), _norm(h, m["hnorm"], eps)], axis=-1),
            m["eh_proj"], sizes)
        g, more = _layers(joined, sizes["mtp_pattern"], m["blocks"],
                          None if chosen is None else chosen[trunk_layers:],
                          sizes)
        reports += more
        mtp = _nll(_norm(g, m["final_norm"], eps),
                   jnp.where(has_next, later, -1), params["lm_head"], sizes)
    reports = (jax.tree.map(lambda *r: jnp.concatenate(r), *reports)
               if reports else None)
    return trunk, mtp, reports


def loss_and_routing(params, tokens, targets, sizes, chosen=None):
    """tokens / targets [B, S] (targets: the next token, −1 = none) → (the
    loss CE_trunk + mtp_weight · CE_mtp; one report an expert layer, the MTP
    module's last — ``differ`` summed and ``worst_margin`` the largest over
    the rows, ``own`` the router's own sets, [B, S, n_experts] bool; (CE_trunk,
    CE_mtp)). ``chosen``: None, or the sets to gate by, as ``own`` has them,
    one an expert layer."""
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    # a row's sets: [B, expert layers, S, n_experts]
    sets = jnp.stack(list(chosen), axis=1) if chosen else None
    row = jax.checkpoint(lambda t: _row(params, *t, sizes))
    (nll, count), (mtp_nll, mtp_count), reports = jax.lax.map(
        row, (tokens, targets, sets))
    layers = 0 if reports is None else reports["differ"].shape[1]
    reports = [{"differ": jnp.sum(reports["differ"][:, i]),
                "worst_margin": jnp.max(reports["worst_margin"][:, i]),
                "own": reports["own"][:, i]} for i in range(layers)]
    trunk = jnp.sum(nll) / jnp.maximum(jnp.sum(count), 1)
    mtp = jnp.sum(mtp_nll) / jnp.maximum(jnp.sum(mtp_count), 1)
    return trunk + sizes["mtp_weight"] * mtp, reports, (trunk, mtp)


def loss(params, tokens, targets, sizes, chosen=None):
    return loss_and_routing(params, tokens, targets, sizes, chosen)[0]
