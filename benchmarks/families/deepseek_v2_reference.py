"""DeepSeek-V2 (multi-head latent attention over a dense or a shared +
routed-experts feed-forward half), forward, loss — balance loss included —
and what the routers chose, in straight ``jax.numpy`` and float32.

The benchmark's plain reference for family ``deepseek_v2`` (DeepSeek-V2-Lite,
``model_type: deepseek_v2``; https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite;
arXiv:2405.04434; modeling_deepseek.py is the published code): no kernel, no
sort, no row buffer or grouped product, no layer scan, no mixed precision,
nothing imported from ``ray_tpu``. The caller sets
``jax.default_matmul_precision("highest")``.

``norm(x; g) = g ⊙ x · rsqrt(mean(x²) + eps)``. Every layer:
``h = x + MLA(norm(x; g_attn))``, ``x' = h + FF(norm(h; g_ffn))``, the kind
from ``sizes["pattern"]`` (``D``: a dense MLP, ``E``: experts):

- **MLA** (u [S, D], H heads): ``q = u·W_q`` → [S, H, nope + rope] =
  ``[q_nope | q_pe]``; ``[c | k_pe] = u·W_kva`` → [S, rank + rope];
  ``c ← norm(c; g_kv)``; ``[k_nope | v] = c·W_kvb`` → [S, H, nope + v];
  ``k_pe`` is ONE head for all H. RoPE on ``q_pe`` and ``k_pe`` only, with
  YaRN's frequencies, made HERE from the formulas (``yarn_inv_freq``): with
  ``f_i = θ^(−2i/rope)``, ``low`` / ``high`` the correction range for
  ``beta_fast`` / ``beta_slow`` over ``original_len`` positions (floored,
  ceiled), ``ramp_i = clip((i − low)/(high − low), 0, 1)``:
  ``inv_freq_i = f_i / factor · ramp_i + f_i · (1 − ramp_i)``; cos and sin
  times ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``
  (``mscale(s, m) = 0.1 · m · ln s + 1``). The published checkpoint stores
  the rotary channels interleaved (pairs (2i, 2i + 1)) and the published code
  de-interleaves them before a rotate-half: ``sizes["rope_pairing"]``
  ``"interleaved"`` does exactly that; ``"half"`` takes weights whose rotary
  columns are ALREADY de-interleaved (the program's: the configuration's
  ``assumed`` says so; tests/test_deepseek_v2.py maps one onto the other).
  ``k = [k_nope | k_pe]`` a head, ``o = softmax(q·kᵀ · s + causal)·v`` with
  ``s = (nope + rope)^−½ · mscale(factor, mscale_all_dim)²``, q·k at nope +
  rope and v at its own width written out, a block of query rows at a time;
  ``MLA = o·W_o``.
- **dense**: ``(silu(u·W₁) ⊙ u·W₃)·W₂``.
- **experts**: ``p = softmax(u·W_g)`` over all n_experts; the top_k largest
  are chosen; ``gates = scaling · the chosen p AS THEY ARE`` (no division by
  their sum); ``FF = Σ_{e chosen and held} gate_e · (silu(u·W₁ᵉ) ⊙
  u·W₃ᵉ)·W₂ᵉ + (silu(u·S₁) ⊙ u·S₃)·S₂`` — a loop over the held experts, each
  on every token, the gates (0 where the token did not choose it) the mask;
  the shared expert on every token. The layer's **balance loss**, within one
  row of S tokens: ``Σ_e f_e · P_e``, ``f_e = count_e · n_experts / (top_k ·
  S)`` from the COUNT of the row's tokens that chose e (no gradient), ``P_e``
  the row's mean of ``p_e``.
- end: ``norm`` → the untied head, mean cross-entropy over the positions
  with a target, plus ``alpha`` × Σ over the expert layers of the mean over
  the batch's rows of the balance loss.

Departures from a whole model, the same in the program: only the experts
``held_first … held_first + held − 1`` (those whose weights are in the tree)
are computed — what absent experts would add is left out —, and embedding
and head hold the vocabulary's first rows / columns.

**What the routers chose.** A token whose 6th and 7th probabilities lie
closer than the bf16 stream resolves chooses another set in the program than
here, and its experts' gradients then differ by whole tokens, not by
rounding. So the reference can be GIVEN the sets the program chose
(``chosen``: one [B, S, n_experts] bool an expert layer) and gates and counts
by them; it reports, a layer, the tokens whose own set differs (``differ``)
and how far below its own last chosen probability a given-but-not-own expert
lies at worst (``worst_margin``, RELATIVE to that last chosen probability: a
near-tie flipped reads 1e-3, a wrong rule the probabilities' spread).

It reads the program's parameter tree as the program lays it out (one entry
a run of a repeated sub-pattern, ``_groups``; a kind's layers of the run
stacked on a leading axis) and walks the layers one at a time — a
``lax.scan`` over a run's stack, so that the compiled reference is one
layer's size a kind: its loops are over whole layers, rows, experts and
blocks of the SAME equations, never a rearrangement of them. A row is worked
alone — rows meet in the loss's means only — under ``jax.checkpoint``, each
layer under one of its own, attention a block of QUERY_BLOCK query rows
under one more, and what is a function of one token — a feed-forward half,
the head's loss — TOKEN_BLOCK tokens at a time, each block under one more:
4 rows of 8,192 tokens and their gradient then take one row's q, k, v, one
block's logits and one token block's hidden tensors beside the step's state
and the gradient (the running sum over the rows and a row's: the caller
makes it a part of the parameter tensors at a time).

Switches for the readings a tolerance must catch, never for what the model
is: ``stats_dtype`` (what the configuration computes in float32 — the
router's logits and probabilities, attention's logits and softmax, the
head's logits and log-softmax — rounded to a narrower type), ``operand_dtype``
(the forward matmuls' operands rounded, one scale a tensor), ``drop_routed``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

EXPERTS = {"D": False, "E": True}
QUERY_BLOCK = 256
# what is a function of ONE token (a feed-forward half, the head's loss) is
# worked this many tokens of a row at a time, each block under a checkpoint
# of its own: memory, not meaning
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


def _as_stats(x, sizes):
    """x as ``stats_dtype`` holds it element for element (no scale), its
    gradient passed on: the control for what must be float32. By
    ``lax.reduce_precision``: a cast there and back is one the TPU compiler
    may leave out (excess precision is allowed it), and then the control
    controls nothing (my chip run, PR 55: every reading 0)."""
    dtype = sizes.get("stats_dtype")
    if dtype is None:
        return x
    info = jnp.finfo(dtype)
    rounded = jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                       mantissa_bits=info.nmant)
    return x + jax.lax.stop_gradient(rounded - x)


def _mm(spec, a, b, sizes):
    dtype = sizes.get("operand_dtype")
    return jnp.einsum(spec, _rounded(a, dtype), _rounded(b, dtype))


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(sizes):
    """The rope / 2 frequencies, from the formulas in the module docstring."""
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
    """x [..., S, rope] rotated. ``interleaved``: the channels arrive as
    pairs (2i, 2i + 1) and are de-interleaved first, as the published code
    does; then pairs (i, i + rope/2), angle position · inv_freq_i."""
    s, d = x.shape[-2:]
    if sizes["rope_pairing"] == "interleaved":
        x = x.reshape(x.shape[:-1] + (d // 2, 2))
        x = jnp.swapaxes(x, -1, -2).reshape(x.shape[:-2] + (d,))
    elif sizes["rope_pairing"] != "half":
        raise ValueError(sizes["rope_pairing"])
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * yarn_inv_freq(sizes)
    scale = (_mscale(sizes["rope_factor"], sizes["rope_mscale"])
             / _mscale(sizes["rope_factor"], sizes["rope_mscale_all_dim"]))
    cos, sin = jnp.cos(angles) * scale, jnp.sin(angles) * scale
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def softmax_scale(sizes):
    m = _mscale(sizes["rope_factor"], sizes["rope_mscale_all_dim"])
    return m * m / math.sqrt(sizes["nope"] + sizes["rope"])


def mla(u, p, sizes):
    """u [S, D] → [S, D]: multi-head latent attention on one row."""
    nope, rope, rank = sizes["nope"], sizes["rope"], sizes["rank"]
    s = u.shape[0]
    q = _mm("sd,dhk->hsk", u, p["wq"], sizes)               # [H, S, 192]
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
        logits = _as_stats(_mm("hqd,hkd->hqk", qb, k, sizes) * scale, sizes)
        visible = cols[None, :] <= (first + jnp.arange(block))[:, None]
        probs = _as_stats(jax.nn.softmax(
            jnp.where(visible, logits, -jnp.inf), axis=-1), sizes)
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
    """u [S, D] → (g [S, n_experts]: a token's gate on each expert of its
    set, 0 on the others; the row's balance loss; the report on ``given``).
    The set is ``given`` [S, n_experts] bool where one is given, else the
    router's own. Float32 throughout, whatever ``operand_dtype``;
    ``stats_dtype`` rounds the logits and the probabilities."""
    s, n = u.shape[0], p["router_w"].shape[-1]
    probs = _as_stats(jax.nn.softmax(
        _as_stats(u @ p["router_w"], sizes), axis=-1), sizes)
    top, idx = jax.lax.top_k(probs, sizes["top_k"])
    own = jnp.sum(jax.nn.one_hot(idx, n, dtype=probs.dtype), axis=1) > 0
    chosen = own if given is None else given
    # how far below the own set's last probability a given-but-not-own lies,
    # relative to it
    short = jnp.where(chosen & ~own, (top[:, -1:] - probs) / top[:, -1:], 0.0)
    report = {"differ": jnp.sum(jnp.any(chosen != own, axis=-1)),
              "worst_margin": jnp.max(short), "own": own}
    gates = sizes["scaling"] * jnp.where(chosen, probs, 0.0)
    # the balance loss from counts: f is a constant, P carries the gradient
    count = jax.lax.stop_gradient(jnp.sum(chosen, axis=0).astype(jnp.float32))
    f = count * n / (sizes["top_k"] * s)
    balance = jnp.sum(f * jnp.mean(probs, axis=0))
    return gates, balance, report


def experts(u, p, sizes, given=None):
    """u [S, D] → (the layer's feed-forward output [S, D]: the held experts'
    part and the shared expert; the row's balance loss; the report)."""
    gates, balance, report = routed_gates(u, p, sizes, given)

    def feed_forward(u, gates):
        out = _swiglu(u, p["shared_w1"], p["shared_w3"], p["shared_w2"], sizes)
        if sizes.get("drop_shared"):
            out = jnp.zeros_like(u)
        if sizes.get("drop_routed"):
            return out

        def add_expert(out, held):          # every held expert, masked
            w1, w3, w2, g = held
            return out + g[:, None] * _swiglu(u, w1, w3, w2, sizes), None

        first = sizes["held_first"]
        mine = gates[:, first:first + p["w1"].shape[0]].T      # [held, block]
        return jax.lax.scan(add_expert, out,
                            (p["w1"], p["w3"], p["w2"], mine))[0]

    return _by_tokens(feed_forward, u, gates), balance, report


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
    """One layer on one row, x [S, D] → (x', the row's balance loss — 0 for
    a dense layer —, the router's report or None)."""
    h = x + mla(_norm(x, p["attn_norm"], sizes["eps"]), p, sizes)
    u = _norm(h, p["ffn_norm"], sizes["eps"])
    if not EXPERTS[kind]:
        dense = _by_tokens(lambda u: _swiglu(
            u, p["w_gate"], p["w_up"], p["w_down"], sizes), u)
        return h + dense, jnp.zeros((), jnp.float32), None
    f, balance, report = experts(u, p, sizes, given)
    return h + f, balance, report


def _row(params, tokens, targets, chosen, sizes):
    """One row [S] (``chosen``: None, or the row's sets, [expert layers, S,
    n_experts]) → (its summed negative log-likelihood, its targets, the sum
    over its expert layers of the balance loss, the expert layers' reports
    stacked in order or None). A run of layers of one kind is a ``scan``
    over the run's stacked tensors, each layer under ``jax.checkpoint``: a
    program of one layer's size a kind."""
    x = params["wte"][tokens]
    reports, balance, seen = [], 0.0, 0
    for (sub, reps), group in zip(_groups(sizes["pattern"]), params["blocks"],
                                  strict=True):
        if len(sub) != 1:
            raise ValueError(f"a run of mixed kinds {sub!r}: this family's "
                             "patterns are dense layers, then expert layers")
        sets = None
        if EXPERTS[sub] and chosen is not None:
            sets, seen = chosen[seen:seen + reps], seen + reps

        def one_layer(x, layer_in, kind=sub):
            p, given = layer_in
            x, b, report = layer(x, p, given, kind, sizes)
            return x, (b, report)

        x, (b, report) = jax.lax.scan(jax.checkpoint(one_layer), x,
                                      (group[sub], sets))
        balance = balance + jnp.sum(b)
        if EXPERTS[sub]:
            reports.append(report)
    x = _norm(x, params["final_norm"], sizes["eps"])

    def nll_of(x, targets):
        logp = _as_stats(jax.nn.log_softmax(_as_stats(
            _mm("sd,dv->sv", x, params["lm_head"], sizes), sizes)), sizes)
        mask = targets >= 0
        nll = -jnp.take_along_axis(
            logp, jnp.where(mask, targets, 0)[:, None], axis=-1)[:, 0]
        return nll * mask

    reports = (jax.tree.map(lambda *r: jnp.concatenate(r), *reports)
               if reports else None)
    return (jnp.sum(_by_tokens(nll_of, x, targets)), jnp.sum(targets >= 0),
            balance, reports)


def loss_and_routing(params, tokens, targets, sizes, chosen=None):
    """tokens / targets [B, S] (targets: the next token, −1 = none) → (the
    loss: mean cross-entropy + alpha · Σ over the expert layers of the rows'
    mean balance loss; one report an expert layer — ``differ`` summed and
    ``worst_margin`` the largest over the rows, ``own`` the router's own
    sets, [B, S, n_experts] bool; the balance term before alpha). ``chosen``:
    None, or the sets to gate and count by, as ``own`` has them, one an
    expert layer."""
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    # a row's sets: [B, expert layers, S, n_experts]
    sets = jnp.stack(list(chosen), axis=1) if chosen else None
    row = jax.checkpoint(lambda t: _row(params, *t, sizes))
    nll, count, balance, reports = jax.lax.map(row, (tokens, targets, sets))
    layers = 0 if reports is None else reports["differ"].shape[1]
    reports = [{"differ": jnp.sum(reports["differ"][:, i]),
                "worst_margin": jnp.max(reports["worst_margin"][:, i]),
                "own": reports["own"][:, i]} for i in range(layers)]
    balance = jnp.mean(balance)
    loss = jnp.sum(nll) / jnp.maximum(jnp.sum(count), 1)
    return loss + sizes["alpha"] * balance, reports, balance


def loss(params, tokens, targets, sizes, chosen=None):
    return loss_and_routing(params, tokens, targets, sizes, chosen)[0]
