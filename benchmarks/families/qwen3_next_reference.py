"""Qwen3-Next (Gated DeltaNet and gated attention over a mixture of experts
beside a gated shared expert), forward, loss — balance loss included — and
what the routers chose, in straight ``jax.numpy`` and float32.

The benchmark's plain reference for family ``qwen3_next``
(Qwen3-Next-80B-A3B-Instruct, ``model_type: qwen3_next``;
https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct;
modeling_qwen3_next.py is the published code; the delta rule is
arXiv:2412.06464): no kernel, no chunk form, no solve, no sort, no row buffer
or grouped product, no mixed precision, nothing imported from ``ray_tpu``.
The caller sets ``jax.default_matmul_precision("highest")``.

``norm(x; w) = x · rsqrt(mean(x²) + eps) · (1 + w)`` (zero-centred). Every
layer: ``h = x + Mixer(norm(x; w_op))``, ``x' = h + Experts(norm(h; w_ffn))``,
the mixer's kind from ``sizes["pattern"]``:

- ``L`` — **Gated DeltaNet** on one row u [S, D]: ``[q, k, v, z] = u·W_qkvz``,
  ``[b, a] = u·W_ba`` (H_k key heads of d_k, H_v = r·H_k value heads of d_v);
  ``q‖k‖v ← silu(conv(q‖k‖v))``, the conv causal and depthwise, K taps, no
  bias (tap K−1 meets the current token); q, k ← ``x · rsqrt(Σx² + 1e-6)``
  over the head, q times d_k^-½; ``β = sigmoid(b)``, ``g = −exp(A_log) ·
  softplus(a + dt_bias)``; value head h reads key head h // r; then TOKEN BY
  TOKEN, S ∈ R^{d_k × d_v} from 0:

      S ← e^{g_t} S;    S ← S + k_t ⊗ β_t (v_t − Sᵀ k_t);    o_t = Sᵀ q_t

  (a ``lax.scan`` over the row's tokens — in blocks of SCAN_BLOCK under
  ``jax.checkpoint``, which is memory, not meaning; the products written as
  sums of elementwise products, so no matmul precision enters); ``y = o ·
  rsqrt(mean(o²) + eps) · w_n · silu(z)`` over each head's d_v (this gain is
  NOT zero-centred); ``y·W_out``. ``sizes["order"]`` ``"published"`` reads
  ``W_qkvz``'s columns grouped by key head (a key head's q, k, then its r
  value heads' v, then their z) and ``W_ba``'s likewise (b, a), as
  ``fix_query_key_value_ordering`` does; ``"flat"`` takes weights whose
  columns are q, k, v, z and b, a (the program's: the configuration's
  ``assumed`` says so; tests/test_qwen3_next.py maps one onto the other).
- ``F`` — **gated attention**: ``[q, gate] = u·W_q`` a head (q hd, then gate
  hd), ``k, v = u·W_k, u·W_v`` (KH heads, each serving H / KH of q's);
  ``q ← norm(q; w_q)``, ``k ← norm(k; w_k)`` over the head; rotate-half RoPE
  at ``theta`` on channels 0 … rotary − 1 (pairs (i, i + rotary/2)); ``o =
  softmax(q·kᵀ · hd^-½ + causal)·v``, a block of query rows at a time;
  ``o ⊙ sigmoid(gate)``; ``· W_o``.
- **experts**: ``p = softmax(u·W_r)`` over all n_experts; the top_k largest
  chosen; ``gates = the chosen p over their sum``; ``Σ_{e chosen and held}
  gate_e · (silu(u·W₁ᵉ) ⊙ u·W₃ᵉ)·W₂ᵉ + sigmoid(u·w_g) · (silu(u·S₁) ⊙
  u·S₃)·S₂``. The layer's **balance loss**, within one row of S tokens:
  ``Σ_e f_e · P_e``, ``f_e = count_e · n_experts / (top_k · S)`` from the
  COUNT of the row's tokens that chose e (no gradient), ``P_e`` the row's
  mean of ``p_e``.
- end: ``norm`` → the untied head, mean cross-entropy over the positions with
  a target, plus ``alpha`` × Σ over the layers of the mean over the batch's
  rows of the balance loss.

Departures from a whole model, the same in the program: only the experts
``held_first … held_first + held − 1`` (those whose weights are in the tree)
are computed — what absent experts would add is left out —, and embedding
and head hold the vocabulary's first rows / columns.

**What the routers chose**, as the DeepSeek reference has it and for its
reason: the reference can be GIVEN the sets the program chose (``chosen``:
one [B, S, n_experts] bool a layer) and gates and counts by them; it reports,
a layer, the tokens whose own set differs (``differ``) and how far below its
own last chosen probability a given-but-not-own expert lies at worst
(``worst_margin``, relative to that probability).

It reads the program's parameter tree as the program lays it out (one entry
a run of a repeated sub-pattern, ``_groups``; a kind's layers of the run
stacked on a leading axis) and walks the layers one at a time — a
``lax.scan`` over a run's repeats. A row is worked alone — rows meet in the
loss's means only — under ``jax.checkpoint``, each layer under one of its
own, attention a block of QUERY_BLOCK query rows under one more, and what is
a function of one token TOKEN_BLOCK tokens at a time.

Switches for the readings a tolerance must catch, never for what the model
is: ``operand_dtype`` (every forward matmul's operands rounded, one scale a
tensor), ``scan_dtype`` (q, k and v as the recurrence reads them, rounded),
``gate_dtype`` (attention's output gate: the sigmoid and its product, rounded
element for element).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256
TOKEN_BLOCK = 2048
SCAN_BLOCK = 128        # tokens of the recurrence under one checkpoint


def _by_tokens(fn, *per_token):
    """``fn`` (arrays [block, ...] → a tree of [block, ...]) over the leading
    axis of ``per_token`` in blocks of TOKEN_BLOCK, each under
    ``jax.checkpoint``; the blocks' results joined along that axis."""
    s = per_token[0].shape[0]
    block = min(TOKEN_BLOCK, s)
    if s % block:
        block = s
    cut = [x.reshape((s // block, block) + x.shape[1:]) for x in per_token]
    out = jax.lax.map(jax.checkpoint(lambda xs: fn(*xs)), cut)
    return jax.tree.map(lambda y: y.reshape((s,) + y.shape[2:]), out)


def _norm(x, w, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * (1.0 + w))


def _rounded(x, dtype):
    """x as ``dtype`` holds it (one scale a tensor: the largest magnitude at
    the top of the type's binades), its gradient passed on. By
    ``lax.reduce_precision``, as ``_as`` and for its reason: with the
    recurrence's q, k and v the cast there and back WAS left out on the chip
    and the control read 0 (my chip run, PR 61)."""
    if dtype is None:
        return x
    info = jnp.finfo(dtype)
    scale = jnp.max(jnp.abs(x)) / 2.0 ** (2 ** (info.nexp - 1) - 1)
    q = jax.lax.reduce_precision(x / scale, exponent_bits=info.nexp,
                                 mantissa_bits=info.nmant) * scale
    return x + jax.lax.stop_gradient(q - x)


def _as(x, dtype):
    """x as ``dtype`` holds it element for element (no scale), its gradient
    passed on. By ``lax.reduce_precision``: a cast there and back is one the
    TPU compiler may leave out."""
    if dtype is None:
        return x
    info = jnp.finfo(dtype)
    rounded = jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                       mantissa_bits=info.nmant)
    return x + jax.lax.stop_gradient(rounded - x)


def _mm(spec, a, b, sizes):
    dtype = sizes.get("operand_dtype")
    return jnp.einsum(spec, _rounded(a, dtype), _rounded(b, dtype))


def _conv(x, w):
    """x [S, C], w [K, C] → the causal depthwise conv, tap K−1 on the
    current token, no bias."""
    K, S = w.shape[0], x.shape[0]
    padded = jnp.pad(x, ((K - 1, 0), (0, 0)))
    return sum(padded[tap:tap + S] * w[tap] for tap in range(K))


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta):
    """The recurrence of the module docstring, token by token: q, k [S, H,
    d_k], v [S, H, d_v] (a value head's own copy of its key head's rows), g,
    beta [S, H] → o [S, H, d_v]."""
    s, h, dk = q.shape
    dv = v.shape[-1]

    def token(S, xs):
        qt, kt, vt, gt, bt = xs
        S = jnp.exp(gt)[:, None, None] * S
        seen = jnp.sum(S * kt[:, :, None], axis=1)              # Sᵀ k
        S = S + kt[:, :, None] * (bt[:, None] * (vt - seen))[:, None, :]
        return S, jnp.sum(S * qt[:, :, None], axis=1)           # Sᵀ q

    block = SCAN_BLOCK if s % SCAN_BLOCK == 0 else s

    def tokens(S, xs):
        return jax.lax.scan(token, S, xs)

    cut = [x.reshape((s // block, block) + x.shape[1:])
           for x in (q, k, v, g, beta)]
    _, o = jax.lax.scan(jax.checkpoint(tokens),
                        jnp.zeros((h, dk, dv), jnp.float32), tuple(cut))
    return o.reshape(s, h, dv)


def _unpacked(qkvz, ba, sizes):
    """The fused projections' outputs [S, ·] → q, k [S, Hk, dk], v, z [S, Hv,
    dv], b, a [S, Hv], by ``sizes["order"]``."""
    s = qkvz.shape[0]
    hk, hv, dk, dv = sizes["Hk"], sizes["Hv"], sizes["dk"], sizes["dv"]
    r = hv // hk
    if sizes["order"] == "published":
        per = qkvz.reshape(s, hk, 2 * dk + 2 * r * dv)
        q, k = per[..., :dk], per[..., dk:2 * dk]
        v = per[..., 2 * dk:2 * dk + r * dv].reshape(s, hv, dv)
        z = per[..., 2 * dk + r * dv:].reshape(s, hv, dv)
        both = ba.reshape(s, hk, 2 * r)
        return (q, k, v, z, both[..., :r].reshape(s, hv),
                both[..., r:].reshape(s, hv))
    if sizes["order"] != "flat":
        raise ValueError(sizes["order"])
    kw, vw = hk * dk, hv * dv
    return (qkvz[:, :kw].reshape(s, hk, dk),
            qkvz[:, kw:2 * kw].reshape(s, hk, dk),
            qkvz[:, 2 * kw:2 * kw + vw].reshape(s, hv, dv),
            qkvz[:, 2 * kw + vw:].reshape(s, hv, dv), ba[:, :hv], ba[:, hv:])


def delta_mixer(u, p, sizes):
    """u [S, D] → [S, D]: the Gated DeltaNet mixer on one row."""
    s = u.shape[0]
    hk, hv, dk, dv = sizes["Hk"], sizes["Hv"], sizes["dk"], sizes["dv"]
    q, k, v, z, b, a = _unpacked(_mm("sd,de->se", u, p["w_qkvz"], sizes),
                                 _mm("sd,de->se", u, p["w_ba"], sizes), sizes)
    flat = jnp.concatenate([t.reshape(s, -1) for t in (q, k, v)], axis=-1)
    mixed = jax.nn.silu(_conv(flat, p["conv_w"]))
    kw = hk * dk
    q = _l2norm(mixed[:, :kw].reshape(s, hk, dk)) * dk ** -0.5
    k = _l2norm(mixed[:, kw:2 * kw].reshape(s, hk, dk))
    v = mixed[:, 2 * kw:].reshape(s, hv, dv)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    scan_dtype = sizes.get("scan_dtype")
    q, k, v = (_rounded(t, scan_dtype) for t in (q, k, v))
    o = delta_rule(jnp.repeat(q, hv // hk, axis=1),
                   jnp.repeat(k, hv // hk, axis=1), v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + sizes["eps"])
    y = o * p["delta_norm"] * jax.nn.silu(z)
    return _mm("se,ed->sd", y.reshape(s, hv * dv), p["w_out"], sizes)


def _rope(x, sizes):
    """x [H, S, hd]: channels 0 … rotary − 1 rotated (rotate-half, pairs
    (i, i + rotary/2)), the others as they are."""
    s, rot = x.shape[-2], sizes["rotary"]
    inv = sizes["theta"] ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def attention(u, p, sizes):
    """u [S, D] → [S, D]: gated attention on one row."""
    s = u.shape[0]
    heads, hd = p["wq"].shape[1], p["wk"].shape[-1]
    qg = _mm("sd,dhk->hsk", u, p["wq"], sizes)              # [H, S, 2·hd]
    q, gate = qg[..., :hd], qg[..., hd:]
    q = _rope(_norm(q, p["q_norm"], sizes["eps"]), sizes)
    k = _rope(_norm(_mm("sd,dhk->hsk", u, p["wk"], sizes), p["k_norm"],
                    sizes["eps"]), sizes)
    v = _mm("sd,dhk->hsk", u, p["wv"], sizes)
    group = heads // k.shape[0]
    k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    block = min(QUERY_BLOCK, s)
    if s % block:
        block = s
    cols = jnp.arange(s)

    def rows_of(args):
        """A block of query rows [H, block, hd] against every key."""
        qb, first = args
        logits = _mm("hqd,hkd->hqk", qb, k, sizes) / math.sqrt(hd)
        visible = cols[None, :] <= (first + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(visible, logits, -jnp.inf), axis=-1)
        return _mm("hqk,hkd->hqd", probs, v, sizes)

    blocks = q.reshape(heads, s // block, block, hd).swapaxes(0, 1)
    o = jax.lax.map(jax.checkpoint(rows_of),
                    (blocks, jnp.arange(s // block) * block))
    o = o.swapaxes(0, 1).reshape(heads, s, hd)
    gate_dtype = sizes.get("gate_dtype")
    o = _as(o * _as(jax.nn.sigmoid(_as(gate, gate_dtype)), gate_dtype),
            gate_dtype)
    return _mm("hsk,hkd->sd", o, p["wo"], sizes)


def _swiglu(u, w1, w3, w2, sizes):
    return _mm("sf,fd->sd", jax.nn.silu(_mm("sd,df->sf", u, w1, sizes))
               * _mm("sd,df->sf", u, w3, sizes), w2, sizes)


def routed_gates(u, p, sizes, given=None):
    """u [S, D] → (g [S, n_experts]: a token's gate on each expert of its
    set, 0 on the others; the row's balance loss; the report on ``given``).
    The set is ``given`` [S, n_experts] bool where one is given, else the
    router's own. Float32 throughout, whatever ``operand_dtype``."""
    s, n = u.shape[0], p["router_w"].shape[-1]
    probs = jax.nn.softmax(u @ p["router_w"], axis=-1)
    top, idx = jax.lax.top_k(probs, sizes["top_k"])
    own = jnp.sum(jax.nn.one_hot(idx, n, dtype=probs.dtype), axis=1) > 0
    chosen = own if given is None else given
    short = jnp.where(chosen & ~own, (top[:, -1:] - probs) / top[:, -1:], 0.0)
    report = {"differ": jnp.sum(jnp.any(chosen != own, axis=-1)),
              "worst_margin": jnp.max(short), "own": own}
    kept = jnp.where(chosen, probs, 0.0)
    gates = kept / jnp.sum(kept, axis=-1, keepdims=True)
    count = jax.lax.stop_gradient(jnp.sum(chosen, axis=0).astype(jnp.float32))
    f = count * n / (sizes["top_k"] * s)
    balance = jnp.sum(f * jnp.mean(probs, axis=0))
    return gates, balance, report


def experts(u, p, sizes, given=None):
    """u [S, D] → (the expert half's output [S, D]: the held experts' part
    and the gated shared expert; the row's balance loss; the report)."""
    gates, balance, report = routed_gates(u, p, sizes, given)

    def feed_forward(u, gates):
        out = (jax.nn.sigmoid(_mm("sd,do->so", u, p["shared_gate"], sizes))
               * _swiglu(u, p["shared_w1"], p["shared_w3"], p["shared_w2"],
                         sizes))
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
    equal ones the shortest sub-pattern): ``"LLLF"`` → ``[("L", 3),
    ("F", 1)]``."""
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


def layer(x, p, given, kind, sizes):
    """One layer on one row, x [S, D] → (x', the row's balance loss, the
    router's report)."""
    u = _norm(x, p["op_norm"], sizes["eps"])
    h = x + (delta_mixer(u, p, sizes) if kind == "L"
             else attention(u, p, sizes))
    f, balance, report = experts(_norm(h, p["ffn_norm"], sizes["eps"]), p,
                                 sizes, given)
    return h + f, balance, report


def _row(params, tokens, targets, chosen, sizes):
    """One row [S] (``chosen``: None, or the row's sets, [layers, S,
    n_experts]) → (its summed negative log-likelihood, its targets, the sum
    over its layers of the balance loss, the layers' reports stacked in
    order). A run of the pattern is a ``scan`` over its repeats, each layer
    under ``jax.checkpoint``."""
    x = params["wte"][tokens]
    reports, balance, seen = [], 0.0, 0
    for (sub, reps), group in zip(_groups(sizes["pattern"]), params["blocks"],
                                  strict=True):
        n = len(sub) * reps
        sets = None
        if chosen is not None:
            sets = chosen[seen:seen + n].reshape((reps, len(sub))
                                                 + chosen.shape[1:])
        seen += n
        per_rep = {kind: sub.count(kind) for kind in dict.fromkeys(sub)}
        stacks = {kind: jax.tree.map(
            lambda t, c=count: t.reshape((reps, c) + t.shape[1:]), group[kind])
            for kind, count in per_rep.items()}

        def one_repeat(x, rep_in, sub=sub):
            stack, given = rep_in
            at, bs, rs = dict.fromkeys(sub, 0), [], []
            for i, kind in enumerate(sub):
                p = jax.tree.map(lambda t: t[at[kind]], stack[kind])
                at[kind] += 1
                x, b, r = jax.checkpoint(
                    lambda x, p, g, kind=kind: layer(x, p, g, kind, sizes))(
                    x, p, None if given is None else given[i])
                bs.append(b)
                rs.append(r)
            return x, (jnp.stack(bs), jax.tree.map(lambda *t: jnp.stack(t),
                                                   *rs))

        x, (b, report) = jax.lax.scan(one_repeat, x, (stacks, sets))
        balance = balance + jnp.sum(b)
        # [reps, layers of the sub-pattern, ...] → the layers in order
        reports.append(jax.tree.map(
            lambda t: t.reshape((n,) + t.shape[2:]), report))
    x = _norm(x, params["final_norm"], sizes["eps"])

    def nll_of(x, targets):
        logp = jax.nn.log_softmax(_mm("sd,dv->sv", x, params["lm_head"], sizes))
        mask = targets >= 0
        nll = -jnp.take_along_axis(
            logp, jnp.where(mask, targets, 0)[:, None], axis=-1)[:, 0]
        return nll * mask

    reports = jax.tree.map(lambda *r: jnp.concatenate(r), *reports)
    return (jnp.sum(_by_tokens(nll_of, x, targets)), jnp.sum(targets >= 0),
            balance, reports)


def loss_and_routing(params, tokens, targets, sizes, chosen=None):
    """tokens / targets [B, S] (targets: the next token, −1 = none) → (the
    loss: mean cross-entropy + alpha · Σ over the layers of the rows' mean
    balance loss; one report a layer — ``differ`` summed and ``worst_margin``
    the largest over the rows, ``own`` the router's own sets, [B, S,
    n_experts] bool; the balance term before alpha). ``chosen``: None, or
    the sets to gate and count by, as ``own`` has them, one a layer."""
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    sets = jnp.stack(list(chosen), axis=1) if chosen else None
    row = jax.checkpoint(lambda t: _row(params, *t, sizes))
    nll, count, balance, reports = jax.lax.map(row, (tokens, targets, sets))
    layers = reports["differ"].shape[1]
    reports = [{"differ": jnp.sum(reports["differ"][:, i]),
                "worst_margin": jnp.max(reports["worst_margin"][:, i]),
                "own": reports["own"][:, i]} for i in range(layers)]
    balance = jnp.mean(balance)
    loss = jnp.sum(nll) / jnp.maximum(jnp.sum(count), 1)
    return loss + sizes["alpha"] * balance, reports, balance


def loss(params, tokens, targets, sizes, chosen=None):
    return loss_and_routing(params, tokens, targets, sizes, chosen)[0]
