"""MiniCPM-SALA (lightning linear attention / block-sparse attention hybrid),
forward and loss, in straight ``jax.numpy`` and float32.

The benchmark's plain reference for family ``minicpm_sala``
(openbmb/MiniCPM-SALA, ``model_type: minicpm_sala``;
https://huggingface.co/openbmb/MiniCPM-SALA; Lightning Attention: Qin et al.,
arXiv:2401.04658; the sparse layer: MiniCPM4 / InfLLM-v2, arXiv:2506.07900):
no kernel, no chunked scan, no tiles, no mixed precision, nothing imported
from ``ray_tpu``. The caller sets ``jax.default_matmul_precision("highest")``.

``norm(x; g) = x · rsqrt(mean(x²) + eps) · g``. The embedding's output is
multiplied by ``scale_emb``; every layer is, with ``c = scale_depth /
√n_layer_published``, ``x ← x + c · mixer(norm(x))`` then ``x ← x + c ·
(silu(h·W_gate) ⊙ (h·W_up))·W_down``, ``h = norm(x)``; the head sees
``norm(x) · dim_model_base / d_model``; cross-entropy over every column the
head has. The mixer, by ``sizes["pattern"]`` (u = the normed input):

- ``L`` (lightning): ``q, k, v = u·W_q, u·W_k, u·W_v`` a head; ``q, k ←
  norm(·; g_q), norm(·; g_k)`` over the head's width; RoPE (θ, rotate-half)
  on q and k; **token by token** ``s_t = λ_h·s_{t−1} + k_tᵀ v_t``, ``o_t =
  (q_t / √hd)·s_t``, ``λ_h = exp(−2^{−8(h+1)/H})`` for the head's published
  index ``h = head_first + i`` of H; ``o ← norm(o; g_o)`` a head; ``o ⊙
  sigmoid(u·W_g)``; ``o·W_o``.
- ``S`` (sparse): ``q, k, v``, ``q, k ← norm`` as above, no RoPE; a
  key-value head serves n_head / n_kv_head query heads (its group). Rows of
  at most ``dense_len`` tokens: causal ``softmax(q kᵀ/√hd)·v``. Longer rows,
  **by masks**: compressed keys ``K^c_j = mean(k[stride·j : stride·j +
  kernel])``; ``p_t = softmax_j(q_t·K^c_j/√hd)`` over the j whose every token
  is ≤ t (``stride·j + kernel − 1 ≤ t``), summed over the group's heads;
  block b's score is the max of ``p_t[j]`` over the j whose tokens overlap
  the block's; block b is forced for token t when ``b < init_blocks`` or it
  holds one of t's last ``window`` tokens; token t is given the top_k
  highest of (forced first, then by score) among the blocks ``b ≤ t //
  block``; ``o_t = softmax over the given blocks' keys s ≤ t``. Then ``o ⊙
  sigmoid(u·W_g)``; ``o·W_o``. The selection carries no gradient.

**One departure, for the comparison's sake**: ``loss`` takes ``chosen`` —
the block ids the PROGRAM gave each token, a sparse layer — and then attends
over THOSE, while still making its own scores and its own top_k, and reports
(``selection``) the share of the program's visible choices that are its own
too and, for the others, how far below its own last chosen score their
score lies, as a share of that score (``worst_margin``). Two blocks whose
scores differ by rounding change places between bf16 and float32 layers
below; attending over different keys would then read as a wrong model.
A wrong selection RULE chooses blocks far below the last chosen score, and
the margin says so. With ``chosen=None`` it attends over its own choice.

Departures from a whole model, the same in the program: only the heads whose
weights are in the tree are computed (a chip's share), and the
out-projections' partial sums go on as they are.

It reads the program's parameter tree as the program lays it out (one entry
a run of a repeated sub-pattern, ``_groups``; a kind's layers of the run
stacked in their order) and walks a run with a ``lax.scan`` over its
repeats, each layer under ``jax.checkpoint``; the MLP, the sparse layer and
the head go in blocks of rows so that a 16,384-token row fits beside the
step's state — the same numbers.

Two switches exist for the readings a tolerance must catch, never for what
the model is: ``operand_dtype`` (the forward matmuls' operands rounded to a
narrower type, one scale a tensor) and ``drop_pooling`` (a block's score is
its FIRST overlapping compressed key's, not the max: a wrong selection rule).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

SCAN_BLOCK = 128          # tokens of the recurrence under one jax.checkpoint
ROW_BLOCK = 256           # rows of the sparse layer / the MLP at a time
FORCED = 1e4              # a forced block's score (a sum of softmaxes is small)


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
    """x [B, S, H, hd], position = the index along S; the head's width split
    as [first half, second half] (rotate-half)."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def _blocks_of(n, want):
    """The largest divisor of n that is at most ``want``."""
    return max(d for d in range(1, min(n, want) + 1) if n % d == 0)


def lightning(u, p, sizes):
    """u [B, S, D] → [B, S, D]: the recurrence, one token at a time."""
    b, s, _ = u.shape
    heads, hd = p["wq"].shape[1], p["wq"].shape[2]
    q = _rope(_norm(_mm("bsd,dhk->bshk", u, p["wq"], sizes), p["q_norm"],
                    sizes["eps"]), sizes["theta"])
    k = _rope(_norm(_mm("bsd,dhk->bshk", u, p["wk"], sizes), p["k_norm"],
                    sizes["eps"]), sizes["theta"])
    v = _mm("bsd,dhk->bshk", u, p["wv"], sizes)
    gate = _mm("bsd,dhk->bshk", u, p["wg"], sizes)
    h = sizes["lightning_head_first"] + jnp.arange(heads, dtype=jnp.float32)
    lam = jnp.exp(-jnp.exp2(-8.0 * (h + 1.0)
                            / sizes["lightning_heads_published"]))

    def step(state, t):
        q_t, k_t, v_t = t                                   # [B, H, hd]
        state = (lam[None, :, None, None] * state
                 + k_t[..., :, None] * v_t[..., None, :])
        return state, jnp.einsum("bhk,bhkv->bhv", q_t / math.sqrt(hd), state)

    blk = math.gcd(s, SCAN_BLOCK)

    @jax.checkpoint
    def block(state, ts):
        return jax.lax.scan(step, state, ts)

    _, o = jax.lax.scan(block, jnp.zeros((b, heads, hd, hd), jnp.float32), tuple(
        jnp.moveaxis(t, 1, 0).reshape((s // blk, blk) + t.shape[:1] + t.shape[2:])
        for t in (q, k, v)))
    o = jnp.moveaxis(o.reshape((s,) + o.shape[2:]), 0, 1)   # [B, S, H, hd]
    o = _norm(o, p["o_norm"], sizes["eps"]) * jax.nn.sigmoid(gate)
    return _mm("bshk,hkd->bsd", o, p["wo"], sizes)


def block_scores(q, k, first, sizes):
    """q [B, KH, g, R, hd] (tokens ``first`` … of the row), k [B, KH, S, hd]
    → [B, KH, R, S / block]: each block's score for each token — FORCED for a
    forced block, −1 for one the token does not see."""
    z = sizes["sparse"]
    s, hd = k.shape[2], k.shape[3]
    rows = q.shape[3]
    n_c, nb = (s - z["kernel"]) // z["stride"] + 1, s // z["block"]
    starts = jnp.arange(n_c) * z["stride"]
    window = starts[:, None] + jnp.arange(z["kernel"])[None, :]
    kc = jnp.mean(k[:, :, window, :], axis=3)               # [B, KH, n_c, hd]
    t = first + jnp.arange(rows)
    logits = jnp.einsum("bkgrd,bkcd->bkgrc", q, kc) / math.sqrt(hd)
    seen = starts[None, :] + z["kernel"] - 1 <= t[:, None]  # [R, n_c]
    p = jnp.where(seen, jax.nn.softmax(
        jnp.where(seen, logits, -jnp.inf), axis=-1), 0.0)
    p = jnp.sum(jnp.nan_to_num(p), axis=2)                  # [B, KH, R, n_c]
    # the compressed keys whose tokens overlap block b's
    lo = jnp.arange(nb) * z["block"]
    overlap = ((starts[None, :] < lo[:, None] + z["block"])
               & (starts[None, :] + z["kernel"] > lo[:, None]))   # [nb, n_c]
    if sizes.get("drop_pooling"):
        overlap = overlap & (jnp.cumsum(overlap, axis=1) == 1)
    score = jnp.max(jnp.where(overlap, p[..., None, :], -1.0), axis=-1)
    b = jnp.arange(nb)[None, :]
    forced = (b < z["init_blocks"]) | (
        (b + 1) * z["block"] > (t - z["window"] + 1)[:, None])
    visible = b <= (t // z["block"])[:, None]
    return jnp.where(visible, jnp.where(forced, FORCED, score), -1.0)


def _sparse_rows(q, k, v, chosen, first, sizes):
    """One block of rows of the sparse branch: (o [B, KH, g, R, hd], how many
    of the program's visible choices there are, how many of them are the
    reference's own, the worst margin of the others)."""
    z = sizes["sparse"]
    s, hd = k.shape[2], k.shape[3]
    rows, nb = q.shape[3], s // z["block"]
    top = min(z["top_k"], nb)
    t = first + jnp.arange(rows)
    score = jax.lax.stop_gradient(block_scores(q, k, first, sizes))
    values, own = jax.lax.top_k(score, top)
    count = agree = jnp.zeros((), jnp.float32)
    worst = jnp.zeros((), jnp.float32)
    if chosen is None:
        chosen = own
    else:
        visible = chosen <= (t // z["block"])[None, None, :, None]
        theirs = jnp.take_along_axis(score, chosen, axis=-1)
        mine = jnp.any(chosen[..., :, None] == own[..., None, :], axis=-1)
        kth = values[..., -1:]
        margin = jnp.where(visible & ~mine, (kth - theirs) / kth, 0.0)
        count, agree = jnp.sum(visible), jnp.sum(visible & mine)
        worst = jnp.max(margin)
    given = jnp.any(chosen[..., None] == jnp.arange(nb), axis=-2)   # [B,KH,R,nb]
    keys = jnp.repeat(given, z["block"], axis=-1)                   # [B,KH,R,S]
    keys = keys & (jnp.arange(s)[None, :] <= t[:, None])
    logits = jnp.einsum("bkgrd,bksd->bkgrs", q, k) / math.sqrt(hd)
    p = jax.nn.softmax(jnp.where(keys[:, :, None], logits, -jnp.inf), axis=-1)
    return (jnp.einsum("bkgrs,bksd->bkgrd", p, v),
            jnp.asarray(count, jnp.float32), jnp.asarray(agree, jnp.float32),
            worst)


def sparse(u, p, sizes, chosen=None):
    """u [B, S, D] → ([B, S, D], the selection's report or None)."""
    b, s, _ = u.shape
    heads, kv, hd = p["wq"].shape[1], p["wk"].shape[1], p["wq"].shape[2]
    g = heads // kv
    q = _norm(_mm("bsd,dhk->bhsk", u, p["wq"], sizes), p["q_norm"],
              sizes["eps"]).reshape(b, kv, g, s, hd)
    k = _norm(_mm("bsd,dhk->bhsk", u, p["wk"], sizes), p["k_norm"],
              sizes["eps"])
    v = _mm("bsd,dhk->bhsk", u, p["wv"], sizes)
    gate = _mm("bsd,dhk->bhsk", u, p["wg"], sizes)
    report = None
    if s <= sizes["sparse"]["dense_len"]:
        logits = jnp.einsum("bkgqd,bksd->bkgqs", q, k) / math.sqrt(hd)
        causal = jnp.tril(jnp.ones((s, s), bool))
        o = jnp.einsum("bkgqs,bksd->bkgqd", jax.nn.softmax(
            jnp.where(causal, logits, -jnp.inf), axis=-1), v)
    else:
        rows = _blocks_of(s, ROW_BLOCK)

        @jax.checkpoint
        def of_rows(args):
            i, q_r, chosen_r = args
            return _sparse_rows(q_r, k, v, chosen_r, i * rows, sizes)

        q_r = jnp.moveaxis(q.reshape(b, kv, g, s // rows, rows, hd), 3, 0)
        chosen_r = None if chosen is None else jnp.moveaxis(
            chosen.reshape(b, kv, s // rows, rows, -1), 2, 0)
        o, count, agree, worst = jax.lax.map(
            of_rows, (jnp.arange(s // rows), q_r, chosen_r))
        o = jnp.moveaxis(o, 0, 3).reshape(b, kv, g, s, hd)
        report = {"agree_share": jnp.sum(agree) / jnp.maximum(jnp.sum(count), 1),
                  "worst_margin": jnp.max(worst)}
    o = o.reshape(b, heads, s, hd) * jax.nn.sigmoid(gate)
    return _mm("bhsk,hkd->bsd", o, p["wo"], sizes), report


def _groups(pattern):
    """A pattern as runs of a repeated sub-pattern, as the program stacks its
    layers (greedy from the left: the repeat that covers most layers, of
    equal ones the shortest sub-pattern): ``"LLLS"`` → ``[("L", 3), ("S", 1)]``."""
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
    """[(kind, that layer's parameters)] in the layers' order (for whoever
    wants one layer out of the stacks: the tier-1 tests)."""
    out = []
    for (sub, reps), group in zip(_groups(pattern), stacks, strict=True):
        for r in range(reps):
            seen = dict.fromkeys(sub, 0)
            for kind in sub:
                at = r * sub.count(kind) + seen[kind]
                seen[kind] += 1
                out.append((kind, jax.tree.map(lambda t, at=at: t[at],
                                               group[kind])))
    return out


def _mlp(x, p, sizes):
    """x [B, S, D] → x + c · SwiGLU(norm(x)), a block of rows at a time."""
    b, s, d = x.shape
    rows = _blocks_of(s, 8 * ROW_BLOCK)

    @jax.checkpoint
    def of_rows(x_r):
        h = _norm(x_r, p["mlp_norm"], sizes["eps"])
        hidden = (jax.nn.silu(_mm("brd,df->brf", h, p["w_gate"], sizes))
                  * _mm("brd,df->brf", h, p["w_up"], sizes))
        return x_r + sizes["depth_scale"] * _mm("brf,fd->brd", hidden,
                                                p["w_down"], sizes)

    out = jax.lax.map(of_rows, jnp.moveaxis(
        x.reshape(b, s // rows, rows, d), 1, 0))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, d)


def mixer_output(u, p, kind, sizes, chosen=None):
    """The mixer of ``kind`` alone on a normed input u [B, S, D] → [B, S, D]."""
    if kind == "L":
        return lightning(u, p, sizes)
    return sparse(u, p, sizes, chosen)[0]


def _layer(x, p, chosen, kind, sizes):
    u = _norm(x, p["norm"], sizes["eps"])
    if kind == "L":
        y, report = lightning(u, p, sizes), None
    else:
        y, report = sparse(u, p, sizes, chosen)
    return _mlp(x + sizes["depth_scale"] * y, p, sizes), report


def layers(x, pattern, stacks, sizes, chosen=None):
    """x through ``pattern``'s layers, one at a time, each under
    ``jax.checkpoint`` → (x, the sparse layers' selection reports in their
    order). ``stacks[g][kind]`` stacks run g's layers of a kind and the run's
    repeats are the steps of one ``lax.scan`` — one copy of a layer's weights
    and of its gradient in the compiled reference, not one a layer; the
    program's chosen ids ride along as the scan's inputs."""
    reports, before = [], 0
    for (sub, reps), group in zip(_groups(pattern), stacks, strict=True):
        per = sub.count("S")
        mine = None
        if chosen is not None and per:
            mine = jnp.stack(chosen[before:before + reps * per])
            mine = mine.reshape((reps, per) + mine.shape[1:])
        before += reps * per

        def repeat(x, xs, sub=sub):
            of_kind, ids = xs
            seen, out = dict.fromkeys(of_kind, 0), []
            for kind in sub:
                p = of_kind[kind]
                if sub.count(kind) > 1:
                    p = jax.tree.map(lambda t: t[seen[kind]], p)
                given = ids[seen[kind]] if kind == "S" and ids is not None else None
                seen[kind] += 1
                x, report = jax.checkpoint(functools.partial(
                    _layer, kind=kind, sizes=sizes))(x, p, given)
                if report is not None:
                    out.append(report)
            return x, out

        # (a kind that comes once a repeat is scanned as it is stacked: a
        # reshape here is a second copy of its gradient in the backward)
        x, out = jax.lax.scan(repeat, x, ({
            kind: stack if sub.count(kind) == 1 else jax.tree.map(
                lambda t: t.reshape((reps, -1) + t.shape[1:]), stack)
            for kind, stack in group.items()}, mine))
        reports += [{k: v[r] for k, v in o.items()}
                    for r in range(reps) for o in out]
    return x, reports


def _cross_entropy(x, targets, params, sizes):
    """(Σ of −log p(target) over the targets ≥ 0, their number), a block of
    rows at a time: the logits of a whole row never stand at once."""
    b, s, d = x.shape
    rows = _blocks_of(s, 8 * ROW_BLOCK)

    @jax.checkpoint
    def of_rows(args):
        x_r, t_r = args
        h = _norm(x_r, params["final_norm"], sizes["eps"]) * sizes["head_scale"]
        logp = jax.nn.log_softmax(_mm("brd,dv->brv", h, params["lm_head"], sizes))
        mask = t_r >= 0
        nll = -jnp.take_along_axis(
            logp, jnp.where(mask, t_r, 0)[..., None], axis=-1)[..., 0]
        return jnp.sum(nll * mask), jnp.sum(mask)

    total, count = jax.lax.map(of_rows, (
        jnp.moveaxis(x.reshape(b, s // rows, rows, d), 1, 0),
        jnp.moveaxis(targets.reshape(b, s // rows, rows), 1, 0)))
    return jnp.sum(total) / jnp.maximum(jnp.sum(count), 1)


def loss_and_selection(params, tokens, targets, sizes, chosen=None):
    """(mean cross-entropy of tokens / targets [B, S] — targets: the next
    token, −1 = none —, the sparse layers' selection reports in their order).
    ``chosen``: the program's block ids a sparse layer (module docstring)."""
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    x, reports = layers(params["wte"][tokens] * sizes["scale_emb"],
                        sizes["pattern"], params["blocks"], sizes, chosen)
    return _cross_entropy(x, targets, params, sizes), reports


def loss(params, tokens, targets, sizes, chosen=None):
    return loss_and_selection(params, tokens, targets, sizes, chosen)[0]
