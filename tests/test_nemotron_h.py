"""Nemotron-H (models/nemotron_h.py, ops/mamba2.py, ops/moe.latent_moe) against
the benchmark's plain float32 reference on seeded weights, at tiny sizes on
the CPU: the whole model (loss and gradient norm, remat on and off), each kind
of layer alone, the chunked scan against the token-by-token recurrence, the
share of a deployment tied to the uncut layer, no pair dropped, the MTP
targets, the mesh refusals, the pattern machinery and the rule over kinds."""

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks.families import nemotron_h as family
from benchmarks.families import nemotron_h_reference as reference
from ray_tpu.models import blocks, gpt2, nemotron_h as nh, parts
from ray_tpu.ops import mamba2, moe
from ray_tpu.parallel import mesh as mesh_lib
from ray_tpu.tracing import names

import moe_pr50_passes as pr50


def _batch(cfg, rows=2, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, 64, (rows, cfg.seq_len)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1)
    targets[:, -1] = -1
    return tokens, targets


def _layer_of(group, kind, i=0):
    """Layer i of ``kind`` out of one run's stacks."""
    return jax.tree.map(lambda t: t[i], group[kind])


def _both(cfg, params, tokens, targets, **switches):
    sizes = family.reference_sizes(cfg, **switches)
    with jax.default_matmul_precision("highest"):
        ref = jax.value_and_grad(
            lambda p: reference.loss(p, tokens, targets, sizes))(params)
        got = jax.jit(jax.value_and_grad(
            lambda p: nh.loss_fn(p, tokens, targets, cfg)))(params)
    return got, ref


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_gradient_equal_the_reference_in_float32(remat):
    cfg = nh.nemotron_h_tiny(dtype=jnp.float32, remat=remat)
    params = nh.init(cfg, jax.random.PRNGKey(1))
    (loss, grads), (ref_loss, ref_grads) = _both(cfg, params, *_batch(cfg))
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)
    np.testing.assert_allclose(optax.global_norm(grads),
                               optax.global_norm(ref_grads), rtol=1e-5)
    worst = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))),
        grads, ref_grads)
    assert max(jax.tree.leaves(worst)) < 1e-4, worst


def test_bf16_program_is_near_the_reference_and_the_switches_are_not():
    """The limits of the chip's check have something to catch: in bf16 the
    program stays near the reference; the reference with the MTP loss left
    out does not, and with the routed experts left out an expert layer's
    output is another (at these widths too little for the loss to show)."""
    cfg = nh.nemotron_h_tiny()
    params = nh.init(cfg, jax.random.PRNGKey(2))
    tokens, targets = _batch(cfg)
    (loss, _), (ref_loss, _) = _both(cfg, params, tokens, targets)
    assert abs(loss - ref_loss) < 2e-3 * ref_loss
    sizes = family.reference_sizes(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 16, cfg.d_model))
    p = _layer_of(params["blocks"][-1], "E")
    with jax.default_matmul_precision("highest"):
        no_mtp = reference.loss(params, tokens, targets,
                                {**sizes, "mtp_weight": 0.0})
        routed = reference.latent_moe(x, p, sizes)
        shared = reference.latent_moe(x, p, {**sizes, "drop_routed": True})
    assert abs(no_mtp - ref_loss) > 0.05 * ref_loss
    assert float(jnp.max(jnp.abs(routed - shared))) > 1e-4 * float(
        jnp.max(jnp.abs(shared)))


@pytest.mark.parametrize("kind", ["M", "E", "*"])
def test_each_kind_of_layer_alone_equals_the_reference(kind):
    cfg = nh.nemotron_h_tiny(dtype=jnp.float32, pattern=kind, mtp_pattern="")
    params = nh.init(cfg, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, cfg.seq_len, cfg.d_model))
    p = _layer_of(params["blocks"][0], kind)
    sizes = family.reference_sizes(cfg)
    with jax.default_matmul_precision("highest"):
        got = nh._layer(x, p, cfg, kind)
        want = reference.layers(x, kind, params["blocks"], sizes)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def _recurrence(x, dt, A, Bm, Cm):
    """The state-space recurrence a token at a time, in float32."""
    (B, _, H, P), (G, N) = x.shape, Bm.shape[2:]
    hg = H // G
    x, Bm, Cm = (t.astype(jnp.float32) for t in (x, Bm, Cm))

    def step(h, t):
        x_t, dt_t, b_t, c_t = t
        a = jnp.exp(dt_t * A).reshape(B, G, hg)
        dx = (x_t * dt_t[..., None]).reshape(B, G, hg, P)
        h = a[..., None, None] * h + dx[..., None] * b_t[:, :, None, None, :]
        return h, jnp.einsum("bghpn,bgn->bghp", h, c_t).reshape(B, H, P)

    _, y = jax.lax.scan(step, jnp.zeros((B, G, hg, P, N)), tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1)


def _chunked_xla(x, dt, A, Bm, Cm, chunk):
    """The chunked form in plain XLA with the kernels' dtype rules (what
    ops/mamba2.ssd_scan was before PR 41; its backward is AD's): the second
    oracle, so that a difference between the kernels and the recurrence can
    be told from the chunked form's own rounding of bf16 operands."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    Q = min(chunk, S)
    pad = -S % Q
    if pad:
        x, dt, Bm, Cm = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                         for t in (x, dt, Bm, Cm))
    nc, hg, dtype = (S + pad) // Q, H // G, x.dtype
    xc = x.reshape(Bsz, nc, Q, G, hg, P)
    dtc = dt.reshape(Bsz, nc, Q, G, hg)
    Bc = Bm.reshape(Bsz, nc, Q, G, N)
    Cc = Cm.reshape(Bsz, nc, Q, G, N)
    cum = jnp.cumsum(dtc * A.reshape(G, hg), axis=2)          # [B,nc,Q,G,hg]
    total = cum[:, :, -1]
    dx = (xc.astype(jnp.float32) * dtc[..., None]).astype(dtype)
    cb = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc,
                    preferred_element_type=jnp.float32)
    ci = jnp.moveaxis(cum, 2, -1)
    decay = ci[..., :, None] - ci[..., None, :]
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.exp(jnp.where(causal, decay, -jnp.inf))           # [B,nc,G,hg,Q,Q]
    m = (cb[:, :, :, None] * L).astype(dtype)
    y = jnp.einsum("bcghij,bcjghp->bcighp", m, dx,
                   preferred_element_type=jnp.float32)
    to_end = jnp.exp(total[:, :, None] - cum)
    dx_end = (dx.astype(jnp.float32) * to_end[..., None]).astype(dtype)
    added = jnp.einsum("bcjghp,bcjgn->bcghpn", dx_end, Bc,
                       preferred_element_type=jnp.float32)

    def step(h, xs):
        add, tot = xs
        return h * jnp.exp(tot)[..., None, None] + add, h

    _, starts = jax.lax.scan(step, jnp.zeros((Bsz, G, hg, P, N), jnp.float32),
                             (jnp.moveaxis(added, 1, 0),
                              jnp.moveaxis(total, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)
    y_state = jnp.einsum("bcign,bcghpn->bcighp", Cc, starts.astype(dtype),
                         preferred_element_type=jnp.float32)
    y = y + y_state * jnp.exp(cum)[..., None]
    return y.reshape(Bsz, nc * Q, H, P)[:, :S]


_TINY = dict(B=2, H=4, P=8, G=2, N=8)
_SCAN_CASES = {
    # at chunk boundaries, at a row that is no whole number of chunks, at one
    # shorter than a chunk, at one that is one chunk
    "64-16": dict(_TINY, seq=64, chunk=16),
    "50-16": dict(_TINY, seq=50, chunk=16),
    "10-16": dict(_TINY, seq=10, chunk=16),
    "48-48": dict(_TINY, seq=48, chunk=48),
    # the cell's proportions and dtype: one group of 16 heads of 64 over a
    # state of 128, two chunks of 128, one row
    "cell-bf16": dict(B=1, H=16, P=64, G=1, N=128, seq=256, chunk=128,
                      dtype=jnp.bfloat16, bc_scale=128 ** -0.5, tol=1e-2,
                      oracle_tol=1e-2),
    # two groups whose head tiles are whole lanes: blocks taken in place
    "two-groups": dict(B=1, H=4, P=64, G=2, N=128, seq=256, chunk=128),
    # 64 heads a group in four tiles of 16 (their d B, d C add up), Q = 256
    "hg64-q256": dict(B=1, H=64, P=8, G=1, N=8, seq=512, chunk=256),
    # Δ·A near 0: nothing decays, the carried state's part of y dominates
    "state-kept": dict(_TINY, seq=64, chunk=16, dt_scale=1e-3),
}


def _scan_inputs(case):
    """((x, dt, A, Bm, Cm), a weight for y) of a case, from one key."""
    c = {"dtype": jnp.float32, "dt_scale": 1.0, "bc_scale": 1.0, **case}
    B, H, P, G, N, seq = (c[k] for k in "B H P G N seq".split())
    k = jax.random.split(jax.random.PRNGKey(5), 6)
    x = jax.random.normal(k[0], (B, seq, H, P)).astype(c["dtype"])
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, seq, H))) * c["dt_scale"]
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    Bm, Cm = ((jax.random.normal(k_, (B, seq, G, N)) * c["bc_scale"]
               ).astype(c["dtype"]) for k_ in k[3:5])
    return (x, dt, A, Bm, Cm), jax.random.normal(k[5], (B, seq, H, P))


@pytest.mark.parametrize("case", list(_SCAN_CASES.values()),
                         ids=list(_SCAN_CASES))
def test_chunked_scan_equals_the_recurrence(case):
    """Value and all five gradients of ops/mamba2.ssd_scan (the Pallas kernel
    pair, interpreted here) against the token-by-token float32 recurrence,
    and against the chunked form in plain XLA under the same dtype rules
    (where operands are bf16 both stand a bf16 rounding from the recurrence:
    a fault of the kernels' shows against both)."""
    c = {"tol": 1e-4, "oracle_tol": 1e-4, **case}
    chunk, args, w = c["chunk"], *_scan_inputs(case)
    x = args[0]

    def both(f):
        return jax.value_and_grad(lambda *a: jnp.sum(jnp.sin(f(*a)) * w),
                                  argnums=(0, 1, 2, 3, 4))(*args)

    with jax.default_matmul_precision("highest"):
        got = mamba2.ssd_scan(*args, chunk)
        want = _recurrence(*args)
        oracle = _chunked_xla(*args, chunk)
        g_got, g_want, g_oracle = (both(f)[1] for f in (
            lambda *a: mamba2.ssd_scan(*a, chunk), _recurrence,
            lambda *a: _chunked_xla(*a, chunk)))
    assert got.dtype == jnp.float32 and got.shape == x.shape
    assert all(g.dtype == a.dtype and g.shape == a.shape
               for g, a in zip(g_got, args))

    def off(a, b):
        a, b = (np.asarray(t, np.float64) for t in (a, b))
        return np.linalg.norm(a - b) / np.linalg.norm(b)

    if case.items() >= _TINY.items():
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
        for a, b in zip(g_got, g_want):
            np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)
    offs = [(off(a, b), off(a, o)) for a, b, o in zip(
        (got,) + g_got, (want,) + g_want, (oracle,) + g_oracle)]
    assert all(r < c["tol"] and o < c["oracle_tol"] for r, o in offs), (
        np.round(offs, 6).tolist())


def test_state_kept_case_is_the_carried_states():
    """The last case above tests what it says: with Δ·A near 0 a late chunk's
    y is mostly the state it started from, not its own tokens'."""
    c = _SCAN_CASES["state-kept"]
    (x, dt, A, Bm, Cm), _ = _scan_inputs(c)
    last = slice(c["seq"] - c["chunk"], None)
    whole = mamba2.ssd_scan(x, dt, A, Bm, Cm, c["chunk"])[:, last]
    own = mamba2.ssd_scan(x[:, last], dt[:, last], A, Bm[:, last], Cm[:, last],
                          c["chunk"])
    assert (np.linalg.norm(whole - own) > 1.5 * np.linalg.norm(own))


def test_the_scan_leaves_no_decay_matrix_outside_its_kernels():
    """PR 41: at the cell's proportions (two rows of four chunks here) nothing
    of a chunk's [Q, Q] — the decay matrix, C·Bᵀ ∘ L, their casts, their
    gradients: 268 MB a layer at the cell's 256 chunks of 16 heads — is an
    XLA value, forward or backward: outside the two pallas_calls every value
    of the scan and of its gradient is no larger than y (x-sized, float32) or
    the chunk states, and none ends in [Q, Q] but the one lower triangle of
    ones the log-decays are summed with."""
    B, S, H, P, G, N, Q = 2, 512, 16, 64, 1, 128, 128
    sd = jax.ShapeDtypeStruct
    args = (sd((B, S, H, P), jnp.bfloat16), sd((B, S, H), jnp.float32),
            sd((H,), jnp.float32), sd((B, S, G, N), jnp.bfloat16),
            sd((B, S, G, N), jnp.bfloat16))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(mamba2.ssd_scan(*a, Q)), argnums=(0, 1, 2, 3, 4)))(
            *args)
    kernels, shapes = [], []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                kernels.append(str(eqn.params["name"]))
                shapes.extend(v.aval.shape for v in eqn.outvars)
                continue                      # what a kernel holds is VMEM's
            shapes.extend(v.aval.shape for v in eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert sorted(kernels) == ["ssd_chunk_bwd", "ssd_chunk_fwd"]
    largest = max(B * S * H * P, B * (S // Q) * H * P * N)
    assert max(int(np.prod(s)) for s in shapes) <= largest
    # [Q, Q] a chunk AND head (d B, d C are [Q, N] a chunk, and N = Q here)
    square = [s for s in shapes if s[-2:] == (Q, Q)
              and int(np.prod(s)) > B * (S // Q) * Q * Q]
    assert not square, square


# ---- the share ties to the model: the parts the shares give add up to the
# ---- uncut reference's layer output

def _whole_and_shares(kind):
    """An uncut one-layer config and the four shares of it."""
    whole = nh.nemotron_h_tiny(
        dtype=jnp.float32, pattern=kind, mtp_pattern="", held_first=0,
        held_count=32, mamba_heads=8, mamba_groups=4, n_head=8, n_kv_head=4)
    return whole, [nh.replace(whole, held_first=8 * i, held_count=8,
                              mamba_heads=2, mamba_groups=1, n_head=2,
                              n_kv_head=1) for i in range(4)]


def _cut(p, kind, i, whole):
    """Share i's slice of the uncut layer's parameters."""
    if kind == "E":
        return {**p, "w1": p["w1"][8 * i:8 * i + 8],
                "w2": p["w2"][8 * i:8 * i + 8]}
    if kind == "*":
        return {**p, "wq": p["wq"][:, 2 * i:2 * i + 2], "wk": p["wk"][:, i:i + 1],
                "wv": p["wv"][:, i:i + 1], "wo": p["wo"][2 * i:2 * i + 2]}
    P, N = whole.mamba_head_dim, whole.ssm_state
    inner, gn = whole.mamba_inner, whole.mamba_groups * whole.ssm_state
    ch = slice(2 * P * i, 2 * P * (i + 1))               # the group's channels
    xbc = np.r_[ch, inner + N * i:inner + N * (i + 1),
                inner + gn + N * i:inner + gn + N * (i + 1)]
    hd = slice(2 * i, 2 * i + 2)
    return {**p, "w_z": p["w_z"][:, ch], "w_xbc": p["w_xbc"][:, xbc],
            "w_dt": p["w_dt"][:, hd], "conv_w": p["conv_w"][:, xbc],
            "conv_b": p["conv_b"][xbc], "dt_bias": p["dt_bias"][hd],
            "A_log": p["A_log"][hd], "D": p["D"][hd],
            "gate_norm": p["gate_norm"][ch], "w_out": p["w_out"][ch]}


@pytest.mark.parametrize("kind", ["M", "E", "*"])
def test_the_shares_add_up_to_the_uncut_layer(kind):
    """32 experts in four shares of 8, Mamba and attention heads in shares of
    a group: the program's four partial outputs add up to the uncut
    REFERENCE's layer output — what every chip computes alike (the shared
    expert; the residual) counted once."""
    whole, shares = _whole_and_shares(kind)
    params = nh.init(whole, jax.random.PRNGKey(6))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, whole.seq_len, whole.d_model))
    p = _layer_of(params["blocks"][0], kind)
    with jax.default_matmul_precision("highest"):
        want = reference.layers(x, kind, params["blocks"],
                                family.reference_sizes(whole)) - x
        pieces = [nh._layer(x, _cut(p, kind, i, whole), cfg, kind) - x
                  for i, cfg in enumerate(shares)]
        total = sum(pieces)
        if kind == "E":      # the shared expert is in every share: count it once
            alike = reference.latent_moe(
                reference._norm(x, p["norm"], whole.rms_eps), p,
                {**family.reference_sizes(whole), "drop_routed": True})
            total = total - 3 * alike
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    # and a part is only a part
    assert float(jnp.max(jnp.abs(pieces[0] - want))) > 1e-3 * float(
        jnp.max(jnp.abs(want)))


def _fill_passes(monkeypatch, load, passes):
    """Make moe's row buffer one that the pairs of ``load`` (moe.held_load)
    fill ``passes`` times with the last pass partly full, and say its rows."""
    pairs = int(load["pairs"])
    rows = -(-2 * pairs // (2 * passes - 1))     # the last pass about half
    assert -(-pairs // rows) == passes and pairs % rows
    monkeypatch.setattr(moe, "row_buffer", lambda *shape: rows)
    return rows


def _held_load(cfg, p, x):
    """moe.held_load of the expert layer ``p`` on the stream x."""
    u = parts.rmsnorm(x, p["norm"], cfg.rms_eps).reshape(-1, cfg.d_model)
    return moe.held_load(u, p, top_k=cfg.top_k, held=cfg.held,
                         scaling=cfg.routed_scaling)


def _expert_layer_and_reference(cfg, stacks):
    """(program, reference): x → Σ sin(the expert layer's output) with every
    gradient — the input's and each tensor's of the layer, the router's
    among them — both in float32."""
    sizes = family.reference_sizes(cfg)

    def program(x, p):
        return jnp.sum(jnp.sin(nh._layer(x, p, cfg, "E")))

    def plain(x, p):
        return jnp.sum(jnp.sin(reference.layers(
            x, "E", [{"E": jax.tree.map(lambda t: t[None], p)}], sizes)))

    p = _layer_of(stacks[0], "E")
    return (lambda x: jax.value_and_grad(program, (0, 1))(x, p),
            lambda x: jax.value_and_grad(plain, (0, 1))(x, p))


def _assert_gradients_close(got, want, rtol=2e-4):
    """Value and every gradient leaf, each to ``rtol`` of the leaf's largest
    entry; the selection bias takes no gradient on either side."""
    np.testing.assert_allclose(got[0], want[0], rtol=rtol)
    flat_got = jax.tree_util.tree_leaves_with_path(got[1])
    flat_want = jax.tree.leaves(want[1])
    assert len(flat_got) == len(flat_want)
    for (path, a), b in zip(flat_got, flat_want):
        assert bool(jnp.all(jnp.isfinite(a))), path
        np.testing.assert_allclose(
            a, b, rtol=rtol, atol=rtol * float(jnp.max(jnp.abs(b))),
            err_msg=jax.tree_util.keystr(path))
    assert float(jnp.max(jnp.abs(want[1][1]["router_w"]))) > 0
    assert not np.any(np.asarray(got[1][1]["router_bias"]))


@pytest.mark.parametrize("passes", [1, 2, 3])
def test_no_pair_is_dropped_when_the_router_sends_everything_to_one_expert(
        passes, monkeypatch):
    """Every token's first choice on ONE held expert: the layer still equals
    the reference (nothing has a capacity), and the load it reports is all
    there — over one pass of the row buffer or over two or three with the
    last partly filled, in the value and in every gradient (the router's,
    which reaches it through the gates a pass looks up, included)."""
    cfg = nh.nemotron_h_tiny(dtype=jnp.float32, pattern="E", mtp_pattern="")
    params = nh.init(cfg, jax.random.PRNGKey(8))
    x = jax.random.normal(jax.random.PRNGKey(9), (2, cfg.seq_len, cfg.d_model))
    bias = np.zeros((1, cfg.n_experts), np.float32)
    bias[0, cfg.held_first + 3] = 10.0                   # chosen by every token
    stacks = [{"E": {**params["blocks"][0]["E"],
                     "router_bias": jnp.asarray(bias)}}]
    p = _layer_of(stacks[0], "E")
    tokens = 2 * cfg.seq_len
    with jax.default_matmul_precision("highest"):
        rows = _fill_passes(monkeypatch, _held_load(cfg, p, x), passes)
        got = nh._layer(x, p, cfg, "E")
        want = reference.layers(x, "E", stacks, family.reference_sizes(cfg))
        after = _held_load(cfg, p, x)
        program, plain = _expert_layer_and_reference(cfg, stacks)
        _assert_gradients_close(program(x), plain(x))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    assert int(after["max_per_expert"]) == tokens
    assert int(after["pairs"]) >= tokens and int(after["pairs_dropped"]) == 0
    assert int(after["tokens_without_held_expert"]) == 0
    assert (int(after["buffer_rows"]), int(after["buffer_passes"])) == (
        rows, passes)
    np.testing.assert_allclose(after["buffer_fill"],
                               int(after["pairs"]) / (passes * rows))
    assert 0.5 <= float(after["buffer_fill"]) < 1.0


def test_row_buffer_is_the_worst_case_where_that_is_small_and_passes_take_the_rest():
    # tiny: one tile of rows passes the worst case, so one pass takes any batch
    assert moe.row_buffer(128, 32, 4, 8) == 128 * 4
    assert moe.buffer_passes(128, 32, 4, 8) == 1
    # the cell: 1.25x the mean of 11,264 pairs in whole tiles of 512 rows (a
    # pass holds a batch up to 27 % over the mean), far under the worst
    # 262,144, which would take 19 passes
    assert moe.row_buffer(32768, 512, 22, 8) == 14336 == 28 * 512
    assert moe.buffer_passes(32768, 512, 22, 8) == 19
    # a router that sends every token to both held experts: 12,000 pairs over
    # two passes of 7,680 rows (the second partly filled), every one of them
    # in the sum
    assert (moe.row_buffer(6000, 4, 2, 2), moe.buffer_passes(6000, 4, 2, 2)
            ) == (7680, 2)
    k = jax.random.split(jax.random.PRNGKey(13), 3)
    p = {"router_w": jnp.zeros((8, 4)).at[:, :2].set(1.0),
         "router_bias": jnp.zeros((4,)),
         "w1": jax.random.normal(k[0], (2, 4, 6)),
         "w2": jax.random.normal(k[1], (2, 6, 4))}
    ell = jax.random.normal(k[2], (6000, 4))

    def routed(ell, p):
        return moe.routed_experts(jnp.ones((6000, 8)), ell, p, top_k=2,
                                  held=moe.Held(0, 2), scaling=1.0)[0]

    def dense(ell, p):                         # both gates are 1/2
        return sum(0.5 * jnp.square(jax.nn.relu(ell @ p["w1"][e])) @ p["w2"][e]
                   for e in range(2))

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(routed(ell, p), dense(ell, p),
                                   rtol=1e-4, atol=1e-4)
        got = jax.grad(lambda *a: jnp.sum(jnp.sin(routed(*a))), (0, 1))(ell, p)
        want = jax.grad(lambda *a: jnp.sum(jnp.sin(dense(*a))), (0, 1))(ell, p)
    for name in ("w1", "w2"):            # the gates are constants in `dense`
        np.testing.assert_allclose(got[1][name], want[1][name], rtol=1e-4,
                                   atol=1e-5 * float(jnp.max(jnp.abs(want[1][name]))))
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("passes", [1, 2, 3])
def test_rows_past_the_last_group_never_reach_a_result_or_a_gradient(
        passes, monkeypatch):
    """The TPU's grouped kernels — the compiler's, and since PR 60 the
    program's own (`ops/grouped_matmul.py`) — leave the rows past the last
    group as they found them (the chip run that lacked a mask: NaN gates'
    gradients, then a NaN router, then every pair on the first experts and a
    DMA past the buffer). Here the passes' seam, `moe.grouped_dot`, is made
    to leave NaN there, in its output
    and in its operand's cotangent: the layer and its gradients are what they
    were and what the float32 reference's are, the router's included — with
    one pass partly filled, and with two and three of which the last is."""
    from jax import lax

    real = lax.ragged_dot

    @jax.custom_vjp
    def dirty(lhs, rhs, sizes):
        return _nan_past(real(lhs, rhs, sizes, preferred_element_type=lhs.dtype),
                         sizes)

    def _nan_past(x, sizes):
        return jnp.where(jnp.arange(x.shape[0])[:, None] < jnp.sum(sizes),
                         x, jnp.nan)

    def fwd(lhs, rhs, sizes):
        return dirty(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        _, vjp = jax.vjp(lambda l, r: real(
            l, r, sizes, preferred_element_type=l.dtype), lhs, rhs)
        d_lhs, d_rhs = vjp(jnp.where(jnp.isnan(g), 0, g))
        return _nan_past(d_lhs, sizes), d_rhs, None

    dirty.defvjp(fwd, bwd)
    cfg = nh.nemotron_h_tiny(dtype=jnp.float32, pattern="E", mtp_pattern="")
    params = nh.init(cfg, jax.random.PRNGKey(11))
    x = jax.random.normal(jax.random.PRNGKey(12), (2, cfg.seq_len, cfg.d_model))
    p = _layer_of(params["blocks"][0], "E")
    _fill_passes(monkeypatch, _held_load(cfg, p, x), passes)
    program, plain = _expert_layer_and_reference(cfg, params["blocks"])
    want = program(x)
    monkeypatch.setattr(moe, "grouped_dot", dirty)
    got = program(x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    with jax.default_matmul_precision("highest"):
        _assert_gradients_close(program(x), plain(x))


@pytest.mark.parametrize("rows,sizes", [
    (160, [[64, 64]]),                           # one pass, 128 of 160 rows
    (80, [[64, 16], [0, 48]]),                   # two, the second 48 of 80
    (48, [[48, 0], [16, 32], [0, 32]]),          # three, the third 32 of 48
])
def test_passes_share_an_experts_run_of_rows(rows, sizes):
    """An expert's run of rows goes on in the next pass; each pass looks up
    its own rows' tokens, latents and gates, and the passes' sum — with its
    gradient by the latents, both weights AND the gates, whose cotangent
    every pass adds its own rows' scalars to — is the dense float32 sum."""
    T, passes = 64, len(sizes)
    k = jax.random.split(jax.random.PRNGKey(36), 5)
    gates = jax.random.uniform(k[0], (T, 2), minval=0.2)
    pairs = moe.held_pairs(jnp.ones((T, 2), bool), gates, rows=rows,
                           passes=passes + 1)     # and one that never runs
    assert pairs.per_expert.tolist() == [64, 64]
    assert pairs.group_sizes.tolist() == sizes + [[0, 0]]
    assert pairs.valid.sum(axis=1).tolist() == [sum(g) for g in sizes] + [0]
    # expert 0's tokens first, each once and in order; then expert 1's
    assert (pairs.key.reshape(-1)[:128] % T).tolist() == 2 * list(range(64))
    np.testing.assert_array_equal(pairs.gate_rows.reshape(-1)[:128],
                                  gates.T.reshape(-1))
    ell = jax.random.normal(k[1], (T, 4))
    w1, w2 = jax.random.normal(k[2], (2, 4, 6)), jax.random.normal(k[3], (2, 6, 4))

    def routed(ell, w1, w2, gates):
        pairs = moe.held_pairs(jnp.ones((T, 2), bool), gates, rows, passes + 1)
        return moe._run_passes(ell, (w1, w2), pairs.gates, pairs.gate_rows,
                               pairs.key, pairs.valid, pairs.group_sizes,
                               passes)

    def dense(ell, w1, w2, gates):
        return sum(gates[:, e, None]
                   * jnp.square(jax.nn.relu(ell @ w1[e])) @ w2[e]
                   for e in range(2))

    def graded(f):
        return jax.value_and_grad(
            lambda *a: jnp.sum(jnp.sin(f(*a))), (0, 1, 2, 3))(ell, w1, w2, gates)

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(routed(ell, w1, w2, gates),
                                   dense(ell, w1, w2, gates),
                                   rtol=1e-5, atol=1e-5)
        got, want = graded(routed), graded(dense)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        assert float(jnp.max(jnp.abs(b))) > 0
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(jnp.max(jnp.abs(b))))


class _Rows(NamedTuple):
    """The passes' rows with each row's token and gate looked up — what
    moe.HeldPairs held until PR 36, when the passes took the lookups over."""
    token: jax.Array
    gate: jax.Array
    valid: jax.Array
    group_sizes: jax.Array
    per_expert: jax.Array


def _looked_up(pairs: moe.HeldPairs, T: int) -> _Rows:
    # (looked up here, so that AD sees them: the rows' own copy out of the
    # sort, `gate_rows`, is a constant to it)
    return _Rows(pairs.key % T, pairs.gates[pairs.key], pairs.valid,
                 pairs.group_sizes, pairs.per_expert)


def _chosen_list_pairs(scores, bias, top_k, scaling, held, rows, passes):
    """What ops/moe computed until PR 34, written out plainly: the chosen ids
    [T, k], their gates gathered by id, the T · k (token, choice) pairs
    stably argsorted by held expert."""
    T = scores.shape[0]
    _, idx = jax.lax.top_k(scores + bias, top_k)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    gates = scaling * picked / jnp.sum(picked, axis=-1, keepdims=True)
    local = idx - held.first
    here = (local >= 0) & (local < held.count)
    key = jnp.where(here, local, held.count).reshape(T * top_k)
    per_expert = jnp.sum(key[:, None] == jnp.arange(held.count), axis=0,
                         dtype=jnp.int32)
    order = jnp.argsort(key, stable=True)
    total = passes * rows
    order = jnp.pad(order, (0, max(0, total - T * top_k)))[:total]
    valid = jnp.arange(total) < jnp.sum(per_expert)
    lo = (jnp.arange(passes) * rows)[:, None]
    ends = jnp.clip(jnp.cumsum(per_expert)[None, :], lo, lo + rows) - lo
    return idx, _Rows(
        token=(order // top_k).astype(jnp.int32).reshape(passes, rows),
        gate=gates.reshape(T * top_k)[order].reshape(passes, rows),
        valid=valid.reshape(passes, rows),
        group_sizes=jnp.diff(ends, axis=1, prepend=0).astype(jnp.int32),
        per_expert=per_expert)


def _membership_logits(case):
    """(logits [T, E], selection bias [E], top_k, held, rows) of a case."""
    T, E = 96, 32
    rng = np.random.default_rng(34)
    logits = rng.normal(0, 1.3, (T, E)).astype(np.float32)
    bias = rng.normal(0, 0.01, E).astype(np.float32)
    top_k, held, rows = 4, moe.Held(8, 8), 40
    if case == "ties_across_the_kth_place":
        # a few levels only, no bias: every row has equal biased scores on
        # both sides of its k-th place, many rows inside the held ids
        logits, bias = np.round(logits), np.zeros(E, np.float32)
        # and written out: ids 0 and 1 above a plateau over ids 5..20, of
        # which the k-th place takes 5 and 6 (not held), 7's equals stay out;
        # the next row's plateau starts inside the held ids: 10, 11 are in
        logits[0], logits[1] = -3.0, -3.0
        logits[0, [0, 1]], logits[0, 5:21] = 2.0, 1.0
        logits[1, [0, 31]], logits[1, 10:16] = 2.0, 1.0
    elif case == "no_held_expert_and_all_of_them":
        top_k = 10
        logits[0, 8:16] = -9.0                 # chooses none of the held
        logits[1, 8:16] = 9.0                  # chooses all eight
    elif case == "top_k_above_held":
        top_k, held, rows = 12, moe.Held(29, 3), 56
    elif case == "top_k_below_held":
        top_k, held, rows = 2, moe.Held(0, 16), 24
    return jnp.asarray(logits), jnp.asarray(bias), top_k, held, rows


@pytest.mark.parametrize("case", [
    "random", "ties_across_the_kth_place", "no_held_expert_and_all_of_them",
    "top_k_above_held", "top_k_below_held"])
def test_the_membership_dispatch_is_the_chosen_lists(case):
    """moe.route + moe.held_pairs (the chosen set as a mask, gates from a
    static slice, pairs sorted from the [T, held] membership) against the
    chosen-list form above: rows, validity, group sizes and loads equal
    element for element; gates and their derivative by the scores to 1e-6
    (22 float32 numbers added in another order)."""
    logits, bias, top_k, held, rows = _membership_logits(case)
    T, E = logits.shape
    passes = -(-T * min(top_k, held.count) // rows)
    assert passes > 1
    scaling, cot = 2.5, jax.random.normal(jax.random.PRNGKey(0), (passes, rows))

    def new(logits):         # the router's product with an identity is exact
        here, gates = moe.route(logits, jnp.eye(E), bias, top_k, scaling, held)
        return here, _looked_up(moe.held_pairs(here, gates, rows, passes), T)

    def old(logits):
        return _chosen_list_pairs(jax.nn.sigmoid(logits), bias, top_k, scaling,
                                  held, rows, passes)

    (here, got), (idx, want) = new(logits), old(logits)
    # the gates the sort laid beside the keys are the table's, row for row
    pairs = moe.held_pairs(*moe.route(logits, jnp.eye(E), bias, top_k, scaling,
                                      held), rows, passes)
    np.testing.assert_array_equal(np.where(pairs.valid, pairs.gate_rows, 0),
                                  np.where(got.valid, got.gate, 0))
    # the mask is the list: top_k a row, ties to the lower ids
    everyone, _ = moe.route(logits, jnp.eye(E), bias, top_k, scaling,
                            moe.Held(0, E))
    assert everyone.sum(axis=1).tolist() == [top_k] * T
    listed = np.zeros((T, E), bool)
    np.put_along_axis(listed, np.asarray(idx), True, axis=1)
    np.testing.assert_array_equal(everyone, listed)
    np.testing.assert_array_equal(here, listed[:, held.first:][:, :held.count])
    if case == "ties_across_the_kth_place":
        assert np.flatnonzero(listed[0]).tolist() == [0, 1, 5, 6]
        assert np.flatnonzero(listed[1]).tolist() == [0, 10, 11, 31]
    if case == "no_held_expert_and_all_of_them":
        assert here[:2].sum(axis=1).tolist() == [0, held.count]

    assert int(want.valid.sum()) == int(here.sum()) > rows
    np.testing.assert_array_equal(got.valid, want.valid)
    np.testing.assert_array_equal(got.per_expert, want.per_expert)
    np.testing.assert_array_equal(got.group_sizes, want.group_sizes)
    np.testing.assert_array_equal(jnp.where(got.valid, got.token, -1),
                                  jnp.where(want.valid, want.token, -1))
    np.testing.assert_allclose(jnp.where(got.valid, got.gate, 0),
                               jnp.where(want.valid, want.gate, 0),
                               rtol=1e-6, atol=1e-7)

    def pulled(f):           # d (the valid rows' gates · cot) / d logits:
        def scalar(logits):  # the scores' derivative times s · (1 − s)
            pairs = f(logits)[1]
            return jnp.sum(jnp.where(pairs.valid, pairs.gate * cot, 0))
        return jax.grad(scalar)(logits)

    d_got, d_want = pulled(new), pulled(old)
    assert float(jnp.max(jnp.abs(d_want))) > 0.01
    np.testing.assert_allclose(d_got, d_want, rtol=1e-6, atol=1e-6)


def test_mtp_targets_end_with_the_row():
    """The module at t embeds token t+1 and predicts token t+2: the last two
    positions of a row have no target, and a row's second loss is the
    reference's."""
    cfg = nh.nemotron_h_tiny(dtype=jnp.float32)
    params = nh.init(cfg, jax.random.PRNGKey(10))
    tokens, targets = _batch(cfg, rows=1)
    _, _, mtp_targets, _ = nh._hidden(params, tokens, jnp.asarray(targets), cfg)
    np.testing.assert_array_equal(mtp_targets[0, :-2], tokens[0, 2:])
    np.testing.assert_array_equal(mtp_targets[0, -2:], [-1, -1])
    with jax.default_matmul_precision("highest"):
        trunk, mtp = nh.losses(params, tokens, targets, cfg)
        want = reference.losses(params, tokens, targets,
                                family.reference_sizes(cfg))
    np.testing.assert_allclose([trunk, mtp], want, rtol=1e-6)


@pytest.mark.parametrize("axis", ["pp", "ep", "cp"])
def test_mesh_rules_refuse_what_cannot_run(axis):
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(**{axis: 2}), jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="not implemented"):
        nh.mesh_rules(nh.nemotron_h_tiny(), mesh)


def test_trains_through_the_one_factory_on_a_data_mesh():
    from ray_tpu.train.train_step import make_train_step

    cfg = nh.nemotron_h_tiny(remat=True)
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(fsdp=2), jax.devices()[:2])
    bundle = make_train_step(nh, cfg, mesh=mesh)
    tokens, targets = _batch(cfg, rows=4)
    batch = jax.device_put({"tokens": tokens, "targets": targets},
                           bundle.data_sharding)
    state, losses = bundle.state, []
    for _ in range(3):
        state, metrics = bundle.step_fn(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] and all(np.isfinite(losses))


# ---- the shared machinery

@pytest.mark.parametrize("pattern,groups", [
    ("MEMEMEMEM*E", [("ME", 4), ("M", 1), ("*", 1), ("E", 1)]),
    ("B" * 12, [("B", 12)]),
    ("*E", [("*", 1), ("E", 1)]),
    ("MEME*E", [("ME", 2), ("*", 1), ("E", 1)]),
    ("MEMEMEM*EMEMEMEM*",
     [("ME", 3), ("M", 1), ("*", 1), ("EM", 4), ("*", 1)]),
    (nh.nemotron_3_super_120b().pattern,
     [("MEMEMEM*E", 3), ("MEMEMEMEM*E", 4), ("ME", 3), ("M", 1), ("*", 1),
      ("EM", 4), ("E", 1)]),
])
def test_pattern_groups(pattern, groups):
    assert blocks.pattern_groups(pattern) == groups
    assert reference._groups(pattern) == groups
    assert "".join(sub * reps for sub, reps in groups) == pattern


def test_run_pattern_scans_repeats_and_applies_the_rest_in_order():
    """Each kind adds its own digit: the order of application is the
    pattern's, whichever layers one scan holds."""
    fns = {"a": lambda x, p: x * 10 + p["v"], "b": lambda x, p: x * 10 - p["v"]}
    pattern = "abababba"
    stacks, n = [], 0
    for sub, reps in blocks.pattern_groups(pattern):
        group = {}
        for kind in dict.fromkeys(sub):
            count = reps * sub.count(kind)
            group[kind] = {"v": jnp.arange(n, n + count, dtype=jnp.float32)}
            n += count
        stacks.append(group)
    want, seen = 0.0, {id(g[k]["v"]): 0 for g in stacks for k in g}
    for (sub, reps), group in zip(blocks.pattern_groups(pattern), stacks):
        for _ in range(reps):
            for kind in sub:
                i = seen[id(group[kind]["v"])]
                seen[id(group[kind]["v"])] += 1
                want = fns[kind](want, {"v": float(group[kind]["v"][i])})
    got = blocks.run_pattern(fns, pattern, jnp.zeros(()), stacks)
    assert float(got) == want


def test_the_rule_counts_applications_per_kind():
    """One rule over all the kinds: a candidate costs its bytes once an
    application of ITS kind, and the largest kind's block sets the working
    set of a run that holds both."""
    C, K = blocks.RematCandidate, blocks.KindShard
    a = K(5, (C(("x",), 100, 1000),), 700)
    b = K(1, (C(("y",), 100, 500),), 300)
    shard = gpt2.block_shard(gpt2.gpt2_tiny(), 8, 128, None, True)
    (head, run) = blocks.backward_phases(shard, {"a": a, "b": b}, [("aaaaab", 1)])
    model = blocks.model_working_set(shard, 6)
    assert (head, run) == (("head", model), ("aaaaab", model + 700))
    limit = blocks.REMAT_RESERVE_BYTES + run.nbytes
    keep = lambda extra: blocks.choose_remat_policy_kinds(
        [a, b], run.nbytes, limit + extra, 0)
    assert keep(0).saved == ()
    assert keep(100).saved == ("y",)             # one application fits
    assert keep(500).saved == ("x",)             # five of the better one
    assert keep(600) == blocks.RematPolicy(("x", "y"), 600, 600, limit + 600)
    # the one-kind rule is the same rule
    one = parts.choose_remat_policy(shard, 2, 2 ** 31, 0)
    assert one == blocks.choose_remat_policy_kinds(
        [K(2, tuple(parts.remat_candidates(shard)),
           parts.block_working_set(shard))],
        parts.rematted_working_set(shard, 2), 2 ** 31, 0)
    assert (blocks.model_working_set(shard, 2) + parts.block_working_set(shard)
            == parts.rematted_working_set(shard, 2))


def test_the_cells_kinds_and_the_familys_arithmetic():
    from benchmarks.harness import spec

    cell, config, _ = spec.load_cell("nemotron-3-super-120b-l11.dataset")
    cfg = family.program_config(config, cell)
    shapes = family.shapes(config, cell)
    assert shapes["params"] == nh.param_count(cfg) == 838_245_872
    assert family.train_flops_per_token(shapes) == nh.flops_per_token(cfg)
    base, kinds = nh.kind_shards(cfg, cell["per_chip_batch"], cfg.seq_len, None)
    assert {k: v.applications for k, v in kinds.items()} == {
        "M": 5, "E": 6, "*": 2}
    named = {n for k in kinds.values() for c in k.candidates for n in c.names}
    assert named <= set(names.RESIDUALS) and names.RES_MID not in named
    assert {names.RES_SSD_STATES, names.RES_MOE_LATENT, names.RES_Q} <= named
    assert base.head_rows == 128 and base.mlp_rows < cfg.seq_len


ROUTING = (names.RES_MOE_KTH, names.RES_MOE_LAST, names.RES_MOE_PAIR_KEY,
           names.RES_MOE_PAIR_GATE)


def test_the_cells_decision_from_its_shapes_keeps_the_routing_first():
    """PR 42: `nemotron-3-super-120b-l11.dataset`'s decision, from its shapes
    and a v5e's bytes_limit alone. The estimate follows the backward's phases
    — the scan of eight layers sets it, where its 1.7 GiB of stacked
    gradients meet eight block inputs and an expert layer's block, and the
    head, the MTP module and the five later layers are dead — so the budget is
    about a GiB where the sum of everything left it at -1.1; and the rule
    spends it on the routing's outcome first (a sort spared for 8 bytes a
    token, the router's float32 product for its scores), then on what matmul
    outputs still fit. The latent input does not."""
    from benchmarks.harness import spec
    from ray_tpu.train.train_step import _resident_bytes

    cell, config, _ = spec.load_cell("nemotron-3-super-120b-l11.dataset")
    cfg = family.program_config(config, cell)
    base, kinds = nh.kind_shards(cfg, cell["per_chip_batch"], cfg.seq_len, None)
    runs = blocks.pattern_groups(cfg.pattern) + blocks.pattern_groups(cfg.mtp_pattern)
    assert [blocks.run_name(r) for r in runs] == [
        "4 x scan(ME)", "M", "*", "E", "*", "E"]
    # a layer's gradients are its parameters' bytes: the kinds' add up to the
    # stacks'
    params = jax.eval_shape(lambda: nh.init(cfg, jax.random.PRNGKey(0)))
    nbytes = lambda tree: sum(x.size * x.dtype.itemsize
                              for x in jax.tree.leaves(tree))
    assert sum(k.applications * k.grad_bytes for k in kinds.values()) == (
        nbytes(params["blocks"]) + nbytes(params["mtp"]["blocks"]))

    phases = blocks.backward_phases(base, kinds, runs)
    assert [p.name for p in phases] == ["head", "E", "*", "E", "*", "M",
                                        "4 x scan(ME)"]
    largest = max(phases, key=lambda p: p.nbytes)
    assert largest.name == "4 x scan(ME)"
    # the sum the rule took until now: every block input, the head and the
    # largest block beside every gradient
    summed = blocks.model_working_set(base, 13) + max(
        k.block_bytes for k in kinds.values())
    assert summed - largest.nbytes > 2 ** 30
    # (the moments in the benchmark's optimizer are bfloat16: 12 B a parameter
    # with the gradients)
    resident = 3 * nbytes(params)
    policy = blocks.choose_remat_policy_kinds(
        tuple(kinds.values()), largest.nbytes, family.V5E_BYTES_LIMIT, resident)
    assert 2 ** 30 <= policy.budget_bytes <= 1.25 * 2 ** 30
    assert 0 < policy.saved_bytes <= policy.budget_bytes
    assert blocks.choose_remat_policy_kinds(
        tuple(kinds.values()), summed, family.V5E_BYTES_LIMIT, resident
    ).saved == ()
    # the order taken: Δ's projection (one MXU pass for 4 bytes a head), the
    # sort, the router's product — before any matmul output
    assert policy.saved[:4] == (names.RES_MAMBA_DT, names.RES_MOE_KTH,
                                names.RES_MOE_LAST, names.RES_MOE_SCORES)
    assert set(ROUTING) < set(policy.saved)
    assert names.RES_MAMBA_Z in policy.saved
    assert names.RES_MOE_LATENT not in policy.saved
    by_name = {c.names: c for c in kinds["E"].candidates}
    T = cell["per_chip_batch"] * cfg.seq_len
    assert by_name[(names.RES_MOE_KTH, names.RES_MOE_LAST)].nbytes == 8 * T
    assert by_name[(names.RES_MOE_SCORES,)].nbytes == 4 * T * 512
    assert by_name[(names.RES_MOE_PAIR_KEY,
                    names.RES_MOE_PAIR_GATE)].nbytes < 2 ** 22


def _count(jaxpr, pred):
    """Equations of ``jaxpr`` and every jaxpr inside it that ``pred`` takes."""
    return sum(bool(pred(eqn)) + sum(_count(sub, pred) for sub in
                                     jax.core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)


@pytest.mark.parametrize("saved,top_ks,sorts,router_products", [
    ((), 2, 2, 2),
    (ROUTING, 1, 1, 2),
    ((names.RES_MOE_SCORES,), 2, 2, 1),
    (ROUTING + (names.RES_MOE_SCORES,), 1, 1, 1),
    (names.RESIDUALS, 1, 1, 1),
], ids=["nothing", "routing", "scores", "routing_and_scores", "every_name"])
def test_a_kept_routing_outcome_is_not_made_again(saved, top_ks, sorts,
                                                  router_products):
    """The gradient of one checkpointed expert layer: with nothing kept its
    backward's second forward sorts the scores (`top_k`) and the pairs' keys
    again and multiplies the router's product a second time; with the
    `top_k`'s last value and index and the sorted keys kept it sorts once,
    with the scores kept — the sigmoid's backward reads the NAMED value
    (moe._sigmoid) — the product is made once. Whatever is kept, the same
    gradients bit for bit."""
    cfg = nh.nemotron_h_tiny(latent=16)    # no other [T, n_experts] product
    params = nh.init(cfg, jax.random.PRNGKey(21))
    p = _layer_of(params["blocks"][0], "E")
    x = jax.random.normal(jax.random.PRNGKey(22), (2, cfg.seq_len, cfg.d_model),
                          cfg.dtype)
    T = 2 * cfg.seq_len

    def grads(policy):
        fn = jax.checkpoint(lambda x, p: nh._layer(x, p, cfg, "E"),
                            policy=policy)
        return jax.grad(lambda x, p: jnp.sum(jnp.sin(fn(x, p).astype(
            jnp.float32))), (0, 1))

    kept = grads(jax.checkpoint_policies.save_only_these_names(*saved))
    jaxpr = jax.make_jaxpr(kept)(x, p).jaxpr
    router = lambda e: (e.primitive.name == "dot_general"
                        and e.outvars[0].aval.shape == (T, cfg.n_experts)
                        and e.outvars[0].aval.dtype == jnp.float32)
    assert _count(jaxpr, lambda e: e.primitive.name == "top_k") == top_ks
    assert _count(jaxpr, lambda e: e.primitive.name == "sort") == sorts
    assert _count(jaxpr, router) == router_products
    want = jax.jit(grads(jax.checkpoint_policies.nothing_saveable))(x, p)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(
            jax.jit(kept)(x, p)), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32),
                                      err_msg=jax.tree_util.keystr(path))
    assert float(jnp.max(jnp.abs(want[1]["router_w"]))) > 0


def test_the_chosen_set_from_a_kept_kth_and_last_is_the_top_ks_on_ties():
    """The ties case under a checkpoint that keeps `kth` and `last` alone:
    the residuals are the two [T, 1] columns, the backward holds no `top_k`,
    and the gates' derivative — which the chosen set decides, through the
    normalising sum — is the chosen-list form's, where `lax.top_k`'s ids
    are gathered."""
    from jax._src.ad_checkpoint import saved_residuals

    logits, bias, top_k, held, rows = _membership_logits(
        "ties_across_the_kth_place")
    T, E = logits.shape
    passes = -(-T * min(top_k, held.count) // rows)
    cot = jax.random.normal(jax.random.PRNGKey(0), (passes, rows))

    def gates_of(pairs):
        return jnp.sum(jnp.where(pairs.valid, pairs.gate * cot, 0))

    @partial(jax.checkpoint, policy=jax.checkpoint_policies.
             save_only_these_names(names.RES_MOE_KTH, names.RES_MOE_LAST))
    def kept(logits):
        here, gates = moe.route(logits, jnp.eye(E), bias, top_k, 2.5, held)
        return gates_of(_looked_up(moe.held_pairs(here, gates, rows, passes), T))

    def listed(logits):
        return gates_of(_chosen_list_pairs(jax.nn.sigmoid(logits), bias, top_k,
                                           2.5, held, rows, passes)[1])

    residuals = sorted((aval.shape, str(aval.dtype))
                       for aval, why in saved_residuals(kept, logits)
                       if not why.startswith(("from the argument",
                                               "from a constant")))
    assert residuals == [((T, 1), "float32"), ((T, 1), "int32")]
    # one `top_k`, in the forward: the backward reads the two columns
    assert _count(jax.make_jaxpr(jax.grad(kept))(logits).jaxpr,
                  lambda e: e.primitive.name == "top_k") == 1
    d_kept, d_listed = jax.grad(kept)(logits), jax.grad(listed)(logits)
    assert float(jnp.max(jnp.abs(d_listed))) > 0.01
    np.testing.assert_allclose(d_kept, d_listed, rtol=1e-6, atol=1e-6)


_ADMITS = {"nothing": 0, "the_routing": 3, "what_the_cell_keeps": 6,
           "every_name": None}


@pytest.mark.parametrize("admits", list(_ADMITS))
def test_the_hybrids_loss_and_every_gradient_equal_whatever_is_saved(admits):
    """The whole hybrid at tiny sizes in bf16, `remat=True` with a chip stated
    that has room for the first n candidates of the rule's order — none; Δ,
    the `top_k`'s columns and the scores; six of them — and `remat=False`
    (every name): the same loss and gradients as whole-block remat with no
    chip stated — bit for bit with `remat=True`, to float32's rounding
    without —, and the decision recorded says what was kept and which phase
    of the backward left the budget."""
    cfg = nh.nemotron_h_tiny(remat=admits != "every_name")
    params = nh.init(cfg, jax.random.PRNGKey(23))
    tokens, targets = _batch(cfg)
    base, kinds = nh.kind_shards(cfg, 2, cfg.seq_len, None)
    runs = blocks.pattern_groups(cfg.pattern) + blocks.pattern_groups(cfg.mtp_pattern)
    ranked = sorted(((c, k.applications) for k in kinds.values()
                     for c in k.candidates),
                    key=lambda cn: -cn[0].flops / cn[0].nbytes)
    phase = max(blocks.backward_phases(base, kinds, runs), key=lambda p: p.nbytes)
    limit = None
    if cfg.remat:
        limit = (blocks.REMAT_RESERVE_BYTES + phase.nbytes + 12345 + sum(
            n * c.nbytes for c, n in ranked[:_ADMITS[admits]]))

    def loss(p, cfg=cfg, limit=limit):
        with mesh_lib.chip_memory(limit, 12345):
            return nh.loss_fn(p, tokens, targets, cfg)

    got = jax.jit(jax.value_and_grad(loss))(params)
    want = jax.jit(jax.value_and_grad(
        lambda p: loss(p, nh.nemotron_h_tiny(remat=True), None)))(params)
    # `remat=False` is ANOTHER program: XLA fuses its backward differently
    # and a float32 sum over the tokens comes out in another order — one
    # tensor (the Mamba layers' gate_norm) read 1.0e-6 off in 16 of 128
    # elements, five runs of five, on the seed tree and on PR 47's (the
    # driver's run of the seed tree too). Bits are asked of what shares a
    # program's shape (the three `remat=True` cases); that case is held to
    # float32's rounding over a sum, 1e-5.
    same = (np.testing.assert_array_equal if cfg.remat else
            partial(np.testing.assert_allclose, rtol=1e-5, atol=1e-8))
    same(float(got[0]), float(want[0]))
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                            jax.tree.leaves(want[1])):
        same(np.asarray(g, np.float32), np.asarray(w, np.float32),
             err_msg=jax.tree_util.keystr(path))
    if cfg.remat:
        (d,) = [d for d in blocks.remat_policy_decisions()
                if d["bytes_limit"] == limit and d["seq"] == cfg.seq_len]
        assert d["saved"] == [n for c, _ in ranked[:_ADMITS[admits]]
                              for n in c.names]
        assert (d["phase"], d["phase_bytes"]) == phase
        assert d["saved_bytes"] <= d["budget_bytes"]


def test_balancing_the_selection_bias_evens_the_load_and_changes_nothing_else():
    """moe.balance_bias on one batch: every expert's load comes near the
    mean (before: some experts several times it, some none), with the
    weights as they were; balance_router_bias does so for every expert layer,
    the scanned ones too, and reports the held experts' load after it."""
    cfg = nh.nemotron_h_tiny(dtype=jnp.float32)
    params = nh.init(cfg, jax.random.PRNGKey(14))
    tokens, targets = _batch(cfg, rows=8)
    p = _layer_of(params["blocks"][0], "E")
    u = jax.random.normal(jax.random.PRNGKey(15), (512, cfg.d_model))
    mean = 512 * cfg.top_k / cfg.n_experts

    def loads(bias):
        chosen, _ = moe.route(u, p["router_w"], bias, cfg.top_k,
                              cfg.routed_scaling, moe.Held(0, cfg.n_experts))
        return np.asarray(chosen).sum(axis=0)

    before = loads(p["router_bias"])
    after = loads(moe.balance_bias(u, p["router_w"], p["router_bias"], cfg.top_k))
    assert before.max() > 1.5 * mean
    assert after.max() <= 1.1 * mean and after.min() >= 0.9 * mean

    balanced, events = nh.balance_router_bias(params, tokens, targets, cfg)
    assert [e["layer"] for e in events] == [0, 1, 2, 3]
    held_mean = 8 * cfg.seq_len * cfg.top_k / cfg.n_experts
    for e in events:
        assert e["pairs_dropped"] == 0
        assert e["max_per_expert"] <= 1.2 * held_mean
        assert abs(e["mean_per_expert"] - held_mean) <= 0.1 * held_mean
    # the stacks keep their layout: [layers of the run, n_experts]
    for old, new in zip(params["blocks"] + params["mtp"]["blocks"],
                        balanced["blocks"] + balanced["mtp"]["blocks"]):
        if "E" in old:
            assert new["E"]["router_bias"].shape == old["E"]["router_bias"].shape
            assert new["E"]["router_w"] is old["E"]["router_w"]
    # and the program still equals the reference on the balanced parameters
    (loss, _), (ref_loss, _) = _both(cfg, balanced, tokens[:2], targets[:2])
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)


def test_the_selection_bias_is_a_buffer_no_step_moves():
    """No gradient reaches it, and the optimizer's decay is told to leave it
    (nemotron_h.decays): after steps it is bit for bit what it was, while the
    router's weights beside it have moved."""
    from ray_tpu.train.train_step import default_optimizer, make_train_step

    cfg = nh.nemotron_h_tiny()
    bundle = make_train_step(nh, cfg, optimizer=default_optimizer(
        lr=1e-2, warmup=0, decay_mask=nh.decays), rng=jax.random.PRNGKey(16))
    before = jax.tree.map(np.asarray, bundle.state["params"])
    tokens, targets = _batch(cfg)
    state = bundle.state
    for _ in range(2):
        state, _ = bundle.step_fn(state, {"tokens": tokens, "targets": targets})
    for old, new in zip(before["blocks"], state["params"]["blocks"]):
        if "E" in old:
            np.testing.assert_array_equal(old["E"]["router_bias"],
                                          new["E"]["router_bias"])
            assert float(jnp.max(jnp.abs(
                old["E"]["router_w"] - new["E"]["router_w"]))) > 0


# --------------------------------------------------------------------------- #
# The seam of ops/moe.py (PR 50): the held experts' form and the gates' eps
# became arguments of the one dispatch. With both at their defaults the layer
# is the parent's, bit for bit. The parent's passes, as they stood at PR 49
# (two matrices, relu², positional w1 / w2), are frozen here as the oracle.
# --------------------------------------------------------------------------- #

def _pr49_pass_rows(x, w1, w2, gate, valid, group_sizes):
    x = jnp.where(valid[:, None], x, 0)
    h = jax.lax.ragged_dot(x, w1, group_sizes, preferred_element_type=x.dtype)
    a = jnp.square(jax.nn.relu(jnp.where(valid[:, None], h, 0)))
    o = jax.lax.ragged_dot(a, w2, group_sizes, preferred_element_type=x.dtype)
    return (jnp.where(valid[:, None], o, 0).astype(jnp.float32)
            * gate[:, None])


@jax.custom_vjp
def _pr49_run_passes(ell, w1, w2, gates, key, valid, group_sizes, n):
    def body(i, r):
        token, x, gate = pr50.looked_up(ell, gates, key[i])
        o = _pr49_pass_rows(x, w1, w2, gate, valid[i], group_sizes[i])
        return r.at[token].add(o)

    return jax.lax.fori_loop(0, n, body, jnp.zeros(ell.shape, jnp.float32))


def _pr49_run_passes_fwd(ell, w1, w2, gates, key, valid, group_sizes, n):
    return (_pr49_run_passes(ell, w1, w2, gates, key, valid, group_sizes, n),
            (ell, w1, w2, gates, key, valid, group_sizes, n))


def _pr49_run_passes_bwd(res, d_r):
    ell, w1, w2, gates, key, valid, group_sizes, n = res

    def body(i, sums):
        d_ell, d_w1, d_w2, d_gates = sums
        token, x, gate = pr50.looked_up(ell, gates, key[i])
        _, vjp = jax.vjp(
            lambda x, a, b, g: _pr49_pass_rows(x, a, b, g, valid[i],
                                               group_sizes[i]),
            x, w1, w2, gate)
        d_x, d_a, d_b, d_gate = vjp(d_r[token])
        d_ell = d_ell.at[token].add(d_x.astype(jnp.float32))
        d_gates = d_gates.at[key[i]].add(d_gate)
        return (d_ell, d_w1 + d_a.astype(jnp.float32),
                d_w2 + d_b.astype(jnp.float32), d_gates)

    sums = jax.lax.fori_loop(0, n, body, (
        jnp.zeros(ell.shape, jnp.float32), jnp.zeros(w1.shape, jnp.float32),
        jnp.zeros(w2.shape, jnp.float32), jnp.zeros_like(gates)))
    return (sums[0].astype(ell.dtype), sums[1].astype(w1.dtype),
            sums[2].astype(w2.dtype), sums[3], None, None, None, None)


_pr49_run_passes.defvjp(_pr49_run_passes_fwd, _pr49_run_passes_bwd)


def _pr49_routed_experts(u, ell, p, *, top_k, held, scaling):
    _, pairs, filled = pr50.dispatch(u, p, top_k, held, scaling)
    return _pr49_run_passes(ell, p["w1"], p["w2"], pairs.gates, pairs.key,
                            pairs.valid, pairs.group_sizes, filled)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_latent_moe_is_the_parents_with_the_new_arguments_at_their_defaults(
        dtype, monkeypatch):
    """``latent_moe``'s output and every gradient on a seeded batch, against
    the same layer over the parent's passes: bit-equal. (The benchmark's
    Nemotron cell holds the same at its own sizes: its lowered step is the
    parent's, PERF.md §6, PR 50.)"""
    cfg = nh.nemotron_h_tiny()
    params = nh.init(cfg, jax.random.PRNGKey(11))
    p = {k: (v.astype(dtype) if k in moe.LATENT_MOE_MATMUL_WEIGHTS else v)
         for k, v in _layer_of(params["blocks"][0], "E").items()}
    u = jax.random.normal(jax.random.PRNGKey(12),
                          (2, cfg.seq_len, cfg.d_model)).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(13), u.shape)
    routing = dict(top_k=cfg.top_k, held=cfg.held, scaling=cfg.routed_scaling)

    def layer(u, p):
        y, _ = moe.latent_moe(u, p, **routing)
        return jnp.sum(y * w), y

    now = jax.jit(jax.value_and_grad(layer, (0, 1), has_aux=True))(u, p)
    # (the frozen passes hand out no load beside their result: PR 52)
    monkeypatch.setattr(moe, "routed_experts", lambda *a, **k: (
        _pr49_routed_experts(*a, **k), None))
    then = jax.jit(jax.value_and_grad(layer, (0, 1), has_aux=True))(u, p)
    for a, b in zip(jax.tree.leaves(now), jax.tree.leaves(then), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(jnp.abs(now[1][1]["w1"].astype(jnp.float32)).max()) > 0
    # eps reaches the gates only where a caller gives one
    ut = u.reshape(-1, cfg.d_model)
    plain = moe.route(ut, p["router_w"], p["router_bias"], cfg.top_k,
                      cfg.routed_scaling, cfg.held)
    zero = moe.route(ut, p["router_w"], p["router_bias"], cfg.top_k,
                     cfg.routed_scaling, cfg.held, 0.0)
    some = moe.route(ut, p["router_w"], p["router_bias"], cfg.top_k,
                     cfg.routed_scaling, cfg.held, 0.5)
    np.testing.assert_array_equal(np.asarray(plain[1]), np.asarray(zero[1]))
    assert float(jnp.max(some[1] / plain[1])) < 1.0


# --------------------------------------------------------------------------- #
# The pass that runs alone (PR 51): the first pass stands outside the loop and
# writes what it makes, further passes add to it in float32 inside a loop a
# one-pass batch never enters, and a row's gate comes out of the pairs' sort
# beside its key.
# The pairs and the passes as they stood at PR 50 are frozen in
# tests/moe_pr50_passes.py; whatever the passes a batch fills, the new ones
# are theirs bit for bit.
# --------------------------------------------------------------------------- #

_FORMS = {"relu2": moe.RELU2_EXPERT, "gated": moe.GATED_EXPERT}
# the share of the [T, held] membership that is true, by the passes it fills
_DENSITY = {0: 0.0, 1: 0.12, 2: 0.3, 3: 0.42}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("n", list(_DENSITY))
@pytest.mark.parametrize("form", list(_FORMS))
def test_the_pairs_and_the_passes_are_the_parents_bit_for_bit(form, n, dtype):
    """Value, ``d_ell``, every ``d_ws`` and ``d_gates`` back at ``route``'s
    [T, held], for both kinds of expert and a batch that fills no pass, one
    (the peeled pass alone: its weight gradients the kernel's own outputs),
    two and three (the float32 sums started from the first pass's)."""
    T, held, rows, width, d_expert = 96, 4, 64, 8, 12
    passes = T * held // rows
    rng = np.random.default_rng(51 + n)
    here = jnp.asarray(rng.random((T, held)) < _DENSITY[n])
    assert -(-int(here.sum()) // rows) == n
    k = iter(jax.random.split(jax.random.PRNGKey(51), 6))
    gates = jax.random.uniform(next(k), (T, held), minval=0.2)
    ell = jax.random.normal(next(k), (T, width)).astype(dtype)
    shapes = {"w1": (held, width, d_expert), "w3": (held, width, d_expert),
              "w2": (held, d_expert, width)}
    ws = tuple(jax.random.normal(next(k), shapes[w]).astype(dtype)
               for w in _FORMS[form])
    w = jax.random.normal(next(k), (T, width))

    def graded(routed):
        def loss(ell, ws, gates):
            r = routed(ell, ws, gates)
            return jnp.sum(jnp.sin(r) * w), r
        # (operation by operation, each rounding as it does alone: what is
        # compared is the arithmetic the two programs state, not how a
        # compiler fuses a pass outside a loop and the same pass inside one —
        # the CPU's skips a bfloat16 rounding between two float32 operations
        # and orders a row's sum by the fusion it sits in)
        with jax.disable_jit():
            return jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(
                ell, ws, gates)

    def now(ell, ws, gates):
        pairs = moe.held_pairs(here, gates, rows, passes)
        return moe._run_passes(ell, ws, pairs.gates, pairs.gate_rows,
                               pairs.key, pairs.valid, pairs.group_sizes,
                               -(-jnp.sum(pairs.per_expert) // rows))

    def then(ell, ws, gates):
        pairs = pr50.held_pairs(here, gates, rows, passes)
        return pr50.run_passes(ell, ws, pairs.gates, pairs.key, pairs.valid,
                               pairs.group_sizes,
                               -(-jnp.sum(pairs.per_expert) // rows))

    got, want = graded(now), graded(then)
    assert got[0][1].dtype == jnp.float32
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    _, d_ws, d_gates = want[1]
    assert (float(jnp.abs(d_gates).max()) > 0) == (n > 0)
    assert (float(jnp.abs(d_ws[0].astype(jnp.float32)).max()) > 0) == (n > 0)
    # a gate's cotangent went back to its own place and nowhere else
    np.testing.assert_array_equal(np.asarray(got[1][2] != 0) & ~np.asarray(here),
                                  False)


def test_the_gates_ride_the_sort_and_no_pass_looks_one_up():
    """The passes and their backward as they are traced: ONE sort, of two
    operands by one key — the gates beside the keys, as numbers: AD's rule
    for a sort, which adds an iota operand and gathers by it, is not used —
    and no gather out of the flat [held · T] table of gates, forward or
    backward; the gates' cotangent goes back by the scatter-add a pass."""
    T, held, rows, width, d_expert = 96, 4, 64, 8, 12
    passes = T * held // rows
    here = jnp.asarray(np.random.default_rng(0).random((T, held)) < 0.3)
    ell = jnp.ones((T, width))
    ws = (jnp.ones((held, width, d_expert)), jnp.ones((held, d_expert, width)))

    def f(ell, ws, gates):
        pairs = moe.held_pairs(here, gates, rows, passes)
        return jnp.sum(moe._run_passes(
            ell, ws, pairs.gates, pairs.gate_rows, pairs.key, pairs.valid,
            pairs.group_sizes, -(-jnp.sum(pairs.per_expert) // rows)))

    jaxpr = jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(
        ell, ws, jnp.ones((T, held))).jaxpr
    sorts, gathers = [], []
    _count(jaxpr, lambda e: e.primitive.name == "sort" and sorts.append(e))
    _count(jaxpr, lambda e: e.primitive.name == "gather" and gathers.append(e))
    assert [(len(e.invars), e.params["num_keys"]) for e in sorts] == [(2, 1)]
    assert gathers and all(e.invars[0].aval.shape[-1] == width
                           for e in gathers), gathers
    scalars = lambda e: (e.primitive.name == "scatter-add"
                         and e.invars[0].aval.shape == (T * held,))
    # one in the pass that runs alone, one in the loop's body
    assert _count(jaxpr, scalars) == 2
