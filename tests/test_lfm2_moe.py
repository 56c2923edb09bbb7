"""The LFM2-MoE family (``ray_tpu/models/lfm2_moe.py``, ``ops/short_conv.py``,
``ops/moe.gated_moe``) against its plain float32 reference
(``benchmarks/families/lfm2_moe_reference.py``): each operator and
feed-forward half alone (the conv at a row's first positions, QK-norm before
RoPE over grouped heads, tied scores), the whole step's loss and gradients,
the four shares of an expert layer tied to the uncut layer, the cell's
parameter count and the family's arithmetic, the pattern's groups and the
remat rule's three kinds, the meshes it refuses, the float8 control through
the comparison that decides ``correct`` — and what refused PR 46: each
reader this PR adds names the new cell alone, imports nothing of ``ray_tpu``
at module level and reads nothing, without raising, from the recorded GPT-2
and Nemotron traces."""

import ast
import dataclasses
import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.families import lfm2_moe as family  # noqa: E402
from benchmarks.families import lfm2_moe_reference as reference  # noqa: E402
from ray_tpu.models import blocks, lfm2_moe as lm  # noqa: E402
from ray_tpu.ops import moe, short_conv  # noqa: E402
from ray_tpu.tracing import names  # noqa: E402

CELL = "lfm2-24b-a2b-l5.dataset"
NEW_READERS = ("lfm2_mfu_device", "short_conv_ms_per_step",
               "conv_gate_ms_per_step", "lfm2_experts_roofline",
               "lfm2_flash_attn_roofline")
# PR 51's reader of the passes a batch runs beyond the one-pass path, which
# this cell and the Nemotron cell list
FURTHER_PASSES = "moe_further_passes_ms_per_step"
# PR 52's: the program's span around the step's call and the three readers of
# what each step said it did (tests/test_train_tracing.py holds their cases)
STEP_READERS = ("step_dispatch_ms_per_step", "moe_passes_per_step",
                "moe_multi_pass_steps", "moe_load_imbalance")
# accepted readers of a scope or a kernel this family's step has too
SHARED_READERS = ("moe_routed_ms_per_step", "moe_dispatch_ms_per_step",
                  "flash_fwd_ms_per_step", "flash_bwd_ms_per_step")


def _batch(cfg, rows=2, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, 64, (rows, cfg.seq_len)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1)
    targets[:, -1] = -1
    return tokens, targets


def _params(cfg, seed=0, balance_on=None):
    params = lm.init(cfg, jax.random.PRNGKey(seed))
    if balance_on is not None:
        params, _ = lm.balance_router_bias(params, balance_on, cfg)
    return params


def _sizes(cfg, **switches):
    return family.reference_sizes(cfg, **switches)


def _layer_of(params, cfg, kind):
    """One layer of ``kind``'s tensors out of the built tree."""
    for (sub, _), group in zip(blocks.pattern_groups(cfg.pattern),
                               params["blocks"]):
        if kind in sub:
            return jax.tree.map(lambda t: t[0], group[kind])
    raise KeyError(kind)


def _both(cfg, params, tokens, targets, given=True, **switches):
    """((loss, grads) of the program, (loss, grads, reports) of the
    reference given the program's sets), float32 matmuls on both sides."""
    sizes = _sizes(cfg, **switches)
    with jax.default_matmul_precision("highest"):
        mine = jax.jit(jax.value_and_grad(
            lambda p: lm.loss_fn(p, tokens, targets, cfg)))(params)
        sets = [s.reshape(tokens.shape + (cfg.n_experts,)) for s in jax.jit(
            lambda p: lm.chosen_experts(p, tokens, cfg))(params)]
        (ref, reports), grads = jax.jit(jax.value_and_grad(
            lambda p, sets: reference.loss_and_routing(
                p, tokens, targets, sizes, sets), has_aux=True))(
            params, sets if given else None)
    return mine, (ref, grads, reports)


def _reference_grad(cfg, params, tokens, targets, **switches):
    sizes = _sizes(cfg, **switches)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.grad(
            lambda p: reference.loss(p, tokens, targets, sizes)))(params)


def _norms(tree):
    return [float(jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32)))))
            for g in jax.tree.leaves(tree)]


@pytest.mark.parametrize("remat", [False, True], ids=["no-remat", "remat"])
def test_loss_and_every_gradient_equal_the_reference_in_float32(remat):
    cfg = lm.lfm2_moe_tiny(dtype=jnp.float32, remat=remat)
    tokens, targets = _batch(cfg)
    params = _params(cfg, balance_on=tokens)
    (loss, grads), (ref, ref_grads, reports) = _both(
        cfg, params, tokens, targets)
    assert float(loss) == pytest.approx(float(ref), rel=2e-6)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(ref_grads)):
        scale = float(jnp.linalg.norm(r))
        assert float(jnp.linalg.norm(g - r)) <= 2e-5 * scale + 1e-12, \
            jax.tree_util.keystr(path)
    assert len(reports) == 4
    assert all(int(r["differ"]) == 0 for r in reports)


def test_remat_changes_no_number():
    cfg = lm.lfm2_moe_tiny()
    tokens, targets = _batch(cfg)
    params = _params(cfg)
    a, b = (jax.jit(jax.value_and_grad(
        lambda p, c=c: lm.loss_fn(p, tokens, targets, c)))(params)
        for c in (cfg, dataclasses.replace(cfg, remat=True)))
    assert float(a[0]) == float(b[0])
    for x, y in zip(jax.tree.leaves(a[1]), jax.tree.leaves(b[1])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("half", ["conv", "attention", "dense", "experts"])
def test_a_half_alone_equals_the_reference(half):
    """Each operator and feed-forward half on a normed input, forward and
    the gradients to its input and its tensors, float32."""
    cfg = lm.lfm2_moe_tiny(dtype=jnp.float32, n_head=8, n_kv_head=2,
                           head_dim=8)               # GQA 4 : 1
    kind = {"conv": "C", "attention": "A", "dense": "D", "experts": "C"}[half]
    p = dict(_layer_of(_params(cfg, seed=1), cfg, kind))
    # gains that are not 1, so that a norm on the wrong side shows
    for g in ("q_norm", "k_norm"):
        if g in p:
            p[g] = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(7),
                                                 p[g].shape)
    u = jax.random.normal(jax.random.PRNGKey(2), (2, cfg.seq_len, cfg.d_model))
    sizes = _sizes(cfg)

    def mine(u, p):
        if half == "conv":
            return lm.conv_operator(u, p)
        if half == "attention":
            return lm.attention_operator(u, p, cfg)
        if half == "dense":
            return jnp.einsum("bsf,fd->bsd", jax.nn.silu(
                u @ p["w_gate"]) * (u @ p["w_up"]), p["w_down"])
        return moe.gated_moe(u, p, top_k=cfg.top_k, held=cfg.held,
                             scaling=cfg.routed_scaling, eps=cfg.route_eps)[0]

    def plain(u, p):
        f = {"conv": reference.conv_operator,
             "attention": reference.attention_operator,
             "dense": lambda u, p, z: reference._swiglu(
                 u, p["w_gate"], p["w_up"], p["w_down"], z),
             "experts": lambda u, p, z: reference.experts(u, p, z)[0]}[half]
        return jnp.stack([f(row, p, sizes) for row in u])

    w = jax.random.normal(jax.random.PRNGKey(3), u.shape)
    with jax.default_matmul_precision("highest"):
        (out, g), (ref, r) = (jax.jit(lambda u, p, f=f: (
            f(u, p), jax.grad(lambda u, p: jnp.sum(f(u, p) * w), (0, 1))(u, p)
        ))(u, p) for f in (mine, plain))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(r)):
        np.testing.assert_allclose(a, b, rtol=2e-4,
                                   atol=2e-5 * float(jnp.abs(b).max()) + 1e-9)


def test_the_conv_at_a_rows_first_positions_and_its_causality():
    """c_0 = w_2 z_0, c_1 = w_1 z_0 + w_2 z_1 (z = 0 before the row's
    start); no output moves with a later token."""
    D, S = 8, 6
    bcx = jax.random.normal(jax.random.PRNGKey(0), (1, S, 3 * D))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, D))
    y = short_conv.gated_short_conv(bcx, w)
    b, c, x = bcx[0, :, :D], bcx[0, :, D:2 * D], bcx[0, :, 2 * D:]
    z = b * x
    np.testing.assert_allclose(y[0, 0], c[0] * (w[2] * z[0]), rtol=1e-6)
    np.testing.assert_allclose(
        y[0, 1], c[1] * (w[1] * z[0] + w[2] * z[1]), rtol=1e-6)
    np.testing.assert_allclose(
        y[0, 5], c[5] * (w[0] * z[3] + w[1] * z[4] + w[2] * z[5]), rtol=1e-6)
    later = bcx.at[0, 4:].add(1.0)
    np.testing.assert_array_equal(
        np.asarray(short_conv.gated_short_conv(later, w)[0, :4]),
        np.asarray(y[0, :4]))


def _xla_gated_short_conv(bcx, w):
    """``ops/short_conv.gated_short_conv`` as XLA's shifted multiply-adds,
    differentiated by AD — the op's body until PR 53, kept as the kernel
    pair's oracle."""
    K, D = w.shape
    S = bcx.shape[1]
    f = jnp.float32
    b, c, x = (bcx[..., i * D:(i + 1) * D].astype(f) for i in range(3))
    z = jnp.pad(b * x, ((0, 0), (K - 1, 0), (0, 0)))
    wf = w.astype(f)
    conv = sum(z[:, tap:tap + S] * wf[tap] for tap in range(K))
    return (c * conv).astype(bcx.dtype)


def _conv_case(B, S, D, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (B, S, 3 * D)).astype(dtype),
            jax.random.normal(keys[1], (3, D)) * 0.5,
            jax.random.normal(keys[2], (B, S, D)).astype(dtype))


def _conv_value_and_grads(fn, bcx, w, dy):
    y, vjp = jax.vjp(fn, bcx, w)
    return (y,) + vjp(dy)


# (rows, tokens, width) → (token tiles, channel tiles) the rule cuts it into
CONV_SHAPES = {
    "three-token-tiles": ((2, 768, 128), (3, 1)),
    "two-channel-tiles": ((1, 256, 1024), (1, 2)),
    "tiles-both-ways": ((2, 512, 1024), (2, 2)),
    "tiny-config-whole-axes": ((3, 64, 64), (1, 1)),
    "tokens-and-lanes-padded": ((2, 6, 8), (1, 1)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape", CONV_SHAPES)
def test_the_conv_gate_kernels_equal_the_xla_form(shape, dtype):
    """PR 53: ``conv_gate_fwd`` / ``conv_gate_bwd`` (interpreted here) against
    the XLA form — y, d BCx and d w — in float32 to 1e-6 and in bf16 to
    bf16's rounding, where a row takes several token tiles (the halo before
    and after a tile), where the body takes the width in several channel
    tiles, at the tiny config's whole-axis blocks, and with more rows than
    one (row b's first outputs and last d z see zeros, not row b ± 1: the
    oracle pads every row with them)."""
    (B, S, D), tiles = CONV_SHAPES[shape]
    Sp, Dp = S + -S % 16, D + -D % 128
    tiling = short_conv.choose_conv_tiling("bwd", Sp, Dp, dtype.dtype.itemsize)
    assert (Sp // tiling.token_tile, Dp // tiling.channel_tile) == tiles
    assert tiling.token_tile % 16 == 0 and tiling.channel_tile % 128 == 0
    bcx, w, dy = _conv_case(B, S, D, dtype)
    got = _conv_value_and_grads(short_conv.gated_short_conv, bcx, w, dy)
    want = _conv_value_and_grads(_xla_gated_short_conv, bcx, w, dy)
    # a bf16 output is the same float32 value rounded: one ulp where the
    # float32 sums' last bits differ
    rtol = 1e-6 if dtype == jnp.float32 else 2.0 ** -7
    for name, a, b in zip(("y", "d bcx", "d w"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        tol = 1e-5 if name == "d w" else rtol
        np.testing.assert_allclose(a, b, rtol=tol,
                                   atol=tol * float(np.abs(b).max()) * 0.1,
                                   err_msg=name)


def test_the_conv_gate_kernels_halo_stops_at_a_rows_ends():
    """Two rows of three token tiles each, through the kernels together and
    each alone: bit-equal — nothing of row 0's last tokens reaches row 1's
    first outputs, nothing of row 1's first ``d c`` row 0's last ``d z``;
    and a row's first K − 1 outputs are the taps on its own first tokens."""
    bcx, w, dy = _conv_case(2, 768, 128, jnp.float32, seed=3)
    both = _conv_value_and_grads(short_conv.gated_short_conv, bcx, w, dy)
    alone = [_conv_value_and_grads(short_conv.gated_short_conv,
                                   bcx[r:r + 1], w, dy[r:r + 1])
             for r in range(2)]
    for i in range(2):                                   # y, d bcx
        np.testing.assert_array_equal(
            np.asarray(both[i]),
            np.concatenate([np.asarray(a[i]) for a in alone]))
    np.testing.assert_allclose(both[2], alone[0][2] + alone[1][2], rtol=1e-5,
                               atol=1e-5)
    D = 128
    z = bcx[1, :, :D] * bcx[1, :, 2 * D:]
    np.testing.assert_allclose(both[0][1, 0], bcx[1, 0, D:2 * D] * (w[2] * z[0]),
                               rtol=1e-6, atol=1e-7)


def test_the_conv_gate_kernels_take_their_own_rows_under_a_mesh():
    """Under a mesh (dp 2 × fsdp 2 of the host's CPU devices) the kernels
    run inside a shard_map over the batch axes, a device its own rows at the
    whole width: y and d BCx are the unsharded call's, d w the devices' sum."""
    from ray_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(dp=2, fsdp=2),
                              jax.devices()[:4])
    bcx, w, dy = _conv_case(4, 64, 128, jnp.float32, seed=5)
    fn = lambda *a: _conv_value_and_grads(short_conv.gated_short_conv, *a)
    plain = fn(bcx, w, dy)
    with mesh_lib.use_mesh(mesh):
        sharded = jax.jit(fn)(bcx, w, dy)
    for a, b in zip(sharded[:2], plain[:2]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(sharded[2], plain[2], rtol=1e-5, atol=1e-5)


def test_qk_norm_comes_before_rope():
    """The reference with the two the other way round is another model: the
    program follows the published order (q_layernorm, then RoPE)."""
    cfg = lm.lfm2_moe_tiny(dtype=jnp.float32)
    p = dict(_layer_of(_params(cfg, seed=1), cfg, "A"))
    p["q_norm"] = jnp.linspace(0.5, 1.5, cfg.head_dim)
    u = jax.random.normal(jax.random.PRNGKey(2), (1, cfg.seq_len, cfg.d_model))
    sizes = _sizes(cfg)
    out = lm.attention_operator(u, p, cfg)[0]
    np.testing.assert_allclose(
        out, reference.attention_operator(u[0], p, sizes), rtol=2e-4, atol=1e-6)

    def rope_first(u, p):
        q = reference._norm(reference._rope(jnp.einsum(
            "sd,dhk->hsk", u, p["wq"]), sizes["theta"]), p["q_norm"],
            sizes["eps"])
        return q

    q_right = reference._rope(reference._norm(jnp.einsum(
        "sd,dhk->hsk", u[0], p["wq"]), p["q_norm"], sizes["eps"]),
        sizes["theta"])
    assert float(jnp.abs(rope_first(u[0], p) - q_right).max()) > 1e-2


def test_tied_scores_choose_the_first_experts_in_both():
    """With every score equal the k chosen are the k lowest ids — in the
    program's mask (ops/moe._chosen) and in the reference's ``top_k``."""
    cfg = lm.lfm2_moe_tiny(dtype=jnp.float32)
    p = dict(_layer_of(_params(cfg), cfg, "C"))
    p["router_w"] = jnp.zeros_like(p["router_w"])
    p["router_bias"] = jnp.zeros_like(p["router_bias"])
    u = jax.random.normal(jax.random.PRNGKey(0), (cfg.seq_len, cfg.d_model))
    chosen = moe.chosen_experts(u, p, cfg.top_k)
    want = np.arange(cfg.n_experts) < cfg.top_k
    assert (np.asarray(chosen) == want).all()
    _, report = reference.routed_gates(u, p, _sizes(cfg))
    assert (np.asarray(report["own"]) == want).all()
    # half the ties broken by the bias: it chooses, and only chooses
    p["router_bias"] = p["router_bias"].at[-2:].set(0.25)
    gates, report = reference.routed_gates(u, p, _sizes(cfg))
    assert (np.asarray(report["own"])[0] == np.array(
        [1, 1] + [0] * (cfg.n_experts - 4) + [1, 1], bool)).all()
    np.testing.assert_allclose(np.asarray(gates)[0][[0, 1, -2, -1]],
                               0.5 / (2.0 + cfg.route_eps), rtol=1e-6)
    here, mine = moe.route(u, p["router_w"], p["router_bias"], cfg.top_k,
                           cfg.routed_scaling, moe.Held(0, cfg.n_experts),
                           cfg.route_eps)
    assert (np.asarray(here) == np.asarray(report["own"])).all()
    np.testing.assert_allclose(np.where(here, mine, 0.0), gates, rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_four_shares_add_up_to_the_uncut_layer(dtype):
    """Held 0-3, 4-7, 8-11, 12-15 of 16 experts: four chips' parts of one
    expert layer, each routing over all 16, add up to the reference's uncut
    layer (every expert held). No code stands in for the exchange: the sum
    IS what it would deliver."""
    cfg = lm.lfm2_moe_tiny(dtype=jnp.float32, held_first=0, held_count=16)
    p = dict(_layer_of(_params(cfg, seed=4), cfg, "C"))
    u = jax.random.normal(jax.random.PRNGKey(5), (2, cfg.seq_len, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        whole = jnp.stack([reference.experts(row, p, _sizes(cfg))[0]
                           for row in u])
        parts = []
        for first in (0, 4, 8, 12):
            share = {**p, **{w: p[w][first:first + 4].astype(dtype)
                             for w in moe.GATED_EXPERT}}
            parts.append(moe.gated_moe(
                u.astype(dtype), share, top_k=cfg.top_k,
                held=moe.Held(first, 4), scaling=cfg.routed_scaling,
                eps=cfg.route_eps)[0])
    assert all(part.dtype == jnp.float32 for part in parts)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(sum(parts), whole, rtol=tol,
                               atol=tol * float(jnp.abs(whole).max()))
    # a share is a strict part: none of them is the layer
    assert float(jnp.abs(parts[0] - whole).max()) > 0.1 * float(
        jnp.abs(whole).max())


def test_no_pair_is_dropped_when_every_token_chooses_held_experts():
    """A router that sends every token's four choices to the four held
    experts: four times the mean, so more passes over the row buffer — and
    the same result as the reference."""
    cfg = lm.lfm2_moe_tiny(dtype=jnp.float32, held_first=0, held_count=4,
                           seq_len=512)
    p = dict(_layer_of(_params(cfg, seed=6), cfg, "C"))
    p["router_bias"] = jnp.where(jnp.arange(cfg.n_experts) < 4, 1.0, 0.0)
    u = jax.random.normal(jax.random.PRNGKey(8), (2, cfg.seq_len, cfg.d_model))
    load = moe.held_load(u.reshape(-1, cfg.d_model), p, top_k=cfg.top_k,
                         held=cfg.held, scaling=cfg.routed_scaling,
                         eps=cfg.route_eps)
    assert int(load["pairs"]) == 4 * 2 * cfg.seq_len
    assert int(load["buffer_passes"]) > 1 and int(load["pairs_dropped"]) == 0
    with jax.default_matmul_precision("highest"):
        out, _ = moe.gated_moe(u, p, top_k=cfg.top_k, held=cfg.held,
                               scaling=cfg.routed_scaling, eps=cfg.route_eps)
        ref = jnp.stack([reference.experts(row, p, _sizes(cfg))[0]
                         for row in u])
    np.testing.assert_allclose(out, ref, rtol=2e-5,
                               atol=2e-6 * float(jnp.abs(ref).max()))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("router", ["as_initialised", "all_on_held_experts"])
def test_gated_moe_is_the_parents_whatever_the_passes_it_fills(
        router, dtype, monkeypatch):
    """PR 51: ``gated_moe``'s output and every gradient — the router's
    included, so the gates' cotangent goes all the way back through
    ``route`` — against the same layer over the pairs and passes frozen at
    PR 50 (tests/moe_pr50_passes.py): bit-equal where the batch fills one
    pass, and where a router sends every token's four choices to the four
    held experts, which fills three."""
    import moe_pr50_passes as pr50

    cfg = lm.lfm2_moe_tiny(dtype=dtype, held_first=0, held_count=4, seq_len=512)
    p = {k: (v.astype(dtype) if k in moe.GATED_EXPERT else v)
         for k, v in _layer_of(_params(cfg, seed=6), cfg, "C").items()}
    if router == "all_on_held_experts":
        p["router_bias"] = jnp.where(jnp.arange(cfg.n_experts) < 4, 1.0, 0.0)
    u = jax.random.normal(jax.random.PRNGKey(8),
                          (2, cfg.seq_len, cfg.d_model)).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(9), u.shape)
    load = moe.held_load(u.reshape(-1, cfg.d_model), p, top_k=cfg.top_k,
                         held=cfg.held, scaling=cfg.routed_scaling,
                         eps=cfg.route_eps)
    assert int(load["buffer_passes"]) == (
        3 if router == "all_on_held_experts" else 1)

    def layer(u, p):
        y, _ = moe.gated_moe(u, p, top_k=cfg.top_k, held=cfg.held,
                             scaling=cfg.routed_scaling, eps=cfg.route_eps)
        return jnp.sum(y * w), y

    def graded():
        # (operation by operation, each rounding as it does alone: what is
        # compared is the arithmetic the two programs state, not how a
        # compiler fuses a pass outside a loop and the same pass inside one)
        with jax.disable_jit():
            return jax.value_and_grad(layer, (0, 1), has_aux=True)(u, p)

    now = graded()
    # (the frozen passes hand out no load beside their result: PR 52)
    monkeypatch.setattr(moe, "routed_experts", lambda *a, **k: (
        pr50.routed_experts(*a, **k), None))
    then = graded()
    for a, b in zip(jax.tree.leaves(now), jax.tree.leaves(then), strict=True):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert float(jnp.abs(now[1][1]["router_w"]).max()) > 0
    assert float(jnp.abs(now[1][1]["w3"].astype(jnp.float32)).max()) > 0


def test_bf16_program_is_near_the_reference_and_a_coarser_one_is_not():
    """The family's comparison at tiny sizes: the bf16 program passes its
    limits' order of magnitude, the reference with float8 operands does not,
    and neither does a program whose experts are left out."""
    from benchmarks.families.nemotron_h import grad_error

    cfg = lm.lfm2_moe_tiny(seq_len=256)
    tokens, targets = _batch(cfg)
    params = _params(cfg, balance_on=tokens)
    (loss, grads), (ref_loss, ref_grads, reports) = _both(
        cfg, params, tokens, targets)
    assert abs(float(loss) - float(ref_loss)) < 1e-3 * float(ref_loss)
    mine = grad_error(_norms(grads), _norms(ref_grads))["total"]
    assert mine < 2e-2
    assert max(float(r["worst_margin"]) for r in reports) < 3e-2
    coarse = _reference_grad(cfg, params, tokens, targets,
                             operand_dtype=jnp.float8_e4m3fn)
    none = _reference_grad(cfg, params, tokens, targets, drop_routed=True)
    assert grad_error(_norms(coarse), _norms(ref_grads))["total"] > 1.5 * mine
    assert grad_error(_norms(none), _norms(ref_grads))["total"] > 2 * mine


@pytest.mark.parametrize("control,refused", [
    ({}, ()), ({"operand_dtype": jnp.float8_e4m3fn}, ("grad_norm",))],
    ids=["program", "float8-reference"])
def test_the_comparison_that_decides_correct_refuses_float8(control, refused):
    """The family's ``reference_check`` at the CPU rehearsal's sizes, judged
    by ``harness/checks.failures`` as run.py judges a run: the bf16 program
    is correct; the reference with float8 operands in the program's place is
    not. The gradient's limit is stated for these sizes and this seed; the
    cell's own limits are from readings at the cell's sizes (PERF.md §6)."""
    from benchmarks.harness import checks, spec, traffic
    from ray_tpu.parallel import mesh as mesh_lib

    cell, config, mix = spec.load_cell(CELL)
    with open(os.path.join(ROOT, "benchmarks", "testdata",
                           "rehearse-lfm2_moe.json")) as f:
        tiny = json.load(f)
    config.update(tiny["config"])
    cell.update(tiny["cell"])
    seed = 3000000019
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(**cell["mesh"]),
                              jax.devices()[:1])
    bundle = family.build(config, cell, mesh, seed)
    rows = traffic.host_batch(cell["reference_rows"], seed, cell["seq_len"],
                              mix["alphabet"])
    reading = family.reference_check(bundle, rows, config, cell, **control)
    assert len(reading["expert_load"]) == 4
    assert all(e["pairs_dropped"] == 0 for e in reading["expert_load"])
    summary = {
        "reference": reading,
        "window": {"nonfinite_losses": 0, "losses_tail": [1.0],
                   "first_loss": 2.0, "compiles_in_window": 0},
        "data_ok": True, "step_counter": 3, "steps_run": 3,
        "device_count": cell["chips"]}
    bad = checks.failures(summary, cell, rehearse_cpu=True)
    assert [any(s.startswith(name) for s in bad) for name in refused] == [
        True] * len(refused), (bad, reading["program"])
    assert bool(bad) == bool(refused), (bad, reading["program"])


def _cell():
    from benchmarks.harness import spec

    cell, config, _ = spec.load_cell(CELL)
    return cell, config


def test_the_configuration_holds_every_published_width_and_states_its_cut():
    cell, config = _cell()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the model catalog is not on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-24B-A2B")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == cell["config"])
    assert entry["source"] == row["source_url"] == config["source"]
    changed = {k for k, v in row["config"].items() if config[k] != v}
    assert changed == set(entry["reduced"]) == {
        "num_hidden_layers", "layer_types", "num_dense_layers",
        "num_experts", "vocab_size"}
    for key in entry["reduced"]:
        assert config["published"][key] == row["config"][key]
    first = config["first_layer"]
    assert config["layer_types"] == row["config"]["layer_types"][
        first:first + config["num_hidden_layers"]]
    for stated in ("source", "reduced", "assumed", "deployment", "why"):
        assert config[stated]
    assert any("tie" in a for a in config["assumed"])
    assert any("balance" in a for a in config["assumed"])


def test_the_cells_parameters_and_the_familys_arithmetic():
    """The program's count at the cell's configuration, from abstract shapes
    (nothing is allocated), is the issue's and the family's; x 12 B it is
    9.26 GB; the program's FLOPs a token are the family's; the rooflines'
    work is what the shapes say."""
    cell, config = _cell()
    cfg = family.program_config(config, cell)
    shapes = family.shapes(config, cell)
    assert (cfg.pattern, cfg.seq_len, cfg.first_layer) == ("DACCC", 4096, 1)
    assert (cfg.n_experts, cfg.held, cfg.top_k, cfg.head_dim) == (
        64, moe.Held(0, 16), 4, 64)
    assert lm.param_count(cfg) == shapes["params"] == 771_274_880
    assert 9.25e9 < shapes["params"] * 12 < 9.26e9
    assert family.train_flops_per_token(shapes) == pytest.approx(
        lm.flops_per_token(cfg), rel=1e-12)
    assert family.train_flops_per_token(shapes) / 3e6 == pytest.approx(
        426.8, rel=1e-3)                      # MFLOP a token, forward
    assert shapes["expected_pairs_per_token"] == 1.0
    work = family.experts_call(shapes)
    assert work["flops"] == 9 * 4 * 2 * 32768 * 2048 * 1536
    attn = family.flash_attn_call(shapes)
    assert attn["flops"] == 7 * 8 * 32 * 4096 * 4096 * 64
    assert moe.row_buffer(32768, 64, 4, 16) == 40960
    # the published layers, whole, are the program's default
    assert lm.pattern_from(config["published"]["layer_types"],
                           config["published"]["num_dense_layers"]) \
        == lm.LFM2MoEConfig().pattern == "DD" + "ACCC" * 9 + "AC"
    assert family._pattern(config) == lm.pattern_from(
        config["layer_types"], config["num_dense_layers"])


def test_the_family_refuses_a_program_without_the_model(monkeypatch):
    import importlib.util

    real = importlib.util.find_spec
    monkeypatch.setattr(importlib.util, "find_spec", lambda name, *a: (
        None if name == "ray_tpu.models.lfm2_moe" else real(name, *a)))
    cell, config = _cell()
    with pytest.raises(SystemExit, match="cannot run a cell of family"):
        family.shapes(config, cell)


@pytest.mark.parametrize("axis", ["ep", "tp", "pp", "cp"])
def test_a_mesh_the_family_cannot_run_on_is_refused(axis):
    class Mesh:
        shape = {axis: 2}

    with pytest.raises(NotImplementedError, match=axis):
        lm.mesh_rules(lm.lfm2_moe_tiny(), Mesh())


def test_the_patterns_groups_and_the_rules_three_kinds():
    """The cell's five layers are a dense layer, an attention layer and one
    scan of three; kind_shards at the cell's shapes states three kinds, each
    set of names once (a policy keeps a NAME, in every kind that has it);
    with the described chip's limit and the cell's resident bytes the rule
    keeps the conv's in-projection and the attention's q, k, v."""
    cell, config = _cell()
    cfg = family.program_config(config, cell)
    runs = blocks.pattern_groups(cfg.pattern)
    assert runs == [("D", 1), ("A", 1), ("C", 3)]
    base, kinds = lm.kind_shards(cfg, 8, cfg.seq_len, None)
    assert {k: v.applications for k, v in kinds.items()} == {
        "D": 1, "A": 1, "C": 3}
    named = [c.names for k in kinds.values() for c in k.candidates]
    assert len(named) == len(set(named))
    flat = {n for group in named for n in group}
    assert flat <= set(names.RESIDUALS)
    # (no mesh, on the CPU: attention is XLA's, so no flash_o among them)
    assert {names.RES_CONV_BCX, names.RES_Q, names.RES_MID,
            names.RES_MOE_SCORES, names.RES_MOE_PAIR_KEY} <= flat
    assert names.RES_MLP_GATE not in flat        # the dense MLP goes in chunks
    assert (base.mlp_rows, base.head_rows) == (256, 256)
    # block_mid is every layer's: one candidate, at five layers' bytes
    mid = next(c for c in kinds["C"].candidates if c.names == (names.RES_MID,))
    assert mid.nbytes == -(-5 * 8 * 4096 * 2048 * 2 // 3)
    phase = max(blocks.backward_phases(base, kinds, runs),
                key=lambda p: p.nbytes)
    policy = blocks.choose_remat_policy_kinds(
        tuple(kinds.values()), phase.nbytes, family.V5E_BYTES_LIMIT,
        12 * lm.param_count(cfg))
    assert {names.RES_CONV_BCX, names.RES_Q, names.RES_K,
            names.RES_V} <= set(policy.saved)
    assert policy.saved_bytes <= policy.budget_bytes
    assert len(policy.saved) == len(set(policy.saved))


def test_set_up_balances_the_bias_and_changes_nothing_else():
    cfg = lm.lfm2_moe_tiny()
    tokens, _ = _batch(cfg)
    params = _params(cfg)
    balanced, loads = lm.balance_router_bias(params, tokens, cfg)
    changed = jax.tree_util.tree_map_with_path(
        lambda path, a, b: (path[-1].key, bool((a != b).any())),
        params, balanced)
    assert {name for name, moved in jax.tree.leaves(
        changed, is_leaf=lambda x: isinstance(x, tuple)) if moved} == {
            "router_bias"}
    assert [e["layer"] for e in loads] == [2, 3, 4, 5]   # published indices
    for load in loads:
        assert tuple(load) == names.EXPERT_LOAD_ARGS
        assert load["pairs_dropped"] == 0 and load["tokens"] == 2 * cfg.seq_len
        # balanced: the fullest held expert is near the mean
        assert load["max_per_expert"] <= 1.25 * load["mean_per_expert"] + 2
    # the optimizer's decay leaves the buffers out
    mask = lm.decays(params)
    assert not mask["blocks"][1]["A"]["router_bias"]
    assert mask["blocks"][1]["A"]["router_w"] and mask["wte"]


# --------------------------------------------------------------------------- #
# What refused PR 46 (`benchmark_breaks_parent`): a reader this PR adds must
# leave every other cell's traced run alone
# --------------------------------------------------------------------------- #

def _benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_names_the_new_cell_alone_and_imports_no_program(name):
    entry = next(m for m in _benchmark()["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    path = os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py")
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    for node in tree.body:                        # module level only
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            mods = ([a.name for a in node.names] if isinstance(node, ast.Import)
                    else [node.module or ""])
            assert not any(m.split(".")[0] == "ray_tpu" for m in mods), mods
    reader = importlib.import_module(f"benchmarks.layer_metrics.{name}")
    assert (reader.UNIT, reader.MOVES) == (entry["unit"], entry["moves"])


def test_the_benchmark_gains_one_configuration_and_one_one_chip_cell():
    b = _benchmark()
    # (a later PR's configuration and cell come after them: PR 55's)
    assert "lfm2-24b-a2b-l5" in [c["name"] for c in b["configs"]][5:]
    mine = next(w for w in b["workloads"] if w["name"] == CELL)
    assert mine == {**mine, "config": "lfm2-24b-a2b-l5",
                    "traffic": "dataset", "chips": 1}
    # (PR 51 appended one metric of the dispatch after them, PR 52 the
    # program's span of the step and three readers of its per-step counters)
    metrics = [m["name"] for m in b["per_layer"]]
    first = metrics.index(NEW_READERS[0])
    assert metrics[first:first + len(NEW_READERS) + 1 + len(STEP_READERS)] \
        == list(NEW_READERS) + [FURTHER_PASSES] + list(STEP_READERS)
    for name in SHARED_READERS:
        entry = next(m for m in b["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"]
    # the step's grouped kernel is a Mosaic call too, so the reader of ALL
    # Mosaic time is not this cell's flash time; the p90 is not claimed
    for name in ("flash_attn_ms_per_step", "flash_attn_roofline"):
        entry = next(m for m in b["per_layer"] if m["name"] == name)
        assert CELL not in entry["workloads"]


def test_the_new_family_files_import_no_program_at_module_level():
    for name in ("lfm2_moe", "lfm2_moe_reference"):
        path = os.path.join(ROOT, "benchmarks", "families", name + ".py")
        with open(path) as f:
            tree = ast.parse(f.read(), path)
        level = tree.body if name == "lfm2_moe" else list(ast.walk(tree))
        for node in level:
            if isinstance(node, ast.ImportFrom):
                assert (node.module or "").split(".")[0] != "ray_tpu"
            elif isinstance(node, ast.Import):
                assert all(a.name.split(".")[0] != "ray_tpu" for a in node.names)


_RECORDED = {
    "lfm2_moe": (CELL, CELL + ".1step.scoped.program.json.gz"),
    "gpt2": ("gpt2-124m.dataset",
             "gpt2-124m.dataset.10steps.scoped.xplane.pb.gz"),
    "nemotron_h": ("nemotron-3-super-120b-l11.dataset",
                   "nemotron-3-super-120b-l11.dataset.1step.scoped.program.json.gz"),
}
_facts = {}


def _recorded_facts(family_name):
    """The facts a reader would be handed in that cell's traced run: the
    cell's own shapes, v5e's peaks and the recorded trace's reduction."""
    if family_name not in _facts:
        from benchmarks.harness import peaks, program_trace, spec

        cell_name, trace = _RECORDED[family_name]
        cell, config, mix = spec.load_cell(cell_name)
        path = os.path.join(ROOT, "benchmarks", "testdata", trace)
        tables = (program_trace.read_tables(path) if path.endswith(".json.gz")
                  else program_trace.load_tables(path))
        got = program_trace.reduce_tables(tables)
        assert got["instrumented"]
        fam = importlib.import_module(f"benchmarks.families.{family_name}")
        _facts[family_name] = {
            "cell": cell, "config": config, "traffic": mix, "notes": [],
            "summary": {"shapes": fam.shapes(config, cell)},
            "trace": {"steps": got["steps"], "step_device_ms": 100.0},
            "peaks": peaks.peaks_for("TPU v5 lite"), "driver": {},
            "program_trace": got}
    return _facts[family_name]


@pytest.mark.parametrize("family_name", ["gpt2", "nemotron_h"])
@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_reads_nothing_from_another_cells_trace(name, family_name):
    reader = importlib.import_module(f"benchmarks.layer_metrics.{name}")
    assert reader.read(_recorded_facts(family_name)) is None


@pytest.mark.parametrize("name,value", [
    ("short_conv_ms_per_step", 124.439387), ("conv_gate_ms_per_step", 41.785796),
    # the scope's 106.653 + the grouped kernel's 87.173, found by its name
    ("moe_routed_ms_per_step", 193.825819),
    ("moe_dispatch_ms_per_step", 92.734058),
    ("flash_fwd_ms_per_step", 9.723034), ("flash_bwd_ms_per_step", 14.767245),
    # 9 x 4 grouped products of 32,768 pairs: least 37.674 ms of 87.173
    ("lfm2_experts_roofline", 43.22),
    # one forward and one backward call: least 9.767 ms of 24.490
    ("lfm2_flash_attn_roofline", 39.88)])
def test_a_reader_of_this_cell_reads_its_recorded_trace(name, value):
    """The cell's own traced step (recorded on the chip), through each reader
    that lists the cell by a scope or a kernel: the new ones, and the
    accepted readers of the dispatch's scopes and of the flash kernels'
    names, whose lists the cell joined at the end."""
    entry = next(m for m in _benchmark()["per_layer"] if m["name"] == name)
    assert CELL in entry["workloads"]
    reader = importlib.import_module(f"benchmarks.layer_metrics.{name}")
    got = reader.read(_recorded_facts("lfm2_moe"))
    assert got == pytest.approx(value, rel=1e-3)
    assert 0 < got <= 100 or entry["unit"] != "%"


# --------------------------------------------------------------------------- #
# PR 51: `moe_further_passes_ms_per_step`, the one metric it adds
# --------------------------------------------------------------------------- #

def _further_reader():
    return importlib.import_module(f"benchmarks.layer_metrics.{FURTHER_PASSES}")


def test_the_further_passes_reader_is_one_appended_entry_of_two_cells():
    b = _benchmark()
    entry = next(m for m in b["per_layer"] if m["name"] == FURTHER_PASSES)
    # (the two cells it was appended for; a later expert cell joins after)
    assert {**entry, "workloads": entry["workloads"][:2]} == {
        "name": FURTHER_PASSES, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "Kernels",
        "moves": "tokens_per_s_per_chip",
        "workloads": ["nemotron-3-super-120b-l11.dataset", CELL]}
    reader = _further_reader()
    assert (reader.UNIT, reader.MOVES, reader.SOURCE, reader.LAYER) == (
        entry["unit"], entry["moves"], entry["source"], entry["layer"])
    assert reader.SCOPE == names.MOE_FURTHER_PASSES in names.SCOPES


@pytest.mark.parametrize("family_name", ["lfm2_moe", "nemotron_h", "gpt2"])
def test_the_further_passes_reader_reads_nothing_under_a_parents_vocabulary(
        family_name, monkeypatch):
    """What the driver's control runs: this PR's `benchmarks/` over the
    parent's `ray_tpu/`, whose `names.SCOPES` has no such scope — the reader
    returns None from the traces recorded at PR 50 and raises nothing."""
    from benchmarks.harness import program_trace

    reader = _further_reader()
    facts = _recorded_facts(family_name)
    monkeypatch.setattr(program_trace, "SCOPES", tuple(
        s for s in names.SCOPES if s != names.MOE_FURTHER_PASSES))
    assert reader.read(facts) is None


@pytest.mark.parametrize("family_name,value", [
    # recorded before the scope existed: every op of the dispatch lies outside
    ("lfm2_moe", 0.0), ("nemotron_h", 0.0),
    # a step that routes nothing has nothing to say
    ("gpt2", None)])
def test_the_further_passes_reader_on_the_recorded_cells(family_name, value):
    reader = _further_reader()
    assert reader.read(_recorded_facts(family_name)) == value


def test_the_further_passes_reader_reads_a_recorded_second_pass():
    """A small expert layer whose router sends every token's choices to the
    held experts (two passes), one traced step recorded on the chip
    (`benchmarks/testdata/moe-further-passes.1step.scoped.program.json.gz`):
    the scope holds the loop's dispatch work forward and backward, inside
    `moe_routed` and beside none of the one-pass path's."""
    from benchmarks.harness import program_trace

    tables = program_trace.read_tables(os.path.join(
        ROOT, "benchmarks", "testdata",
        "moe-further-passes.1step.scoped.program.json.gz"))
    got = program_trace.reduce_tables(tables)
    assert got["instrumented"]
    reader = _further_reader()
    facts = {"program_trace": got, "notes": []}
    value = reader.read(facts)
    assert value == pytest.approx(
        got["scope_ms_per_step"][names.MOE_FURTHER_PASSES]) and value > 0
    assert value < got["scope_ms_per_step"][names.MOE_ROUTED]
    # every op under the scope is the routed experts'
    for tf_op, hlo_name, _, kind, _, _ in tables["ops"]:
        c = program_trace.classify(tf_op, hlo_name, kind)
        if names.MOE_FURTHER_PASSES in c["scopes"]:
            assert names.MOE_ROUTED in c["scopes"], tf_op
