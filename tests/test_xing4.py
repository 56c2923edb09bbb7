"""Xing4.0 (PR 57): the DeepSeek-V2 family's layer with query compression, a
configured router rule and bias, an MTP module and a four-stream
manifold-constrained hyper-connection around every sublayer, against the
float32 reference at tiny sizes; the hyper-connection's pieces alone; the
share tied to the uncut layer; the remat rule's arithmetic for a carry wider
than d_model; the configuration, the cell and the benchmark's additions.
The cell's step compiled for the described v5e is
tests/test_flash_attention_tpu_compile.py's; its scopes in a lowered step
tests/test_train_tracing.py's."""

from __future__ import annotations

import ast
import dataclasses
import hashlib
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
from benchmarks.families import xing4 as family  # noqa: E402
from benchmarks.families import xing4_reference as reference  # noqa: E402
from ray_tpu.models import blocks, deepseek_v2 as ds  # noqa: E402
from ray_tpu.models import hyper_connections as hyper  # noqa: E402
from ray_tpu.ops import moe  # noqa: E402
from ray_tpu.tracing import names  # noqa: E402

CELL = "xing4.0-29b-a4b-l5.dataset"
CONFIG = "xing4.0-29b-a4b-l5"
DSV2_CELL = "deepseek-v2-lite-l5.dataset"
NEW_READERS = ("mhc_ms_per_step", "mhc_maps_ms_per_step",
               "mhc_stream_roofline")
# accepted readers that read any family with the scope, kernel or counter
SHARED_READERS = ("dsv2_mfu_device", "mla_flash_attn_roofline",
                  "dsv2_experts_roofline", "mla_latent_ms_per_step",
                  "mtp_ms_per_step", "flash_fwd_ms_per_step",
                  "flash_bwd_ms_per_step", "moe_routed_ms_per_step",
                  "moe_dispatch_ms_per_step", "moe_shared_ms_per_step",
                  "moe_further_passes_ms_per_step", "moe_passes_per_step",
                  "moe_multi_pass_steps", "moe_load_imbalance")


def _batch(cfg, rows=2, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, 64, (rows, cfg.seq_len)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1)
    targets[:, -1] = -1
    return tokens, targets


def _lively(params, seed=7):
    """``params`` with every hyper-connection's α thirtyfold and its biases
    moved: maps that depend on the token and mix the streams (as drawn a
    layer starts as the plain residual layer, which would test little)."""
    def moved(path, x):
        key = getattr(path[-1], "key", "")
        if key.endswith(hyper.ALPHA):
            return x * 30
        if key.endswith(hyper.BIAS) and key.startswith("hc_"):
            k = jax.random.fold_in(jax.random.PRNGKey(seed), len(str(path)))
            return x + 0.3 * jax.random.normal(k, x.shape)
        return x
    return jax.tree_util.tree_map_with_path(moved, params)


def _params(cfg, seed=0):
    return _lively(ds.init(cfg, jax.random.PRNGKey(seed)))


def _sizes(cfg, **switches):
    return family.reference_sizes(cfg, **switches)


def _norms(tree):
    return [float(jnp.linalg.norm(g.astype(jnp.float32)))
            for g in jax.tree.leaves(tree)]


def _benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# ------------------------------------------------ program against reference
def test_loss_and_every_gradient_equal_the_reference_in_float32():
    """Under ``remat`` (every layer a policy-checkpoint, as the cell's): query
    compression, four streams, 20 Sinkhorn rounds, the biased
    sigmoid router (normalised, scaled 2), one shared expert, the MTP module:
    in float32 the program's loss and EVERY tensor's gradient are the
    reference's (the selection biases are buffers: zero on both sides)."""
    cfg = ds.xing4_tiny(dtype=jnp.float32, remat=True)
    assert (cfg.pattern, cfg.mtp_pattern, cfg.hc_mult, cfg.q_lora_rank) == (
        "DEE", "E", 4, 24)
    tokens, targets = _batch(cfg)
    params = _params(cfg)
    with jax.default_matmul_precision("highest"):
        # (each side one compiled program: op by op the rounds take minutes)
        (loss, (trunk, mtp)), grads = jax.jit(jax.value_and_grad(
            lambda p: (ds.loss_fn(p, tokens, targets, cfg),
                       ds.losses(p, tokens, targets, cfg)), has_aux=True))(
            params)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p: reference.loss(p, tokens, targets, _sizes(cfg))))(params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=2e-6)
    assert float(loss) == pytest.approx(
        float(trunk) + cfg.mtp_loss_weight * float(mtp), rel=1e-6)
    assert float(mtp) > 1.0
    flat = jax.tree_util.tree_leaves_with_path(grads)
    assert any("hc_ffn_phi" in jax.tree_util.keystr(p) for p, _ in flat)
    for (path, mine), theirs in zip(flat, jax.tree.leaves(ref_grads),
                                    strict=True):
        size = float(jnp.linalg.norm(theirs))
        if getattr(path[-1], "key", "") == "router_bias":
            assert size == 0.0 and float(jnp.linalg.norm(mine)) == 0.0
            continue
        assert size > 0, jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(mine - theirs)) < 1e-4 * size, (
            jax.tree_util.keystr(path))


def test_bf16_program_is_near_the_reference_and_coarser_ones_are_not():
    """The bf16 program against the float32 reference GIVEN its routers'
    sets; the reference with float8 operands, and the one with its maps and
    Sinkhorn rounds in bf16, each stand further from it than the program."""
    from benchmarks.families.nemotron_h import grad_error

    cfg = ds.xing4_tiny(hc_sinkhorn_iters=4)    # (fewer rounds: compile time)
    tokens, targets = _batch(cfg)
    params = _params(cfg, seed=1)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: ds.loss_fn(p, tokens, targets, cfg)))(params)
        sets = [s.reshape(tokens.shape + (cfg.n_experts,)) for s in jax.jit(
            lambda p: ds.chosen_experts(p, tokens, cfg, targets))(params)]
        assert len(sets) == 3                   # the trunk's two, the MTP's
        (ref_loss, reports), ref_grads = jax.jit(jax.value_and_grad(
            lambda p: reference.loss_and_routing(
                p, tokens, targets, _sizes(cfg), sets)[:2],
            has_aux=True))(params)

        def switched(**switches):
            return jax.jit(jax.grad(lambda p: reference.loss(
                p, tokens, targets, _sizes(cfg, **switches))))(params)

        coarse = switched(operand_dtype=jnp.float8_e4m3fn)
        maps16 = switched(maps_dtype=jnp.bfloat16)
    assert abs(float(loss) - float(ref_loss)) < 1e-3 * float(ref_loss)
    mine = grad_error(_norms(grads), _norms(ref_grads))["total"]
    assert mine < 2e-2
    assert max(float(r["worst_margin"]) for r in reports) < 0.02
    assert grad_error(_norms(coarse), _norms(ref_grads))["total"] > 1.5 * mine
    # the maps' rounding reaches the maps' own tensors first
    def maps_only(tree):
        return [n for (path, _), n in zip(
            jax.tree_util.tree_leaves_with_path(tree), _norms(tree))
            if "hc_" in jax.tree_util.keystr(path)]
    assert (grad_error(maps_only(maps16), maps_only(ref_grads))["total"]
            > 1.5 * grad_error(maps_only(grads), maps_only(ref_grads))["total"])


# ------------------------------------------------------ the hyper-connection
def test_twenty_rounds_leave_rows_and_columns_summing_to_one():
    """H_res after 20 rounds: the columns sum to 1 to the eps in the last
    round's denominators, the rows to 1e-4 at logits of unit spread (within
    1e-2 at the clamp's ∓30 would need more rounds: the config's 20 are the
    paper's); its gradient is the reference's."""
    T = 96
    logits = jax.random.normal(jax.random.PRNGKey(0), (4, 4, 2, T // 2))
    m = hyper.sinkhorn(logits, 20, 1e-6)
    np.testing.assert_allclose(jnp.sum(m, axis=0), 1.0, atol=3e-6)
    np.testing.assert_allclose(jnp.sum(m, axis=1), 1.0, atol=1e-4)
    assert float(m.min()) > 0
    hc = hyper.HyperConnection(4, 20, 1e-6, 30.0, 1e-6)
    width = 32
    p = hyper.init(jax.random.PRNGKey(1), 1, hc, width, 0.02, jnp.float32,
                   "hc_attn_")
    p = {k: v[0] for k, v in _lively({"x": p})["x"].items()}
    x = jax.random.normal(jax.random.PRNGKey(2), (2, T // 2, 4 * width))
    weight = jax.random.normal(jax.random.PRNGKey(3), (4, 4, 2, T // 2))
    sizes = {"hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
             "hc_clamp_min": -30.0, "hc_clamp_max": 30.0, "eps": 1e-6}

    def mine(p, x):
        return jnp.sum(hyper.maps(x, p, "hc_attn_", hc).res * weight)

    def theirs(p, x):
        total = 0.0
        for b in range(x.shape[0]):
            res = reference.hyper_maps(x[b].reshape(-1, 4, width), p,
                                       "hc_attn_", sizes)[2]     # [S, n, n]
            total = total + jnp.sum(res * weight[:, :, b].transpose(2, 0, 1))
        return total

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(mine, argnums=(0, 1))(p, x)
        want = jax.value_and_grad(theirs, argnums=(0, 1))(p, x)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1]),
                    strict=True):
        np.testing.assert_allclose(a, b, rtol=2e-4,
                                   atol=2e-5 * float(jnp.abs(b).max()))


def test_a_layer_starts_as_the_plain_residual_layer():
    """The initial values: H_pre 1 / n, H_post 1, H_res within 1e-2 of the
    identity — so a fresh stream of n copies stays n copies, within rounding,
    and u is the plain layer's x."""
    hc = hyper.HyperConnection(4, 20, 1e-6, 30.0, 1e-6)
    p = {k: v[0] for k, v in hyper.init(
        jax.random.PRNGKey(0), 1, hc, 32, 0.02, jnp.float32, "s_").items()}
    p["s_" + hyper.ALPHA] = jnp.zeros((3,))           # the static part alone
    x = hyper.expand(jax.random.normal(jax.random.PRNGKey(1), (1, 8, 32)), 4)
    h = hyper.maps(x, p, "s_", hc)
    np.testing.assert_allclose(h.pre, 0.25, rtol=1e-6)
    np.testing.assert_allclose(h.post, 1.0, rtol=1e-6)
    eye = jnp.eye(4)[:, :, None, None]
    assert float(jnp.abs(h.res - eye).max()) < 1e-2
    np.testing.assert_allclose(hyper.pre_mix(x, h), x[..., :32], rtol=1e-6)
    np.testing.assert_allclose(hyper.collapse(x, 4), 4 * x[..., :32],
                               rtol=1e-6)


@pytest.mark.parametrize("kind", ["D", "E"])
def test_maps_set_to_the_plain_residuals_give_the_plain_layer(kind):
    """A hyper-connected layer whose maps read stream 0 alone (H_pre one-hot),
    write y to it alone (H_post 1 there) and mix nothing (H_res = I) is
    ``deepseek_v2``'s plain layer on stream 0, and leaves the others as they
    were."""
    cfg = ds.xing4_tiny(dtype=jnp.float32)
    plain = dataclasses.replace(cfg, hc_mult=1)
    layer = jax.tree.map(lambda a: a[0], ds._layer_init(
        jax.random.PRNGKey(3), 1, kind, cfg))
    big = 40.0
    one_hot = jnp.where(jnp.arange(4) == 0, big, -big)
    bias = jnp.concatenate([
        one_hot, jnp.where(jnp.arange(4) == 0, 0.0, -big),
        (2 * big * jnp.eye(4) - big).reshape(-1)])
    for prefix in (ds.HC_ATTN, ds.HC_FFN):
        layer[prefix + hyper.ALPHA] = jnp.zeros((3,))
        layer[prefix + hyper.BIAS] = bias
    flat = {k: v for k, v in layer.items() if not k.startswith("hc_")}
    x = jax.random.normal(jax.random.PRNGKey(4), (2, cfg.seq_len, cfg.d_model))
    others = jax.random.normal(jax.random.PRNGKey(5),
                               (2, cfg.seq_len, 3 * cfg.d_model))
    with jax.default_matmul_precision("highest"):
        got = ds._layer(jnp.concatenate([x, others], axis=-1), layer, cfg, kind)
        want = ds._layer(x, flat, plain, kind)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got[..., :cfg.d_model], want, rtol=1e-5,
                               atol=1e-5 * scale)
    # (to the rounds' eps: H_res's diagonal settles at 1 - hc_eps)
    np.testing.assert_allclose(got[..., cfg.d_model:], others, rtol=1e-5,
                               atol=1e-5)


def _sublayer(n, width, rows, seq, seed=0):
    """A hyper-connection's tensors with lively maps, a carry, a sublayer's
    output and two cotangents, all float32."""
    hc = hyper.HyperConnection(n, 5, 1e-6, 30.0, 1e-6)
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    p = {k: v[0] for k, v in hyper.init(keys[0], 1, hc, width, 0.05,
                                        jnp.float32, "s_").items()}
    p["s_" + hyper.ALPHA] = jnp.array([0.7, 0.5, 0.9])
    p["s_" + hyper.BIAS] += 0.3 * jax.random.normal(
        keys[1], p["s_" + hyper.BIAS].shape)
    x = jax.random.normal(keys[2], (rows, seq, n * width))
    y = jax.random.normal(keys[3], (rows, seq, width))
    return hc, p, x, y, (jax.random.normal(keys[4], x.shape),
                         jax.random.normal(keys[5], y.shape))


def _around_a_sublayer(path, hc, p, x, y, weights, dtype):
    """((u, x'), every gradient) of Σ x' · w + Σ u · v through the
    hyper-connection around a sublayer whose output is the given y: by the
    plain functions, or by what a layer calls (the kernels)."""
    def loss(x, y, p):
        x = x.astype(dtype)
        p = {**p, "s_" + hyper.PHI: p["s_" + hyper.PHI].astype(dtype)}
        if path == "plain":
            h = hyper.maps(x, p, "s_", hc)
            u, out = hyper.pre_mix(x, h), hyper.write_back(x, y, h)
        else:
            x, u, h = hyper.mixed(x, p, "s_", hc)
            out = hyper.joined(x, y, h)
        f = jnp.float32
        return (jnp.sum(out.astype(f) * weights[0])
                + jnp.sum(u.astype(f) * weights[1])), (u, out)

    (_, made), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(x, y, p)
    return made, grads


@pytest.mark.parametrize("seq", [128, 100], ids=["whole-tiles", "ragged"])
@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_kernel_pairs_equal_the_plain_functions(dtype, n, seq):
    """`ops/hyper_connections.py` (interpreted here): u, x' and EVERY
    gradient — d x, d y, d Φ, d α, d b — of the kernel path equal the plain
    `maps` / `pre_mix` / `write_back`: in float32 to 1e-5 of a tensor's
    largest; in bf16 no further from the float32 truth, by a tensor's root
    mean square, than the plain path is. (To a quarter: here d Φ reads 1.09
    to 1.17 of the plain path's, whose product on this backend takes the
    logits' float32 cotangent as it is where the kernel rounds it to the
    stream's dtype, as the chip's matrix unit does on both paths — there the
    two read equal to four digits, PERF.md §6, PR 58; d x reads 0.82 to
    0.86.) 2 × 128 tokens are one tile, 2 × 100 are padded to it."""
    from ray_tpu.ops import hyper_connections as kernels

    hc, p, x, y, weights = _sublayer(n, 128, 2, seq)
    truth = _around_a_sublayer("plain", hc, p, x, y, weights, jnp.float32)
    plain = _around_a_sublayer("plain", hc, p, x, y, weights, dtype)
    got = _around_a_sublayer("kernel", hc, p, x, y, weights, dtype)
    assert {d["kernel"] for d in kernels.mhc_tiling_decisions()
            if (d["n"], d["C"]) == (n, 128)} == set(kernels.KERNELS)

    def errors(tree, norm):
        return [float(norm(a.astype(jnp.float32) - b) / norm(b))
                for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(truth),
                                strict=True)]

    largest, rms = (lambda t: jnp.abs(t).max(),
                    lambda t: jnp.sqrt(jnp.mean(t * t)))
    mine, theirs = errors(got, rms), errors(plain, rms)
    assert len(mine) == 2 + 2 + 3                # u, x'; d x, d y; d Φ, α, b
    if dtype == jnp.float32:
        assert max(errors(got, largest)) < 1e-5
    else:
        assert all(m <= 1.25 * t for m, t in zip(mine, theirs, strict=True))


def test_the_kernel_pairs_take_their_own_rows_under_a_mesh():
    """Under a mesh (dp 2 × fsdp 2 of the host's CPU devices) the kernels run
    inside a shard_map over the batch axes, a device its own rows at the
    whole width: u, x', d x and d y are the unsharded call's, the maps'
    tensors' gradients the devices' sums."""
    from ray_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(dp=2, fsdp=2),
                              jax.devices()[:4])
    hc, p, x, y, weights = _sublayer(4, 128, 4, 32)
    alone = _around_a_sublayer("kernel", hc, p, x, y, weights, jnp.float32)
    with mesh_lib.use_mesh(mesh):
        sharded = _around_a_sublayer("kernel", hc, p, x, y, weights,
                                     jnp.float32)
    for a, b in zip(jax.tree.leaves(sharded), jax.tree.leaves(alone),
                    strict=True):
        np.testing.assert_allclose(a, b, rtol=1e-5,
                                   atol=1e-5 * float(jnp.abs(b).max()))


def test_a_stream_is_whole_lane_tiles_or_the_config_is_refused():
    """The kernels are the one path, so what they need is the config's to
    hold: a hyper-connected model 96 wide is refused where it is stated (the
    plain residual at that width is not), and so is a call of the kernels at
    such a width, or with more streams than put the maps' planes in one lane
    tile. The `ops/mhc_tiling` event carries what a kernel's tiling is."""
    from ray_tpu.ops import hyper_connections as kernels

    with pytest.raises(ValueError, match="whole lane tiles"):
        ds.xing4_tiny(d_model=96)
    assert ds.xing4_tiny(d_model=96, hc_mult=1).hc is None
    with pytest.raises(ValueError, match="whole lane tiles"):
        kernels.choose_mhc_tiling("mix_fwd", 256, 4, 96, 2)
    with pytest.raises(ValueError, match="planes fit one"):
        kernels.choose_mhc_tiling("mix_fwd", 256, 11, 128, 2)
    tiling = kernels.choose_mhc_tiling("write_bwd", 512, 4, 128, 2)
    assert tiling.token_tile == 256
    assert dict(zip(names.MHC_TILING_ARGS, ("write_bwd", 512, 4, 128)
                    + tuple(tiling))) in kernels.mhc_tiling_decisions()
    assert names.MHC_TILING == "ops/mhc_tiling"
    assert set(kernels.KERNELS) == {
        k[len("mhc_"):] for k in names.KERNELS if k.startswith("mhc_")}


# sha256 of the StableHLO text of deepseek_v2_tiny's loss, counters and
# gradient as the PARENT of PR 57 lowers it (JAX 0.9.0; no remat, remat). To
# make one again: jax.jit(jax.value_and_grad(lambda p, t, g: ds.loss_fn(p, t,
# g, cfg, counters=True), has_aux=True)).lower(abstract params, int32 [2,
# seq_len] twice).as_text()
_PARENT_LOWERED = {
    False: "a17a6769a543cabb9ca7d0505767a95e3e7bc42f5ff18dd820345684cfd38349",
    True: "0b2a7c62fc7471726a000b990761fbc31938adacb77ad04230d3d5489b9d85da",
}


@pytest.mark.parametrize("remat", [False, True], ids=["no-remat", "remat"])
def test_one_stream_lowers_to_the_parents_step(remat):
    """``hc_mult`` 1 is the plain ``x + F(norm(x))``: no map, no parameter,
    and the DeepSeek-V2 tiny config's lowered step is, byte for byte, what
    the parent of PR 57 lowered (so is the cell's, compiled for the described
    chip: PERF.md §6, PR 57)."""
    if jax.__version__ != "0.9.0":
        pytest.skip("the recorded text is JAX 0.9.0's")
    cfg = ds.deepseek_v2_tiny(remat=remat)
    assert cfg.hc is None and cfg.carry_width == cfg.d_model
    params = jax.eval_shape(lambda: ds.init(cfg, jax.random.PRNGKey(0)))
    assert not [p for p, _ in jax.tree_util.tree_leaves_with_path(params)
                if "hc_" in jax.tree_util.keystr(p)]
    tok = jax.ShapeDtypeStruct((2, cfg.seq_len), jnp.int32)
    text = jax.jit(jax.value_and_grad(
        lambda p, t, g: ds.loss_fn(p, t, g, cfg, counters=True),
        has_aux=True)).lower(params, tok, tok).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == _PARENT_LOWERED[remat]


def test_the_new_scopes_stand_in_the_lowered_step():
    """`mhc` around every sublayer's hyper-connection work with `mhc_maps`
    inside it, the compressed query under `mla_latent`, the MTP module under
    `mtp` — forward and backward — and the decision event of the trace."""
    import re

    from ray_tpu.train.train_step import make_train_step, synthetic_batch

    cfg = ds.xing4_tiny(remat=True, hc_sinkhorn_iters=3)
    bundle = make_train_step(ds, cfg)
    batch = synthetic_batch(cfg, 2)
    text = bundle.step_fn.lower(bundle.state, batch).as_text(debug_info=True)
    ops = set(re.findall(r'loc\("([^"]+)"', text))

    def under(scope, among=None):
        """The op names that carry ``scope`` (an element of the path, or
        wrapped by a transformation: ``transpose(jvp(mtp))``)."""
        pattern = re.compile(r"(^|[/(])" + re.escape(scope) + r"($|[/)])")
        return [op for op in (ops if among is None else among)
                if pattern.search(op)]

    for scope in (names.MHC, names.MHC_MAPS, names.MLA_LATENT, names.MTP,
                  names.MOE_ROUTED, names.MOE_SHARED, names.LM_HEAD_LOSS):
        assert under(scope), scope
        assert [op for op in under(scope) if "transpose(" in op], scope
    assert names.MHC in names.SCOPES and names.MHC_MAPS in names.SCOPES
    maps = under(names.MHC_MAPS)
    assert len(under(names.MHC, maps)) == len(maps)     # inside `mhc`, all
    # the maps' product under the MTP module is under both
    assert under(names.MTP, maps)
    assert not under(names.MOE_AUX)                     # no balance loss
    assert {"streams": 4, "rounds": 3, "stream_dtype": "bfloat16",
            "carry_bytes_per_token": 4 * cfg.d_model * 2} in hyper.decisions()


# ------------------------------------------------------------------ the share
def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Held 0-1, 2-3, … 14-15 of 16 experts: eight chips' ROUTED parts of one
    expert layer, each routing over all 16 by the biased sigmoid, plus what
    every chip computes alike — the shared expert — counted ONCE, are the
    reference's uncut layer (every expert held). No code stands in for the
    exchange."""
    cfg = ds.xing4_tiny(dtype=jnp.float32, held_first=0, held_count=16)
    stack = ds.init(cfg, jax.random.PRNGKey(4))["blocks"][1]["E"]
    p = {k: v[0] for k, v in stack.items()}
    u = jax.random.normal(jax.random.PRNGKey(5), (2, cfg.seq_len, cfg.d_model))
    routing = dict(top_k=cfg.top_k, scaling=cfg.routed_scaling, rule=cfg.rule,
                   eps=1e-20)
    assert cfg.rule == moe.Rule()           # the Nemotron and LFM2 routers'
    with jax.default_matmul_precision("highest"):
        whole = jnp.stack([reference.experts(row, p, _sizes(cfg))[0]
                           for row in u])
        shared_once = jnp.stack([reference._swiglu(
            row, p["shared_w1"], p["shared_w3"], p["shared_w2"], _sizes(cfg))
            for row in u])
        routed = []
        for first in range(0, 16, 2):
            share = {k: v for k, v in p.items() if not k.startswith("shared_")}
            share.update({w: p[w][first:first + 2] for w in moe.GATED_EXPERT})
            routed.append(moe.gated_moe(u, share, held=moe.Held(first, 2),
                                        **routing)[0])
    scale = float(jnp.abs(whole).max())
    np.testing.assert_allclose(sum(routed) + shared_once, whole, rtol=2e-5,
                               atol=2e-5 * scale)
    assert float(jnp.abs(routed[0] + shared_once - whole).max()) > 0.05 * scale


def test_set_up_balances_the_biases_and_changes_nothing_else():
    cfg = ds.xing4_tiny(hc_sinkhorn_iters=2)
    tokens, targets = _batch(cfg, rows=4)
    params = ds.init(cfg, jax.random.PRNGKey(2))
    batches = [{"tokens": tokens[i:i + 2], "targets": targets[i:i + 2]}
               for i in (0, 2)]
    balanced, events = ds.balance_router_bias(params, batches, cfg)
    assert [e["layer"] for e in events] == [2, 3, 6]    # the MTP module's: 6
    assert all(e["pairs_dropped"] == 0 and e["tokens"] == tokens.size // 2
               for e in events)
    moved = [jax.tree_util.keystr(path) for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(params),
        jax.tree.leaves(balanced), strict=True) if not np.array_equal(a, b)]
    assert len(moved) == 2 and all("router_bias" in m for m in moved)
    counters = ds.step_counters(cfg)
    assert counters.layers == (2, 3, 6)
    assert counters.fields == names.STEP_EXPERT_LOAD_ARGS
    assert counters.float_fields == ()
    _, rows = ds.loss_fn(balanced, tokens, targets, cfg, counters=True)
    assert rows.shape == (3, 3) and rows.dtype == jnp.int32
    decays = ds.decays(params)
    assert not decays["blocks"][1]["E"]["router_bias"]
    assert decays["blocks"][1]["E"]["router_w"]
    with pytest.raises(ValueError, match="balance_router_bias"):
        ds.balance_routers(params, tokens, cfg)


# ----------------------------------------------------- the remat rule's half
def _cell():
    from benchmarks.harness import spec

    cell, config, _ = spec.load_cell(CELL)
    return cell, config


def test_the_rule_prices_a_carry_hc_mult_wide():
    """The shard states the carry's width; ``backward_phases`` stacks block
    inputs of THAT width and a block's moment holds the n-stream tensors
    that wait in it. A family that states no width is priced as before."""
    cell, config = _cell()
    cfg = dataclasses.replace(family.program_config(config, cell),
                              attention_impl="pallas")
    base, kinds = ds.kind_shards(cfg, 1, cfg.seq_len, None)
    one = dataclasses.replace(cfg, hc_mult=1)
    base1, kinds1 = ds.kind_shards(one, 1, cfg.seq_len, None)
    assert (base.carry_width, base1.carry_width) == (4 * 3584, 0)
    assert blocks._block_input(base) == 8192 * 28672
    assert blocks._block_input(base1) == 8192 * 3584 * 2
    runs = ds._runs(cfg)
    assert [blocks.run_name(r) for r in runs] == ["D", "4 x scan(E)", "E"]
    assert kinds["E"].applications == 5 and kinds["D"].applications == 1
    wide = blocks.backward_phases(base, kinds, runs)
    thin = blocks.backward_phases(base1, kinds1, runs)
    assert [p.name for p in wide] == ["head", "E", "4 x scan(E)", "D"]
    T, W, D = 8192, 28672 // 2, 3584
    maps = T * 4 * (24 + 2 * 20 * 16)
    for a, b in zip(wide, thin, strict=True):
        layers = {"head": 6, "E": 6, "4 x scan(E)": 5, "D": 1}[a.name]
        stack = layers * T * (W - D) * 2
        if a.name == "head":    # (less the maps' own gradients, not made yet)
            a_map = cfg.hc.n * D * cfg.hc.outputs + 3 + cfg.hc.outputs
            assert a.nbytes - b.nbytes == stack - 6 * 2 * 4 * a_map
            continue
        # the wider carried cotangent and the waiting streams (the part of
        # the block's input past d_model, the carry after attention with its
        # float32 cotangent, the maps), less the maps' own gradients in the
        # runs before, not made yet
        grown = a.nbytes - b.nbytes - stack
        assert abs(grown - (2 * T * (W - D) * 2 + T * W * 6 + maps)
                   ) < 16 * 2 ** 20, a.name
    # RES_MID is the 4-stream carry here
    mid = next(c for k in kinds.values() for c in k.candidates
               if c.names == (names.RES_MID,))
    assert mid.nbytes * 6 >= 6 * T * W * 2 - 6
    # other families: the same shard, the same number
    from ray_tpu.models import parts
    shard = parts.BlockShard(batch=2, seq=64, d_model=32, heads=2, head_dim=16,
                             d_ff=64, vocab=128, dtype_bytes=2, flash=False,
                             dense_mlp=True)
    assert shard.carry_width == 0
    assert blocks._block_input(shard) == 2 * 64 * 32 * 2
    assert blocks._block_input(shard._replace(carry_width=96)) == 2 * 64 * 96 * 2


# ------------------------------------- the configuration, the cell, the family
def test_the_configuration_holds_every_published_width_and_states_its_cut():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the model catalog is not on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f]
    published = next(r for r in rows if r["name"] == "Xing4.0-29B-A4B")
    cell, config = _cell()
    entry = next(c for c in _benchmark()["configs"] if c["name"] == CONFIG)
    assert entry["source"] == config["source"] == published["source_url"]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    for key, value in published["config"].items():
        if key in entry["reduced"]:
            assert config[key] != value
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"], config["first_layer"],
            config["num_nextn_predict_layers"]) == (5, 8, 16384, 1, 1)
    assert len(config["reduced"]) == 3 and len(config["assumed"]) >= 8
    assert config["family"] == "xing4"
    for word in ("hc_eps", "Sinkhorn", "stream start", "stream end",
                 "mtp_loss_weight", "initial values", "selection bias"):
        assert any(word in a for a in config["assumed"]), word
    assert (cell["seq_len"], cell["per_chip_batch"], cell["remat"],
            cell["reference_rows"], cell["reference_grad"], cell["chips"]) == (
        8192, 1, True, 1, True, 1)


def test_the_cells_parameters_and_the_familys_arithmetic():
    """913,473,348 parameters, counted by the program from abstract shapes
    and by the family from the file; the two counts of a token's operations
    agree; a held expert's 512 tokens a layer, an eighth of the
    deployment's."""
    cell, config = _cell()
    cfg = family.program_config(config, cell)
    shapes = family.shapes(config, cell)
    assert ds.param_count(cfg) == shapes["params"] == 913_473_348
    assert f"{shapes['params']:,}" in config["deployment"]
    assert (cfg.pattern, cfg.mtp_pattern) == ("DEEEE", "E")
    assert cfg.held == moe.Held(0, 8) and cfg.rule == moe.Rule()
    assert (cfg.qk_dim, cfg.v_head_dim, cfg.q_lora_rank, cfg.seq_len,
            cfg.carry_width) == (192, 128, 768, 8192, 14336)
    assert cfg.softmax_scale == pytest.approx(2.0048 / 192 ** 0.5, rel=1e-4)
    assert ds._expert_layer_ids(cfg) == (2, 3, 4, 5, 40)
    assert family.train_flops_per_token(shapes) == pytest.approx(
        ds.flops_per_token(cfg), rel=1e-12)
    assert 4.4e9 < family.train_flops_per_token(shapes) < 4.6e9
    tokens = cell["per_chip_batch"] * cell["seq_len"]
    assert tokens * cfg.top_k / cfg.n_experts == 512
    assert moe.row_buffer(tokens, 64, 4, 8) == 5120       # 1.25 x 4,096
    call = family.mhc_call(shapes)
    assert call["bytes"] == 12 * tokens * (8 * 4 * 3584 * 2 + 12 * 3584)
    assert call["flops"] < 0.01 * family.train_flops_per_token(shapes) * tokens
    flash = family.flash_attn_call(shapes)
    rows = 32 * 8192
    assert flash["flops"] == 6 * rows * 8192 * (4 * 192 + 3 * 128)
    experts = family.experts_call(shapes)
    assert experts["flops"] == 9 * 5 * 2 * (tokens * 0.5) * 3584 * 1024


def test_the_family_refuses_a_program_without_the_hyper_connection(monkeypatch):
    import importlib.util

    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name == "ray_tpu.models.hyper_connections"
        else real(name, *a))
    with pytest.raises(SystemExit, match="cannot run a cell of family xing4"):
        family.shapes(*reversed(_cell()))


def _rehearsal():
    from benchmarks.harness import spec

    cell, config, mix = spec.load_cell(CELL)
    with open(os.path.join(ROOT, "benchmarks", "testdata",
                           "rehearse-xing4.json")) as f:
        tiny = json.load(f)
    config.update(tiny["config"])
    cell.update(tiny["cell"])
    return cell, config, mix


@pytest.mark.parametrize("control,refused", [
    ({}, ()), ({"operand_dtype": jnp.float8_e4m3fn}, ("grad_norm",))],
    ids=["program", "float8-reference"])
def test_the_comparison_that_decides_correct(control, refused):
    """The family's ``reference_check`` at the CPU rehearsal's sizes, judged
    by ``harness/checks.failures`` as run.py judges a run: the bf16 program
    is correct; a switched reference in the program's place is not. The
    limits are stated for these sizes and this seed; the cell's own limits
    are from readings at the cell's sizes (PERF.md §6)."""
    from benchmarks.harness import checks, traffic
    from ray_tpu.parallel import mesh as mesh_lib

    cell, config, mix = _rehearsal()
    if control:     # (one pass: the parts' path is the program case's)
        cell["reference_grad_passes"] = 1
    seed = 3000000019
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(**cell["mesh"]),
                              jax.devices()[:1])
    bundle = family.build(config, cell, mesh, seed)
    rows = traffic.host_batch(cell["reference_rows"], seed, cell["seq_len"],
                              mix["alphabet"])
    reading = family.reference_check(bundle, rows, config, cell, **control)
    assert [e["layer"] for e in reading["expert_load"]] == [2, 3, 4, 5, 6]
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


# ------------------------------------------------- the benchmark's additions
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
    assert (reader.UNIT, reader.MOVES, reader.LAYER, reader.SOURCE) == (
        entry["unit"], entry["moves"], entry["layer"], entry["source"])
    # a program without the scope (any trace before PR 57) reads nothing
    facts = {"config": {"family": "xing4"}, "summary": {"shapes": {}},
             "trace": None, "notes": []}
    assert reader.read(facts) is None


def test_the_benchmark_gains_one_configuration_and_one_one_chip_cell():
    b = _benchmark()
    # (PR 61's configuration and cell came after this one's)
    assert [c["name"] for c in b["configs"]][7] == CONFIG
    assert b["workloads"][8] == {
        **b["workloads"][8], "name": CELL, "config": CONFIG,
        "traffic": "dataset", "chips": 1}
    assert len(b["configs"]) >= 8 and len(b["workloads"]) >= 9
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1
    readers = [m["name"] for m in b["per_layer"]]
    first = readers.index(NEW_READERS[0])
    assert readers[first:first + len(NEW_READERS)] == list(NEW_READERS)
    for name in SHARED_READERS:
        entry = next(m for m in b["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"], name
    # every list that held the DeepSeek cell holds this one, but the balance
    # loss's (this config has none); the rate and the set-up time, not the p90
    for entry in b["per_layer"]:
        if DSV2_CELL in entry.get("workloads", ()):
            assert (CELL in entry["workloads"]) == (
                entry["name"] != "moe_aux_ms_per_step"), entry["name"]
    p90 = next(m for m in b["end_to_end"] if m["name"] == "step_ms_p90")
    assert CELL not in p90["workloads"]
    for entry in b["configs"] + b["workloads"]:
        assert len(entry["why"]) <= 200


def test_the_new_family_files_import_no_program_at_module_level():
    for name in ("xing4", "xing4_reference"):
        path = os.path.join(ROOT, "benchmarks", "families", name + ".py")
        with open(path) as f:
            tree = ast.parse(f.read(), path)
        level = tree.body if name == "xing4" else list(ast.walk(tree))
        for node in level:
            if isinstance(node, ast.ImportFrom):
                module = node.module or ""
                assert module.split(".")[0] != "ray_tpu"
                if name == "xing4_reference":       # nor another reference
                    assert "families" not in module
            elif isinstance(node, ast.Import):
                assert all(a.name.split(".")[0] != "ray_tpu" for a in node.names)
