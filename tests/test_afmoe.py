"""The AFMoE family (``ray_tpu/models/afmoe.py``; the causal WINDOW of
``ray_tpu/ops/attention.py`` and ``parts.causal_attention``) against its
plain float32 reference (``benchmarks/families/afmoe_reference.py``): the
whole step's loss, logits and every gradient for a pattern that holds all
three kinds on rows of four windows, the windowed flash pair in interpret
mode against the masked XLA form (forward, dq, dk, dv; windows below, at, off
and past the block; both layouts; global offsets), the band's walk counted,
the eight shares of an expert layer tied to the uncut layer, the cell's
parameter count and the family's arithmetic, the controls through the
comparison that decides ``correct``, the meshes and the ring's refusal — and
that with no window the GPT-2 and LFM2 tiny steps lower to the parent's
text."""

import ast
import collections
import hashlib
import importlib
import json
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.families import afmoe as family  # noqa: E402
from benchmarks.families import afmoe_reference as reference  # noqa: E402
from ray_tpu.models import afmoe, blocks, gpt2, lfm2_moe, parts  # noqa: E402
from ray_tpu.ops import (  # noqa: E402
    attention, attention_pointwise, moe, ring_attention)
from ray_tpu.tracing import names  # noqa: E402

CELL = "trinity-mini-l5.dataset"
NEW_READERS = ("trinity_mfu_device", "trinity_flash_attn_roofline",
               "attn_window_ms_per_step", "attn_full_ms_per_step",
               "flash_window_tile_share")
SHARED_READERS = ("flash_fwd_ms_per_step", "flash_bwd_ms_per_step",
                  "moe_routed_ms_per_step", "moe_dispatch_ms_per_step",
                  "moe_shared_ms_per_step", "moe_further_passes_ms_per_step",
                  "moe_passes_per_step", "moe_multi_pass_steps",
                  "moe_load_imbalance", "dsv2_experts_roofline",
                  "step_dispatch_ms_per_step", "data_wait_ms_per_step")


def _benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _batch(cfg, rows=2, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, 64, (rows, cfg.seq_len)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1)
    targets[:, -1] = -1
    return tokens, targets


def _params(cfg, seed=0):
    """Seeded random weights, the gains off 1 so that each norm's tells."""
    params = afmoe.init(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 200))

    def stirred(path, p):
        name = getattr(path[-1], "key", "")
        if name.endswith("norm"):
            return p + 0.2 * jax.random.normal(next(keys), p.shape, p.dtype)
        return p

    return jax.tree_util.tree_map_with_path(stirred, params)


def _sizes(cfg, **switches):
    return family.reference_sizes(cfg, **switches)


def _layer_of(params, cfg, kind):
    for (sub, _), group in zip(blocks.pattern_groups(cfg.pattern),
                               params["blocks"]):
        if kind in sub:
            return jax.tree.map(lambda t: t[0], group[kind])
    raise KeyError(kind)


def _grad_errors(mine, theirs):
    return {jax.tree_util.keystr(path): float(
        jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(mine),
                                jax.tree.leaves(theirs))
        if float(jnp.linalg.norm(b)) > 0}


# --------------------------------------------------------------------------- #
# Program against reference
# --------------------------------------------------------------------------- #

# float32 program against float32 reference, both at the highest matmul
# precision: what differs is the order of sums (the flash pair's online
# softmax, the dispatch's grouped products, the chunked head) — 1e-5 of a
# tensor's norm at these sizes. bf16 where float32 is stated stands a
# hundred times further and fails each of the three.
LOSS_RTOL, LOGITS_ATOL, GRAD_RTOL = 2e-6, 2e-5, 1e-4


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("remat", [False, True], ids=["no-remat", "remat"])
def test_loss_logits_and_every_gradient_equal_the_reference(remat, impl):
    """``DWFWW`` on rows of 64 under a window of 16: the band, both its
    edges, NoPE on the full layer and RoPE on the others all act."""
    cfg = afmoe.afmoe_tiny(dtype=jnp.float32, remat=remat,
                           attention_impl=impl)
    assert set(cfg.pattern) == set(afmoe.KINDS)
    assert cfg.seq_len > 2 * cfg.sliding_window
    params, (tokens, targets) = _params(cfg), _batch(cfg)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: afmoe.loss_fn(p, tokens, targets, cfg)))(params)
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: reference.loss(p, tokens, targets, _sizes(cfg))))(params)
        logits = afmoe.forward(params, tokens, cfg)
        want_logits = reference.logits(params, tokens, _sizes(cfg))
    assert float(loss) == pytest.approx(float(want), rel=LOSS_RTOL)
    assert float(jnp.abs(logits - want_logits).max()) < LOGITS_ATOL
    errors = _grad_errors(grads, want_grads)
    # (every tensor but the selection biases: one leaf a run of the pattern)
    assert len(errors) == len(jax.tree.leaves(params)) - 3
    assert max(errors.values()) < GRAD_RTOL, max(errors.items(),
                                                 key=lambda kv: kv[1])


def test_bf16_where_float32_is_stated_fails_the_limits():
    cfg = afmoe.afmoe_tiny(dtype=jnp.bfloat16, attention_impl="xla")
    params, (tokens, targets) = _params(cfg), _batch(cfg)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: afmoe.loss_fn(p, tokens, targets, cfg))(params)
        want, want_grads = jax.value_and_grad(
            lambda p: reference.loss(p, tokens, targets, _sizes(cfg)))(params)
    assert abs(float(loss) - float(want)) > LOSS_RTOL * float(want)
    assert max(_grad_errors(grads, want_grads).values()) > 10 * GRAD_RTOL


@pytest.mark.parametrize("switch", [
    "window_ignored", "rope_on_full", "attn_gate_dropped",
    "post_norms_dropped", "route_scale_one", "embed_unscaled"])
def test_each_switch_of_the_reference_is_a_different_model(switch):
    """What a control switches is in the program: with it the reference's
    gradient leaves the program's by far more than the limit."""
    cfg = afmoe.afmoe_tiny(dtype=jnp.float32, attention_impl="xla")
    params, (tokens, targets) = _params(cfg), _batch(cfg)
    with jax.default_matmul_precision("highest"):
        grads = jax.grad(
            lambda p: afmoe.loss_fn(p, tokens, targets, cfg))(params)
        other = jax.grad(lambda p: reference.loss(
            p, tokens, targets, _sizes(cfg, **{switch: True})))(params)
    assert max(_grad_errors(grads, other).values()) > 100 * GRAD_RTOL


def test_qk_norm_before_rope_and_no_position_on_a_full_layer():
    """A full layer's output does not change when the row's order of EARLIER
    tokens changes (no positional signal: attention over a set); a window
    layer's does."""
    cfg = afmoe.afmoe_tiny(dtype=jnp.float32, attention_impl="xla",
                           sliding_window=64)
    params = _params(cfg)
    u = jax.random.normal(jax.random.PRNGKey(3), (1, cfg.seq_len, cfg.d_model))
    swapped = u.at[0, 3].set(u[0, 9]).at[0, 9].set(u[0, 3])
    for kind, same in (("F", True), ("W", False)):
        p = _layer_of(params, cfg, kind)
        a = afmoe.attention_operator(u, p, cfg, kind)[0, -1]
        b = afmoe.attention_operator(swapped, p, cfg, kind)[0, -1]
        assert (float(jnp.abs(a - b).max()) < 1e-5) == same, kind


# --------------------------------------------------------------------------- #
# The windowed flash pair
# --------------------------------------------------------------------------- #

def _masked(q, k, v, window, q_off=0, kv_off=0):
    """[B, S, H, hd] attention by an explicit mask over global positions."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    i = q_off + jnp.arange(q.shape[1])[:, None]
    j = kv_off + jnp.arange(k.shape[1])[None, :]
    keep = j <= i
    if window is not None:
        keep &= i - j < window
    s = jnp.where(keep, s, -1e30)
    lse = jax.nn.logsumexp(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v), lse


def _qkvd(S, hd, seed=0, H=2):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return tuple(jax.random.normal(key, (1, S, H, hd), jnp.float32)
                 for key in keys)


BLOCK = 64
# below the block, the block, not a multiple of it, several blocks, past S
WINDOWS = [24, 64, 100, 192, 1000]


@pytest.mark.parametrize("hd", [128, 64], ids=["hd_minor", "s_minor"])
@pytest.mark.parametrize("window", WINDOWS)
def test_the_windowed_flash_pair_equals_the_masked_form(window, hd):
    S = 256
    assert attention.kernel_layout(hd) == (
        attention.HD_MINOR if hd == 128 else attention.S_MINOR)
    q, k, v, do = _qkvd(S, hd)

    def flash(q, k, v):
        return attention.flash_attention(
            q, k, v, window=window, block_q=BLOCK, block_k=BLOCK,
            interpret=True)

    o, vjp = jax.vjp(flash, q, k, v)
    want, want_vjp = jax.vjp(lambda q, k, v: _masked(q, k, v, window)[0],
                             q, k, v)
    np.testing.assert_allclose(o, want, atol=3e-6)
    for got, ref in zip(vjp(do), want_vjp(do)):
        np.testing.assert_allclose(got, ref, atol=1e-5)
    if window >= S:     # a window that hides nothing IS the causal program
        windowed = jax.jit(flash).lower(q, k, v).as_text()

        def flash(q, k, v):
            return attention.flash_attention(
                q, k, v, block_q=BLOCK, block_k=BLOCK, interpret=True)

        assert windowed == jax.jit(flash).lower(q, k, v).as_text()


@pytest.mark.parametrize("bq,bk", [(32, 128), (128, 32)])
def test_unequal_tiles_walk_the_same_band(bq, bk):
    S, window = 256, 72
    q, k, v, do = _qkvd(S, 128, seed=1)
    o, vjp = jax.vjp(lambda q, k, v: attention.flash_attention(
        q, k, v, window=window, block_q=bq, block_k=bk, interpret=True),
        q, k, v)
    want, want_vjp = jax.vjp(lambda q, k, v: _masked(q, k, v, window)[0],
                             q, k, v)
    np.testing.assert_allclose(o, want, atol=3e-6)
    for got, ref in zip(vjp(do), want_vjp(do)):
        np.testing.assert_allclose(got, ref, atol=1e-5)


@pytest.mark.parametrize("window", [40, 64, 150])
def test_the_window_is_over_global_positions(window):
    """q rows 192 … 319 against kv 128 … 383 (the offsets a chunk of a longer
    row would have): forward with lse, and the backward's three gradients."""
    q_off, kv_off, Sq, Skv = 192, 128, 128, 256
    q, _, _, do = _qkvd(Sq, 128, seed=2)
    _, k, v, _ = _qkvd(Skv, 128, seed=3)
    o, lse = attention.flash_attention_with_lse(
        q, k, v, q_off, kv_off, window=window, block_q=BLOCK, block_k=BLOCK,
        interpret=True)
    want, vjp = jax.vjp(
        lambda q, k, v: _masked(q, k, v, window, q_off, kv_off)[0], q, k, v)
    want_lse = _masked(q, k, v, window, q_off, kv_off)[1]
    np.testing.assert_allclose(o, want, atol=3e-6)
    np.testing.assert_allclose(lse, want_lse, atol=1e-5)
    grads = attention.mha_backward_chunk(
        q, k, v, o, lse, do, q_off, kv_off, window=window, block_q=BLOCK,
        block_k=BLOCK, interpret=True)
    for got, ref in zip(grads, vjp(do)):
        np.testing.assert_allclose(got, ref, atol=1e-5)


@pytest.mark.parametrize("layout,hd", [("bhsd", 128), ("hbds", 64),
                                       ("bhds", 64), ("bshd", 128)])
@pytest.mark.parametrize("window", [None, 100])
def test_grouped_heads_read_in_the_kernel_equal_the_repeat(window, layout, hd):
    """k and v at KH = 2 heads under H = 8 of q (S8): the kernels' index map
    reads a group's key-value head where it stands, dk and dv are summed over
    the group after the backward — value and the three gradients bit for bit
    what the repeat to q's heads gave, in every layout, both pairs, with and
    without a window."""
    B, H, KH, S = 2, 8, 2, 256
    sizes = dict(b=B, h=H, s=S, d=hd)
    keys = jax.random.split(jax.random.PRNGKey(9), 4)

    def drawn(key, heads):
        return jax.random.normal(
            key, tuple({**sizes, "h": heads}[c] for c in layout), jnp.float32)

    q, do = drawn(keys[0], H), drawn(keys[3], H)
    k, v = drawn(keys[1], KH), drawn(keys[2], KH)
    axis = layout.index("h")

    def flash(q, k, v):
        return jnp.sum(do * attention.flash_attention(
            q, k, v, window=window, block_q=64, block_k=64, interpret=True,
            layout=layout))

    grouped = jax.value_and_grad(flash, (0, 1, 2))(q, k, v)
    repeated = jax.value_and_grad(
        lambda q, k, v: flash(q, jnp.repeat(k, H // KH, axis=axis),
                              jnp.repeat(v, H // KH, axis=axis)),
        (0, 1, 2))(q, k, v)
    assert grouped[1][1].shape == k.shape
    for got, want in zip(jax.tree.leaves(grouped), jax.tree.leaves(repeated)):
        np.testing.assert_array_equal(got, want)


def test_grouped_heads_under_tp_are_refused_and_the_xla_path_repeats():
    from ray_tpu.parallel import mesh as mesh_lib

    q = jnp.zeros((2, 8, 64, 128))
    kv = jnp.zeros((2, 2, 64, 128))
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(tp=2), jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="grouped heads"):
        attention.flash_attention_sharded(q, kv, kv, mesh, interpret=True)
    out = parts.causal_attention(q, kv, kv, "xla", grouped_kv=True)
    assert out.shape == q.shape


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
@pytest.mark.parametrize("window", [None, 24, 64, 100, 2048])
def test_visited_tiles_is_the_band_and_no_tile_outside_it(kernel, window):
    """The walk counted in integers equals the tiles that hold a visible
    pair, counted by brute force — no tile wholly outside the band is
    visited, none that holds a pair is missed."""
    S, bq, bk = 512, 64, 32
    i = np.arange(S)[:, None]
    j = np.arange(S)[None, :]
    causal = j <= i
    seen = causal if window is None else causal & (i - j < window)

    def tiles(mask):
        return int(mask.reshape(S // bq, bq, S // bk, bk).any((1, 3)).sum())

    assert attention.visited_tiles(kernel, S, S, bq, bk, window) == (
        tiles(seen), tiles(causal))


def test_the_tiling_event_says_how_much_of_the_triangle_was_skipped():
    q, k, v, _ = _qkvd(512, 128, seed=4)
    jax.grad(lambda q: attention.flash_attention(
        q, k, v, window=128, block_q=128, block_k=128,
        interpret=True).sum())(q)
    mine = {d["kernel"]: d for d in attention.flash_tiling_decisions()
            if (d["Sq"], d["window"], d["block_q"]) == (512, 128, 128)}
    assert set(mine) == {"fwd", "bwd"}
    for d in mine.values():
        assert tuple(d) == names.FLASH_TILING_ARGS
        # 4 tiles of 128: the triangle's 10 pairs, the band's diagonal + one
        assert (d["tiles_visited"], d["tiles_causal"]) == (7, 10)
    assert names.FLASH_TILING_ARGS[-3:] == ("window", "tiles_visited",
                                            "tiles_causal")


def test_a_window_needs_the_causal_mask_and_a_whole_number():
    q, k, v, _ = _qkvd(64, 128)
    with pytest.raises(ValueError, match="causal"):
        attention.flash_attention(q, k, v, causal=False, window=8,
                                  interpret=True)
    with pytest.raises(ValueError, match="whole number"):
        attention.flash_attention(q, k, v, window=0, interpret=True)


def test_the_ring_and_a_cp_mesh_refuse_a_window_by_name():
    from ray_tpu.parallel import mesh as mesh_lib

    q, k, v, _ = _qkvd(64, 128)
    with pytest.raises(NotImplementedError, match="window=16"):
        ring_attention.ring_attention(q, k, v, window=16)
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(cp=2), jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="window=16"):
        ring_attention.ring_attention_sharded(q, k, v, mesh, window=16)
    head_major = tuple(jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    with pytest.raises(NotImplementedError, match="window=16.*cp"):
        attention.flash_attention_sharded(*head_major, mesh, window=16)


@pytest.mark.parametrize("layout", ["bhsd", "hbds"])
def test_the_one_attention_entry_masks_the_band_on_the_xla_path(layout):
    S, window, hd = 64, 12, 16
    q, k, v, _ = _qkvd(S, hd, seed=5, H=4)
    want = _masked(q, k[:, :, ::2].repeat(2, axis=2),
                   v[:, :, ::2].repeat(2, axis=2), window)[0]
    axes = tuple("bshd".index(c) for c in layout)
    got = parts.causal_attention(
        q.transpose(axes), k[:, :, ::2].transpose(axes),
        v[:, :, ::2].transpose(axes), "xla", layout=layout, window=window)
    back = tuple(layout.index(c) for c in "bshd")
    np.testing.assert_allclose(got.transpose(back), want, atol=2e-6)


# recorded on the parent of PR 66 (commit efacdd6): sha256 of
# jit(value_and_grad(loss_fn)).lower(abstract params, tokens, targets).as_text()
# — "pallas" interprets the flash pair, so the kernels' bodies are in the text
LOWERED_BEFORE = {
    ("gpt2_tiny", "xla", False):
        "72ddb6034115faf48f843c4b8dabe7f5937d7a8c8799abfd03f6e5f3d65a6ce5",
    ("gpt2_tiny", "pallas", False):
        "2e106b850f7da9f4b3d0b3adb74828ded6dbd1e4f1fd70399bd34111237eb1ea",
    ("gpt2_tiny", "pallas", True):
        "9e80d93d272589f3f268a2885fd39f9663cda82111b8800dd40d870afb8fbfa1",
    ("lfm2_moe_tiny", "xla", False):
        "0e471fe0c8c9c27dad6a23443874647e27e84492de111969c8a9ece407f932c4",
    ("lfm2_moe_tiny", "pallas", False):
        "75f3c20f73034c6f61bdc7e1627494c66ff737c8087c4c8545b3cd71b60377ee",
    ("lfm2_moe_tiny", "pallas", True):
        "3a5f4a26f2c3cc62e5896461050ce0abb4b10781cfc3ce39737475cca25f23f4",
}


@pytest.mark.parametrize("name,impl,remat", sorted(LOWERED_BEFORE))
def test_without_a_window_a_step_lowers_as_it_did(name, impl, remat):
    mod, preset = {"gpt2_tiny": (gpt2, gpt2.gpt2_tiny),
                   "lfm2_moe_tiny": (lfm2_moe, lfm2_moe.lfm2_moe_tiny)}[name]
    cfg = preset(attention_impl=impl, remat=remat)
    p = jax.eval_shape(lambda: mod.init(cfg, jax.random.PRNGKey(0)))
    tok = jax.ShapeDtypeStruct((2, cfg.seq_len), jnp.int32)
    text = jax.jit(jax.value_and_grad(
        lambda p, a, b: mod.loss_fn(p, a, b, cfg))).lower(p, tok, tok).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == LOWERED_BEFORE[
        name, impl, remat]


# --------------------------------------------------------------------------- #
# The expert half, the shares
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_eight_shares_add_up_to_the_uncut_layer(dtype):
    """Held 0-1, 2-3, … 14-15 of 16 experts: eight chips' routed parts of one
    expert layer, each routing over all 16, with the shared expert counted
    ONCE, add up to the reference's uncut layer (every expert held). No code
    stands in for the exchange: the sum IS what it would deliver."""
    cfg = afmoe.afmoe_tiny(dtype=jnp.float32, held_first=0, held_count=16)
    p = dict(_layer_of(_params(cfg, seed=4), cfg, "W"))
    # (values bf16 holds: the routers of both dtypes read the same input,
    # so no near-tie is flipped by the cast)
    u = jax.random.normal(jax.random.PRNGKey(5), (2, cfg.seq_len, cfg.d_model)
                          ).astype(jnp.bfloat16).astype(jnp.float32)
    routing = dict(top_k=cfg.top_k, scaling=cfg.route_scale, eps=1e-20)
    shared_w = {w: p[w].astype(dtype) for w in moe.GATED_SHARED_EXPERT}
    with jax.default_matmul_precision("highest"):
        whole = jnp.stack([reference.experts(row, p, _sizes(cfg))[0]
                           for row in u])
        routed, with_shared = [], []
        for first in range(0, 16, 2):
            share = {k: v for k, v in p.items()
                     if k not in moe.GATED_SHARED_EXPERT}
            share.update({w: p[w][first:first + 2].astype(dtype)
                          for w in moe.GATED_EXPERT})
            routed.append(moe.gated_moe(u.astype(dtype), share,
                                        held=moe.Held(first, 2), **routing)[0])
            with_shared.append(moe.gated_moe(
                u.astype(dtype), {**share, **shared_w},
                held=moe.Held(first, 2), **routing)[0])
        shared = with_shared[0] - routed[0]
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    top = float(jnp.abs(whole).max())
    np.testing.assert_allclose(sum(routed) + shared, whole, rtol=tol,
                               atol=tol * top)
    # every chip's layer holds the shared expert whole: the same on each
    for both, alone in zip(with_shared, routed):
        np.testing.assert_allclose(both - alone, shared, atol=4 * tol * top)
    # a share is a strict part: none of them is the layer
    assert float(jnp.abs(with_shared[0] - whole).max()) > 0.1 * top


def test_the_router_is_the_biased_normalised_scaled_sigmoid():
    """moe.route as the family calls it against the reference's gates: the
    bias chooses only, the chosen scores over their sum, times 2.0."""
    cfg = afmoe.afmoe_tiny(dtype=jnp.float32, held_first=0, held_count=16)
    p = _layer_of(_params(cfg, seed=6), cfg, "F")
    p = {**p, "router_bias": 0.3 * jax.random.normal(
        jax.random.PRNGKey(7), p["router_bias"].shape)}
    u = jax.random.normal(jax.random.PRNGKey(8), (96, cfg.d_model))
    gates, report = reference.routed_gates(u, p, _sizes(cfg))
    here, mine = moe.route(u, p["router_w"], p["router_bias"], cfg.top_k,
                           cfg.route_scale, cfg.held, 1e-20)
    assert (np.asarray(here) == np.asarray(report["own"])).all()
    assert int(here.sum()) == 96 * cfg.top_k
    np.testing.assert_allclose(np.where(here, mine, 0.0), gates, rtol=1e-6)
    np.testing.assert_allclose(gates.sum(-1), cfg.route_scale, rtol=1e-5)
    # the unbiased top-k is another set somewhere: the bias does choose
    plain = reference.routed_gates(u, {**p, "router_bias": 0 * p[
        "router_bias"]}, _sizes(cfg))[1]["own"]
    assert (np.asarray(plain) != np.asarray(here)).any()


def test_set_up_balances_the_bias_and_changes_nothing_else():
    cfg = afmoe.afmoe_tiny(dtype=jnp.float32)
    params = afmoe.init(cfg, jax.random.PRNGKey(0))
    batches = [jnp.asarray(_batch(cfg, seed=s)[0]) for s in range(4)]
    balanced, loads = afmoe.balance_router_bias(params, batches, cfg)
    assert [load["layer"] for load in loads] == [2, 3, 4, 5]
    changed = {jax.tree_util.keystr(path)
               for (path, a), b in zip(
                   jax.tree_util.tree_leaves_with_path(params),
                   jax.tree.leaves(balanced))
               if not np.array_equal(np.asarray(a), np.asarray(b))}
    assert changed and all(name.endswith("['router_bias']")
                           for name in changed)
    counters = afmoe.step_counters(cfg)
    assert counters.layers == (2, 3, 4, 5)
    _, packed = afmoe.loss_fn(balanced, *_batch(cfg), cfg, counters=True)
    assert packed.shape == (4, len(names.STEP_EXPERT_LOAD_ARGS))


# --------------------------------------------------------------------------- #
# The configuration, the cell, the family's arithmetic
# --------------------------------------------------------------------------- #

def _cell():
    from benchmarks.harness import spec

    return spec.load_cell(CELL)


def test_the_configuration_holds_every_published_width_and_states_its_cut():
    cell, config, _ = _cell()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Trinity-Mini")
    assert config["source"] == row["source_url"]
    entry = next(c for c in _benchmark()["configs"]
                 if c["name"] == "trinity-mini-l5")
    differ = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differ == set(entry["reduced"]) == {
        "num_hidden_layers", "layer_types", "num_dense_layers", "num_experts",
        "vocab_size"}
    assert config["published"] == {k: row["config"][k] for k in (
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size")}
    assert config["layer_types"] == row["config"]["layer_types"][1:6]
    assert [a[:3] for a in config["assumed"]] == [
        f"({c})" for c in "abcdefghi"]
    assert len(config["reduced"]) == 3 and "EP 8" in config["deployment"]
    assert (cell["seq_len"], cell["per_chip_batch"], cell["chips"],
            cell["traffic"], cell["remat"]) == (16384, 2, 1, "dataset", True)


def test_the_cells_parameters_and_the_familys_arithmetic():
    cell, config, _ = _cell()
    cfg = family.program_config(config, cell)
    assert cfg.pattern == "DWFWW" and cfg.first_layer == 1
    assert (cfg.held, cfg.n_experts, cfg.top_k, cfg.route_scale) == (
        moe.Held(0, 16), 128, 8, 2.826)
    shapes = family.shapes(config, cell)
    assert (afmoe.param_count(cfg) == shapes["params"] == config["params"]
            == 705_473_792)
    # ISSUE 66's arithmetic counted the four biases of 128 (buffers)
    assert shapes["params"] + 4 * 128 == 705_474_304
    assert afmoe.flops_per_token(cfg) == pytest.approx(
        family.train_flops_per_token(shapes), rel=1e-12)
    # a window layer needs 23.4 % of the pairs a full layer needs
    band, half = (family.attended_pairs(16384, 2048),
                  family.attended_pairs(16384, None))
    assert band / half == pytest.approx(0.2344, abs=2e-4)
    assert afmoe.attended_pairs(16384, 2048) == band
    call = family.flash_attn_call(shapes)
    assert call["flops"] == 2 * 128 * 7 * 2 * 32 * (half + 4 * band)
    assert family.experts_call(shapes)["flops"] == (
        9 * 4 * 2 * 32768 * 1.0 * 2048 * 1024)
    assert blocks.pattern_groups(cfg.pattern) == [
        ("D", 1), ("W", 1), ("F", 1), ("W", 2)]
    assert afmoe.pattern_from(config["layer_types"], 1) == "DWFWW"
    full = afmoe.trinity_mini()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Trinity-Mini")
    assert full.pattern == afmoe.pattern_from(
        row["config"]["layer_types"], row["config"]["num_dense_layers"])


def test_the_rule_prices_a_window_layers_attention_by_the_band():
    cfg = afmoe.afmoe_tiny(seq_len=256, sliding_window=32,
                           attention_impl="pallas")
    base, kinds = afmoe.kind_shards(cfg, 2, 256, None)
    assert base.flash_window == 0 and base.window == 0 and base.kv_heads == 2
    assert {k: s.applications for k, s in kinds.items()} == {
        "D": 1, "W": 3, "F": 1}
    whole = parts.BlockShard(2, 256, cfg.d_model, 4, 16, 0, 256, 2, True,
                             False)
    flash = {bool(w): next(
        c for c in parts.remat_candidates(whole._replace(flash_window=w))
        if c.names == (names.RES_FLASH_O, names.RES_FLASH_LSE))
        for w in (0, 32)}
    assert flash[True].nbytes == flash[False].nbytes
    assert flash[True].flops / flash[False].flops == pytest.approx(
        parts.twice_causal_pairs(256, 32) / 256 ** 2)
    # EvaByte's `window` is another field and its arithmetic does not move
    assert parts.twice_causal_pairs(256) == parts.twice_causal_pairs(
        256, 256) == 256 ** 2


def test_the_family_refuses_a_program_without_the_model(monkeypatch):
    import importlib.util

    real = importlib.util.find_spec
    monkeypatch.setattr(importlib.util, "find_spec", lambda name, *a: (
        None if name == "ray_tpu.models.afmoe" else real(name, *a)))
    cell, config, _ = _cell()
    with pytest.raises(SystemExit, match="no window / full attention model"):
        family.shapes(config, cell)


@pytest.mark.parametrize("axis", ["ep", "tp", "pp", "cp"])
def test_a_mesh_the_family_cannot_run_on_is_refused(axis):
    from ray_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(**{axis: 2}), jax.devices()[:2])
    with pytest.raises(NotImplementedError, match=f"{axis} > 1"):
        afmoe.mesh_rules(afmoe.afmoe_tiny(), mesh)


def _tiny_bundle(seed=0):
    from benchmarks.harness import traffic
    from ray_tpu.parallel import mesh as mesh_lib

    cell, config, mix = _cell()
    with open(os.path.join(ROOT, "benchmarks", "testdata",
                           "rehearse-afmoe.json")) as f:
        tiny = json.load(f)
    config, cell = {**config, **tiny["config"]}, {**cell, **tiny["cell"]}
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(fsdp=1), jax.devices()[:1])
    bundle = family.build(config, cell, mesh, seed)
    rows = traffic.host_batch(cell["reference_rows"], seed, cell["seq_len"],
                              mix["alphabet"])
    return bundle, rows, config, cell


_bundle = {}


def _checked(control):
    from benchmarks.harness import checks

    if not _bundle:
        _bundle["it"] = _tiny_bundle()
    bundle, rows, config, cell = _bundle["it"]
    out = family.reference_check(
        bundle, rows, config, cell,
        **(family.controls()[control] if control else {}))
    summary = {"reference": out, "data_ok": True, "step_counter": 0,
               "steps_run": 0, "device_count": 1, "platforms": ["cpu"],
               "attention": ["xla", True],
               "window": {"nonfinite_losses": 0, "losses_tail": [1.0],
                          "first_loss": 2.0, "compiles_in_window": 0}}
    return out, checks.failures(summary, cell, True)


def test_the_comparison_that_decides_correct_passes_the_program():
    out, bad = _checked(None)
    assert not bad, bad
    assert 0 < out["program"]["grad_part_error"] < out["grad_norm_rtol"]
    assert len(out["reference"]["routing"]) == 4
    assert set(out["program"]["grad_error_by_part"]) >= {
        "D/wq", "W/w1", "F/wg", "wte", "lm_head", "final_norm"}
    assert not any("router_bias" in part
                   for part in out["program"]["grad_error_by_part"])


@pytest.mark.parametrize("control", sorted(family.controls()))
def test_the_comparison_that_decides_correct_refuses_every_control(control):
    _, bad = _checked(control)
    assert any("grad_norm" in line or "loss" in line for line in bad), bad


# --------------------------------------------------------------------------- #
# The benchmark's entries and readers
# --------------------------------------------------------------------------- #

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


def test_the_benchmark_gains_one_configuration_and_one_one_chip_cell():
    b = _benchmark()
    assert b["configs"][-1]["name"] == "trinity-mini-l5"
    assert b["workloads"][-1] == {**b["workloads"][-1], "name": CELL,
                                  "config": "trinity-mini-l5",
                                  "traffic": "dataset", "chips": 1}
    assert len(b["workloads"]) == 12 and len(b["configs"]) == 11
    per_layer = [m["name"] for m in b["per_layer"]]
    first = per_layer.index(NEW_READERS[0])
    assert per_layer[first:first + 5] == list(NEW_READERS)
    for name in SHARED_READERS:
        entry = next(m for m in b["per_layer"] if m["name"] == name)
        assert entry["workloads"][-1] == CELL, name
    # ~20 samples a window: the p90 is not this cell's
    p90 = next(m for m in b["end_to_end"] if m["name"] == "step_ms_p90")
    assert CELL not in p90["workloads"]
    for m in b["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert (CELL in m.get("workloads", ())) == (m["name"] in (
                "trinity_mfu_device", "trinity_flash_attn_roofline",
                "dsv2_experts_roofline")), m["name"]


def test_the_new_family_files_import_no_program_at_module_level():
    for name in ("afmoe", "afmoe_reference"):
        path = os.path.join(ROOT, "benchmarks", "families", name + ".py")
        with open(path) as f:
            tree = ast.parse(f.read(), path)
        level = tree.body if name == "afmoe" else list(ast.walk(tree))
        for node in level:
            if isinstance(node, ast.ImportFrom):
                assert (node.module or "").split(".")[0] != "ray_tpu"
            elif isinstance(node, ast.Import):
                assert all(a.name.split(".")[0] != "ray_tpu" for a in node.names)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_reads_nothing_from_a_run_without_its_source(name):
    """A parent's traced run: no trace tables with the new scopes, no
    record with a windowed call — nothing, and no exception."""
    reader = importlib.import_module(f"benchmarks.layer_metrics.{name}")
    cell, config, mix = _cell()
    facts = {"cell": cell, "config": config, "traffic": mix, "notes": [],
             "summary": {"shapes": family.shapes(config, cell)},
             "trace": None, "driver": {}}
    assert reader.read(facts) is None


def test_the_tile_share_reader_reads_the_windowed_calls_alone(monkeypatch):
    from benchmarks.harness import session_timeline
    from benchmarks.layer_metrics import flash_window_tile_share as reader

    def event(kernel, window, visited, causal):
        return {"cat": "ops", "name": "flash_tiling", "ph": "i", "ts": 0,
                "args": {"kernel": kernel, "window": window,
                         "tiles_visited": visited, "tiles_causal": causal}}

    record = [event("fwd", 0, 528, 528), event("bwd", 0, 528, 528),
              event("fwd", 2048, 150, 528), event("bwd", 2048, 150, 528),
              event("fwd", 2048, 150, 528),         # a duplicate: once
              {"cat": "train", "name": "fit", "ph": "X", "ts": 0, "args": {}}]
    monkeypatch.setattr(session_timeline, "load_record", lambda: record)
    assert reader.read({}) == pytest.approx(100 * 300 / 1056)
    # a parent's events carry no window
    monkeypatch.setattr(session_timeline, "load_record", lambda: [
        {"cat": "ops", "name": "flash_tiling", "args": {"kernel": "fwd"}}])
    assert reader.read({}) is None
    monkeypatch.setattr(session_timeline, "load_record", lambda: None)
    assert reader.read({}) is None


# --------------------------------------------------------------------------- #
# The attention operator's seams and its elementwise kernel pairs (PR 67)
# --------------------------------------------------------------------------- #

def _plain_operator(u, p, cfg, kind):
    """``afmoe.attention_operator`` as a plain composition: parts'
    QK-norm and RoPE, the XLA attention path over repeated heads, a plain
    gate — no barrier, no kernel —, the heads where ``parts.head_layout``
    has them (a sum's order follows the layout)."""
    layout = parts.head_layout(cfg.head_dim)
    heads = layout.replace("d", "k")
    window = cfg.window(kind)
    positions = jnp.arange(u.shape[1])

    def projected(w):
        return jnp.einsum(f"bsd,dhk->{heads}", u, w)

    def normed(w, g):
        x = parts.head_rmsnorm(projected(w), g, cfg.rms_eps,
                               heads.index("k"))
        return x if window is None else parts.rope(
            x, positions, cfg.rope_theta, heads[-1] == "s")

    q, k = normed(p["wq"], p["q_norm"]), normed(p["wk"], p["k_norm"])
    o = parts.causal_attention(q, k, projected(p["wv"]), "xla", layout=layout,
                               window=window)
    o = (o.astype(jnp.float32) * jax.nn.sigmoid(
        projected(p["wg"]).astype(jnp.float32))).astype(u.dtype)
    y = jnp.einsum(f"{heads},hkd->bsd", o, p["wo"],
                   preferred_element_type=jnp.float32)
    return parts.rmsnorm(y, p["attn_post_norm"], cfg.rms_eps)


def _operator_inputs(cfg, kind):
    """u and the layer's attention weights in the compute dtype (as
    ``afmoe._layer`` hands them over), and a cotangent."""
    p = _layer_of(_params(cfg), cfg, kind)
    p = {**p, **{n: p[n].astype(cfg.dtype) for n in afmoe._ATTN_WEIGHTS}}
    k = jax.random.split(jax.random.PRNGKey(11), 2)
    u = jax.random.normal(k[0], (2, cfg.seq_len, cfg.d_model)).astype(cfg.dtype)
    dy = jax.random.normal(k[1], (2, cfg.seq_len, cfg.d_model))
    return u, p, dy


def _value_and_vjp(fn, *args):
    """(fn's value, its vjp of the LAST argument) at the arguments before."""
    *primals, dy = args
    y, vjp = jax.vjp(fn, *primals)
    return (y,) + vjp(dy)


def _value_and_grads(fn, u, p, cfg, kind, dy):
    return _value_and_vjp(lambda u, p: fn(u, p, cfg, kind), u, p, dy)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["W", "F"], ids=["window", "full"])
def test_the_seams_change_no_number(kind, dtype):
    """The operator with its barriers (``parts.made_once`` on the weights as
    cast and on the gated o; the path of a head narrower than a lane tile
    and of any run off a TPU) against the plain composition: its value and
    the gradients of a scalar of it with respect to u and every weight — in
    float32 to the bit, in bf16 to one rounding of each value (XLA may keep
    excess precision inside a fusion that a barrier cuts)."""
    cfg = afmoe.afmoe_tiny(dtype=dtype, attention_impl="xla")
    u, p, dy = _operator_inputs(cfg, kind)
    got = jax.tree.leaves(jax.jit(lambda u, p: _value_and_grads(
        afmoe.attention_operator, u, p, cfg, kind, dy))(u, p))
    want = jax.tree.leaves(jax.jit(lambda u, p: _value_and_grads(
        _plain_operator, u, p, cfg, kind, dy))(u, p))
    assert len(got) == len(want) > 8
    for a, b in zip(got, want):
        a, b = (np.asarray(t, np.float32) for t in (a, b))
        if dtype == jnp.float32:
            assert np.array_equal(a, b)
        else:
            ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(b), 1e-30))) - 7)
            assert np.all(np.abs(a - b) <= ulp + 1e-6 * np.abs(b).max())


def _close(got, want, dtype):
    """≤ 1e-6 of the tensor's largest value in float32; in bf16 one ulp of
    EACH VALUE's binade besides (tests/test_qwen3_next.py's, for the
    ``delta_pointwise`` pairs)."""
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    tol = 1e-6 * max(1.0, float(np.max(np.abs(want))))
    if dtype == jnp.bfloat16:
        tol = tol + 2.0 ** (np.floor(np.log2(np.maximum(
            np.abs(want), 1e-30))) - 7)
    return bool(np.all(np.abs(got - want) <= tol))


def _rel(a, b):
    a, b = (np.asarray(t, np.float32) for t in (a, b))
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


def _plain_norm_rope(x, gain, heads, eps, theta):
    B, S, C = x.shape
    x = x.reshape(B, S, heads, C // heads).transpose(0, 2, 1, 3)
    y = parts.head_rmsnorm(x, gain, eps, 3)
    return y if theta is None else parts.rope(y, jnp.arange(S), theta)


def _heads_first(t, heads):
    B, S, C = t.shape
    return t.reshape(B, S, heads, C // heads).transpose(0, 2, 1, 3)


# S = 300 is 304 with the padding: one tile, or 19 of 16 tokens under a
# target of 64; S = 512 is four runs of 128 tokens in ONE tile; two heads a
# step at 256 tokens, one at 512 (a block stays under a MiB in float32)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("theta", [10_000.0, None], ids=["rope", "nope"])
@pytest.mark.parametrize("S,target", [(300, 512), (300, 64), (512, 512)],
                         ids=["one-padded-tile", "19-tiles", "4-runs"])
def test_the_norm_rope_pair_is_the_plain_qk_norm_and_rope(S, target, theta,
                                                          dtype, monkeypatch):
    """``head_norm_rope_fwd`` / ``_bwd`` (interpreted here) at hd 128 against
    ``parts.head_rmsnorm`` + ``parts.rope`` and ``jax.vjp`` of them: y — to
    the bit of the dtype without the rotation, one rounding with it (a
    multiply-add is fused or not) —, d x and d gain."""
    monkeypatch.setattr(attention_pointwise, "_TARGET_TOKENS", target)
    attention_pointwise._norm_rope_call.clear_cache()
    B, H, hd = 2, 4, 128
    k = jax.random.split(jax.random.PRNGKey(S), 3)
    x = jax.random.normal(k[0], (B, S, H * hd), jnp.float32).astype(dtype)
    dy = jax.random.normal(k[1], (B, H, S, hd), jnp.float32).astype(dtype)
    gain = 1 + 0.2 * jax.random.normal(k[2], (hd,), jnp.float32)
    y, vjp = jax.vjp(lambda x, g: attention_pointwise.head_norm_rope(
        x, g, H, 1e-5, theta, interpret=True), x, gain)
    dx, dg = vjp(dy)
    attention_pointwise._norm_rope_call.clear_cache()
    want_y, plain_vjp = jax.vjp(
        lambda x, g: _plain_norm_rope(x, g, H, 1e-5, theta), x, gain)
    want_dx, _ = plain_vjp(dy)
    # the gradients against the float32 arithmetic on the same values too:
    # the plain form in bf16 rounds d x three times on its way back, each to
    # 8 bits of a value that the norm's backward then cancels, and the sum
    # over every token that is d gain once
    # (at the gain as the plain form multiplies by it: rounded to the dtype)
    _, exact_vjp = jax.vjp(
        lambda x, g: _plain_norm_rope(x, g, H, 1e-5, theta),
        x.astype(jnp.float32), gain.astype(dtype).astype(jnp.float32))
    exact_dx, want_dg = exact_vjp(dy.astype(jnp.float32))
    assert (y.dtype, dx.dtype, dg.dtype) == (dtype, dtype, jnp.float32)
    assert y.shape == (B, H, S, hd) and dx.shape == x.shape
    if theta is None and dtype == jnp.bfloat16:
        assert np.array_equal(np.asarray(y, np.float32),
                              np.asarray(want_y, np.float32))
    assert _close(y, want_y, dtype)
    assert _close(dx, exact_dx, dtype)
    assert _rel(dx, want_dx) < (1e-6 if dtype == jnp.float32 else 1e-2)
    assert _rel(dg, want_dg) < (1e-6 if dtype == jnp.float32 else 2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("S,target", [(300, 512), (300, 64), (512, 512)],
                         ids=["one-padded-tile", "19-tiles", "4-runs"])
def test_the_gate_pair_is_the_plain_sigmoid_gate(S, target, dtype,
                                                 monkeypatch):
    """``attn_gate_fwd`` / ``_bwd`` against o · sigmoid(z) in float32 rounded
    once, and ``jax.vjp`` of it: y, d o and d z, each to the bit."""
    monkeypatch.setattr(attention_pointwise, "_TARGET_TOKENS", target)
    attention_pointwise._gate_call.clear_cache()
    B, H, hd = 2, 4, 128
    k = jax.random.split(jax.random.PRNGKey(S + 1), 3)
    o = jax.random.normal(k[0], (B, H, S, hd), jnp.float32).astype(dtype)
    z, dy = (jax.random.normal(key, (B, S, H * hd), jnp.float32).astype(dtype)
             for key in k[1:])

    def plain(o, z):
        y = (o.astype(jnp.float32) * jax.nn.sigmoid(
            _heads_first(z, H).astype(jnp.float32))).astype(o.dtype)
        return y.transpose(0, 2, 1, 3).reshape(z.shape)

    got = _value_and_vjp(lambda o, z: attention_pointwise.sigmoid_gated(
        o, z, interpret=True), o, z, dy)
    attention_pointwise._gate_call.clear_cache()
    want = _value_and_vjp(plain, o, z, dy)
    assert [t.dtype for t in got] == [dtype] * 3
    assert [t.shape for t in got] == [z.shape, o.shape, z.shape]
    assert all(_close(a, b, dtype) for a, b in zip(got, want))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["W", "F"], ids=["window", "full"])
def test_the_operator_on_its_kernels_is_the_plain_composition(kind, dtype,
                                                              tol):
    """At a head of a whole lane tile on the Pallas path (interpreted here)
    the operator runs the two kernel pairs beside the flash pair: value and
    every gradient against the plain composition."""
    cfg = afmoe.afmoe_tiny(dtype=dtype, attention_impl="pallas", head_dim=128)
    u, p, dy = _operator_inputs(cfg, kind)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda u, p: _value_and_grads(
            afmoe.attention_operator, u, p, cfg, kind, dy))(u, p)
        want = jax.jit(lambda u, p: _value_and_grads(
            _plain_operator, u, p, cfg, kind, dy))(u, p)
    calls = _kernel_calls(jax.make_jaxpr(lambda u, p: _value_and_grads(
        afmoe.attention_operator, u, p, cfg, kind, dy))(u, p).jaxpr)
    assert [calls[n] for n in (
        names.HEAD_NORM_ROPE_FWD_KERNEL, names.HEAD_NORM_ROPE_BWD_KERNEL,
        names.ATTN_GATE_FWD_KERNEL, names.ATTN_GATE_BWD_KERNEL)] == [2, 2, 1, 1]
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        # (the plain form in bf16 rounds a gain's gradient, a sum over every
        # token and head, to 8 bits; the kernel's partial sums are float32)
        loose = getattr(path[-1], "key", "") in ("q_norm", "k_norm")
        assert _rel(a, b) < tol * (4 if loose else 1), (path, _rel(a, b))


def _kernel_calls(jaxpr, found=None):
    """How often each Pallas kernel name stands in a jaxpr, sub-jaxprs
    included."""
    found = collections.Counter() if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_calls(sub, found)
    return found


def _barriers_by_scope(jaxpr, found=None):
    """The ``optimization_barrier`` equations of a jaxpr, sub-jaxprs
    included, counted by (the innermost of the program's scopes in their
    name stack, how many values they hold)."""
    found = collections.Counter() if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "optimization_barrier":
            stack = re.split(r"[/()]", str(eqn.source_info.name_stack))
            found[next((s for s in reversed(stack) if s in names.SCOPES), ""),
                  len(eqn.invars)] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _barriers_by_scope(sub, found)
    return found


def test_a_dropped_seam_fails_a_test_and_not_a_cell():
    """``afmoe_tiny``'s loss holds, a layer body, under ``qkv`` ONE barrier
    on the five weights as cast; its gradient holds that one again (the
    cotangents', and the recompute's). On the plain path the gated o keeps
    its barrier under ``gated_attn_gate``; at a head of a whole lane tile on
    the Pallas path QK-norm + RoPE and the gate are the kernel pairs — a
    custom call fuses with nothing — and no other barrier stands. (None on
    u, on q's and k's cotangents or on the gate's product: measured, they
    gave nothing or cost; PERF.md §6, PR 67.)"""
    def barriers(cfg):
        params, (tokens, targets) = _params(cfg), _batch(cfg)
        loss = lambda p: afmoe.loss_fn(p, tokens, targets, cfg)
        return (_barriers_by_scope(jax.make_jaxpr(loss)(params).jaxpr),
                _barriers_by_scope(jax.make_jaxpr(jax.grad(loss))(
                    params).jaxpr))

    # the pattern DWFWW is three layers unrolled and one scanned run of two
    bodies, weights = 4, len(afmoe._ATTN_WEIGHTS)
    fwd, both = barriers(afmoe.afmoe_tiny(attention_impl="xla"))
    assert fwd[names.QKV, weights] == bodies
    assert both[names.QKV, weights] >= 2 * bodies
    assert fwd[names.GATED_ATTN_GATE, 1] == bodies
    assert both[names.GATED_ATTN_GATE, 1] >= 2 * bodies
    wide = afmoe.afmoe_tiny(attention_impl="pallas", head_dim=128)
    fwd, both = barriers(wide)
    assert fwd[names.QKV, weights] == bodies
    assert both[names.QKV, weights] >= 2 * bodies
    assert set(both) == {(names.QKV, weights)}
    params, (tokens, targets) = _params(wide), _batch(wide)
    calls = _kernel_calls(jax.make_jaxpr(jax.grad(
        lambda p: afmoe.loss_fn(p, tokens, targets, wide)))(params).jaxpr)
    assert all(calls[n] >= bodies for n in (
        names.HEAD_NORM_ROPE_FWD_KERNEL, names.HEAD_NORM_ROPE_BWD_KERNEL,
        names.ATTN_GATE_FWD_KERNEL, names.ATTN_GATE_BWD_KERNEL))
