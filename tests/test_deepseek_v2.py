"""The DeepSeek-V2 family (``ray_tpu/models/deepseek_v2.py``, the flash pair at
unequal q·k and v widths, ``ops/moe``'s softmax router, shared expert and
balance loss) against its plain float32 reference
(``benchmarks/families/deepseek_v2_reference.py``): the whole step's loss —
balance loss included — and every gradient, the four shares of an expert
layer tied to the uncut layer, the flash kernels at 24 / 16 and 256 / 128
against the einsum with a given scale, YaRN's frequencies and softmax scale
against numbers written here, the published rotary order mapped onto the
program's, the router's rules (un-normalised gates, a token without a held
choice still teaching the router through the balance loss, ties), the cell's
parameter count and the family's arithmetic, the meshes it refuses, the
bf16-statistics control through the comparison that decides ``correct``, what
a step says of itself — and the benchmark's new entries: each reader this PR
adds names the new cell alone, imports nothing of ``ray_tpu`` at module level
and reads nothing, without raising, from another cell's recorded trace."""

import ast
import dataclasses
import importlib
import json
import math
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.families import deepseek_v2 as family  # noqa: E402
from benchmarks.families import deepseek_v2_reference as reference  # noqa: E402
from ray_tpu.models import blocks, deepseek_v2 as ds, parts  # noqa: E402
from ray_tpu.ops import attention, moe  # noqa: E402
from ray_tpu.tracing import names  # noqa: E402

# the family's default rule: a softmax whose chosen probabilities gate as
# they are (the configuration's, since PR 57)
RULE = ds.DeepseekV2Config().rule
CELL = "deepseek-v2-lite-l5.dataset"
CONFIG = "deepseek-v2-lite-l5"
NEW_READERS = ("dsv2_mfu_device", "mla_flash_attn_roofline",
               "dsv2_experts_roofline", "mla_latent_ms_per_step",
               "moe_aux_ms_per_step")
# accepted readers of a scope, a kernel or a counter this family's step has
SHARED_READERS = ("flash_fwd_ms_per_step", "flash_bwd_ms_per_step",
                  "moe_routed_ms_per_step", "moe_dispatch_ms_per_step",
                  "moe_shared_ms_per_step", "moe_further_passes_ms_per_step",
                  "moe_passes_per_step", "moe_multi_pass_steps",
                  "moe_load_imbalance")


def _batch(cfg, rows=2, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, 64, (rows, cfg.seq_len)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1)
    targets[:, -1] = -1
    return tokens, targets


def _params(cfg, seed=0):
    return ds.init(cfg, jax.random.PRNGKey(seed))


def _sizes(cfg, **switches):
    return family.reference_sizes(cfg, **switches)


def _expert_layer(params, cfg):
    """The first expert layer's tensors."""
    return reference.layer_params(cfg.pattern, params["blocks"])[
        cfg.pattern.index("E")][1]


def _norms(tree):
    return [float(jnp.linalg.norm(g.astype(jnp.float32)))
            for g in jax.tree.leaves(tree)]


def _program_and_reference(cfg, params, tokens, targets, **switches):
    with jax.default_matmul_precision("highest"):
        mine = jax.value_and_grad(ds.loss_fn)(params, tokens, targets, cfg)
        sets = [s.reshape(tokens.shape + (cfg.n_experts,))
                for s in ds.chosen_experts(params, tokens, cfg)]
        (loss, reports), grads = jax.value_and_grad(
            lambda p: reference.loss_and_routing(
                p, tokens, targets, _sizes(cfg, **switches), sets)[:2],
            has_aux=True)(params)
    return mine, (loss, grads, reports)


# ------------------------------------------------------------ the whole step
@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("remat", [False, True], ids=["no-remat", "remat"])
def test_loss_and_every_gradient_equal_the_reference_in_float32(remat, impl):
    """Balance loss included, at a coefficient that makes it a fifth of the
    gradient: through the layer scan, the checkpoint and the step's grad."""
    cfg = ds.deepseek_v2_tiny(dtype=jnp.float32, remat=remat,
                              attention_impl=impl, aux_loss_alpha=0.5)
    tokens, targets = _batch(cfg)
    params = _params(cfg, seed=1)
    (loss, grads), (ref_loss, ref_grads, reports) = _program_and_reference(
        cfg, params, tokens, targets)
    np.testing.assert_allclose(loss, ref_loss, rtol=2e-6)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(ref_grads), strict=True):
        np.testing.assert_allclose(
            g, r, rtol=2e-4, atol=2e-5 * float(jnp.abs(r).max()),
            err_msg=jax.tree_util.keystr(path))
    assert all(int(r["differ"]) == 0 for r in reports)
    # the balance loss is in the objective: without it the loss is another
    plain = ds.loss_fn(params, tokens, targets,
                       ds.deepseek_v2_tiny(dtype=jnp.float32, remat=remat,
                                           attention_impl=impl,
                                           aux_loss_alpha=0.0))
    layers = cfg.pattern.count("E")
    assert 0.3 * layers < float(loss - plain) < 1.0 * layers    # ~0.5 a layer


def test_the_reference_in_blocks_is_the_reference_whole(monkeypatch):
    """The reference's blocks of query rows and of tokens are memory, not
    meaning: in blocks of 16 it gives what it gives with a row whole."""
    cfg = ds.deepseek_v2_tiny(dtype=jnp.float32, aux_loss_alpha=0.5)
    tokens, targets = _batch(cfg)
    params = _params(cfg, seed=1)

    def both():
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(lambda p: reference.loss(
                p, tokens, targets, _sizes(cfg)))(params)

    whole = both()
    monkeypatch.setattr(reference, "TOKEN_BLOCK", 16)
    monkeypatch.setattr(reference, "QUERY_BLOCK", 16)
    for a, b in zip(jax.tree.leaves(both()), jax.tree.leaves(whole),
                    strict=True):
        np.testing.assert_allclose(a, b, rtol=1e-5,
                                   atol=1e-6 * float(jnp.abs(b).max()))


def test_the_balance_loss_is_one_where_the_router_is_uniform():
    """f_e · P_e summed is 1.0 when every expert is chosen equally often and
    scored 1 / n: the loss's own scale (moe.balance_loss, and the
    reference's, from counts)."""
    T, E, K, S = 64, 8, 2, 16
    chosen = np.zeros((T, E), bool)
    for t in range(T):
        chosen[t, [(2 * t) % E, (2 * t + 1) % E]] = True
    scores = jnp.full((T, E), 1.0 / E)
    assert float(moe.balance_loss(scores, jnp.asarray(chosen), K, S)) \
        == pytest.approx(1.0, abs=1e-6)
    # everyone on experts 0 and 1, scored there: n / top_k times as much
    skew = jnp.zeros((T, E)).at[:, :2].set(0.5)
    all_on_two = jnp.zeros((T, E), bool).at[:, :2].set(True)
    assert float(moe.balance_loss(skew, all_on_two, K, S)) \
        == pytest.approx(E / K, abs=1e-5)


def test_a_step_says_its_balance_loss_among_its_counters():
    """``loss_fn(..., counters=True)`` hands out one int32 array; the balance
    loss rides in it as float32 bits, and the step's decoder reads it back."""
    from ray_tpu.train.train_step import _Step

    cfg = ds.deepseek_v2_tiny(dtype=jnp.float32, aux_loss_alpha=0.5)
    tokens, targets = _batch(cfg)
    params = _params(cfg, seed=1)
    loss, counters = ds.loss_fn(params, tokens, targets, cfg, counters=True)
    assert counters.dtype == jnp.int32
    assert counters.shape == (cfg.pattern.count("E"), len(ds.step_fields(cfg)))
    spec = ds.step_counters(cfg)
    assert spec.fields[-1] == names.STEP_BALANCE_LOSS in spec.float_fields
    assert spec.layers == (1, 2, 3)
    args = _Step(None, spec)._decode(tokens.size)(np.asarray(counters))
    balance = args[names.STEP_BALANCE_LOSS]
    assert all(0.8 < b < 1.6 for b in balance), balance
    plain = ds.loss_fn(params, tokens, targets,
                       ds.deepseek_v2_tiny(dtype=jnp.float32,
                                           aux_loss_alpha=0.0))
    assert float(loss - plain) == pytest.approx(0.5 * sum(balance), rel=1e-5)
    assert args["passes"] == [1, 1, 1] and args["held"] == cfg.held_count
    assert ds.step_counters(ds.deepseek_v2_tiny(n_layer=1)) is None


def _assert_the_same_bits(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


def test_remat_changes_no_number():
    cfg = ds.deepseek_v2_tiny()
    tokens, targets = _batch(cfg)
    params = _params(cfg)
    a = jax.value_and_grad(ds.loss_fn)(params, tokens, targets, cfg)
    b = jax.value_and_grad(ds.loss_fn)(
        params, tokens, targets, ds.deepseek_v2_tiny(remat=True))
    _assert_the_same_bits(a, b)


def test_kept_residuals_change_no_number_and_spare_the_second_flash_forward():
    """PR 56: with a limit stated that gives the rule room for the flash
    kernel's o and lse and for the latent pair, the loss and every gradient
    are the fully rematted step's bit for bit — a kept residual is what the
    second forward would have made — and the backward of a checkpointed
    layer calls the forward kernel no second time: one call a run of the
    layers (the dense layer, the scan's body) where nothing kept makes two."""
    from ray_tpu.parallel import mesh as mesh_lib

    cfg = ds.deepseek_v2_tiny(remat=True, attention_impl="pallas")
    tokens, targets = _batch(cfg)
    params = _params(cfg)
    base, kinds = ds.kind_shards(cfg, 2, cfg.seq_len, None)
    phase = _phase(cfg, base, kinds)
    ranked = sorted(((c, k.applications) for k in kinds.values()
                     for c in k.candidates),
                    key=lambda cn: -cn[0].flops / cn[0].nbytes)
    wanted = {names.RES_FLASH_O, names.RES_FLASH_LSE, names.RES_MLA_C,
              names.RES_MLA_KPE}
    last = max(i for i, (c, _) in enumerate(ranked) if wanted & set(c.names))
    limit = blocks.REMAT_RESERVE_BYTES + phase.nbytes + 12345 + sum(
        n * c.nbytes for c, n in ranked[:last + 1])

    def loss(p, limit):
        with mesh_lib.chip_memory(limit, 12345):
            return ds.loss_fn(p, tokens, targets, cfg)

    kept = jax.value_and_grad(partial(loss, limit=limit))
    nothing = jax.value_and_grad(partial(loss, limit=None))
    _assert_the_same_bits(kept(params), nothing(params))
    (d,) = [d for d in blocks.remat_policy_decisions()
            if d["bytes_limit"] == limit and d["seq"] == cfg.seq_len]
    assert wanted <= set(d["saved"]) and len(d["saved"]) < len(names.RESIDUALS)
    forward = f"name={names.FLASH_FWD_KERNEL}"
    runs = len(blocks.pattern_groups(cfg.pattern))
    assert str(jax.make_jaxpr(kept)(params)).count(forward) == runs
    assert str(jax.make_jaxpr(nothing)(params)).count(forward) == 2 * runs


# ------------------------------------------------------------------ the shares
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_four_shares_add_up_to_the_uncut_layer(dtype):
    """Held 0-3, 4-7, 8-11, 12-15 of 16 experts: four chips' ROUTED parts of
    one expert layer, each routing over all 16, plus what every chip
    computes alike — the shared expert — counted ONCE, are the reference's
    uncut layer (every expert held). No code stands in for the exchange."""
    cfg = ds.deepseek_v2_tiny(dtype=jnp.float32, held_first=0, held_count=16)
    p = dict(_expert_layer(_params(cfg, seed=4), cfg))
    u = jax.random.normal(jax.random.PRNGKey(5), (2, cfg.seq_len, cfg.d_model))
    routing = dict(top_k=cfg.top_k, scaling=cfg.routed_scaling, rule=RULE)
    with jax.default_matmul_precision("highest"):
        whole = jnp.stack([reference.experts(row, p, _sizes(cfg))[0]
                           for row in u])
        shared_once = jnp.stack([
            reference.experts(row, p, _sizes(cfg, drop_routed=True))[0]
            for row in u])
        routed = []
        for first in (0, 4, 8, 12):
            share = {k: v for k, v in p.items() if not k.startswith("shared_")}
            share.update({w: p[w][first:first + 4].astype(dtype)
                          for w in moe.GATED_EXPERT})
            routed.append(moe.gated_moe(u.astype(dtype), share,
                                        held=moe.Held(first, 4), **routing)[0])
        with_shared = moe.gated_moe(
            u.astype(dtype),
            {**p, **{w: p[w][:4].astype(dtype) for w in moe.GATED_EXPERT},
             **{w: p[w].astype(dtype) for w in moe.GATED_SHARED_EXPERT}},
            held=moe.Held(0, 4), **routing)[0]
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    scale = float(jnp.abs(whole).max())
    np.testing.assert_allclose(sum(routed) + shared_once, whole, rtol=tol,
                               atol=tol * scale)
    # the layer's own output on one chip is its routed share + the shared
    np.testing.assert_allclose(with_shared, routed[0] + shared_once, rtol=tol,
                               atol=tol * scale)
    assert float(jnp.abs(routed[0] + shared_once - whole).max()) > 0.05 * scale


# ------------------------------------------------------------------ the router
def test_the_gates_are_the_chosen_probabilities_as_they_are():
    """Softmax over all the experts, top_k chosen, no division by their sum:
    a token's gates sum below 1 — and to exactly the chosen probabilities."""
    T, D, E, K = 32, 16, 8, 3
    u = jax.random.normal(jax.random.PRNGKey(0), (T, D))
    w = jax.random.normal(jax.random.PRNGKey(1), (D, E))
    here, gates, (scores, chosen) = moe.route(
        u, w, None, K, 1.0, moe.Held(0, E), rule=RULE, with_choice=True)
    probs = jax.nn.softmax(u @ w, axis=-1)
    np.testing.assert_allclose(scores, probs, rtol=1e-5)
    assert np.all(np.asarray(here).sum(-1) == K)
    total = np.where(here, gates, 0.0).sum(-1)
    np.testing.assert_allclose(
        total, np.sort(np.asarray(probs), -1)[:, -K:].sum(-1), rtol=1e-5)
    assert np.all(total < 1.0)
    # the sigmoid routers' rule still normalises: their gates sum to scaling
    bias = jnp.zeros((E,))
    here, gates = moe.route(u, w, bias, K, 2.5, moe.Held(0, E))
    np.testing.assert_allclose(np.where(here, gates, 0.0).sum(-1), 2.5,
                               rtol=1e-5)


def test_a_token_without_a_held_choice_still_teaches_the_router():
    """Held experts 6, 7 of 8 and a router that sends every token to 0 … 2:
    the routed output is zero and so is its gradient, but the balance loss
    reads every token's probabilities, so W_g still gets one."""
    cfg = ds.deepseek_v2_tiny(dtype=jnp.float32, n_experts=8, top_k=3,
                              held_first=6, held_count=2)
    p = dict(_expert_layer(_params(cfg, seed=2), cfg))
    p["router_w"] = p["router_w"].at[:, :3].add(0.05)
    u = jnp.abs(jax.random.normal(jax.random.PRNGKey(3),
                                  (2, cfg.seq_len, cfg.d_model)))

    def out(router_w, balance):
        y, load = moe.gated_moe(
            u, {k: v for k, v in {**p, "router_w": router_w}.items()
                if not k.startswith("shared_")},
            top_k=cfg.top_k, held=cfg.held, scaling=1.0, rule=RULE,
            balance=balance)
        return jnp.sum(y) + (load[names.STEP_BALANCE_LOSS] if balance else 0.0), load

    (_, load), g = jax.value_and_grad(out, has_aux=True)(p["router_w"], True)
    assert int(load["pairs"]) == 0
    assert float(jnp.abs(g).max()) > 1e-4
    g0 = jax.grad(lambda w: out(w, False)[0])(p["router_w"])
    assert float(jnp.abs(g0).max()) == 0.0


def test_set_up_balances_the_routers_and_changes_nothing_else():
    """``balance_routers``: every expert layer's router after rounds of
    descent on its own balance loss, here all on one batch — the held
    experts' fullest load comes down towards the mean, no pair is dropped,
    and no tensor but ``router_w`` moves."""
    cfg = ds.deepseek_v2_tiny(seq_len=256, n_experts=16, top_k=4,
                              held_first=0, held_count=8)
    tokens, _ = _batch(cfg, rows=4)
    params = _params(cfg, seed=5)
    balanced, events = ds.balance_routers(params, jnp.asarray(tokens), cfg)
    assert [e["layer"] for e in events] == [1, 2, 3]
    assert all(e["pairs_dropped"] == 0 for e in events)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(params),
                            jax.tree.leaves(balanced), strict=True):
        moved = not np.array_equal(np.asarray(a), np.asarray(b))
        assert moved == (getattr(path[-1], "key", None) == "router_w"), path

    def fullest(p):
        with jax.default_matmul_precision("highest"):
            sets = ds.chosen_experts(p, jnp.asarray(tokens), cfg)
        loads = [np.asarray(s).sum(0) for s in sets]
        return [float(load.max() / load.mean()) for load in loads]

    before, after = fullest(params), fullest(balanced)
    assert max(after) < 1.25 < max(before), (before, after)
    # ... which is what the balance loss measures: a round brings it down
    u = jax.random.normal(jax.random.PRNGKey(0), (512, 32))
    w = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (32, 8))
    one = moe.balance_router(u, w, 2, 128, moe.BALANCE_ROUTER_RATE, RULE)
    assert one.dtype == jnp.float32 and one.shape == (32, 8)

    def balance(w):
        return float(moe.balance_loss(
            *moe.scored_choice(u, w, None, 2, RULE.scoring), 2, 128))

    assert balance(one) < balance(w)


def test_tied_probabilities_choose_the_first_experts_in_both():
    """A zero router scores every expert 1 / n: ``_chosen`` orders ties as
    ``lax.top_k`` does, lowest ids first, in the program and the reference."""
    cfg = ds.deepseek_v2_tiny(dtype=jnp.float32)
    p = dict(_expert_layer(_params(cfg), cfg))
    p["router_w"] = jnp.zeros_like(p["router_w"])
    u = jax.random.normal(jax.random.PRNGKey(0), (cfg.seq_len, cfg.d_model))
    mine = moe.chosen_experts(u, p, cfg.top_k, RULE)
    _, _, report = reference.routed_gates(u, p, _sizes(cfg))
    want = np.zeros((cfg.seq_len, cfg.n_experts), bool)
    want[:, :cfg.top_k] = True
    np.testing.assert_array_equal(mine, want)
    np.testing.assert_array_equal(report["own"], want)


# ------------------------------------------------------ flash at unequal widths
def _einsum_attention(q, k, v, scale):
    """q, k [B, H, S, hd], v [B, H, S, hd_v], causal, float32."""
    s = q.shape[2]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    logits = jnp.where(jnp.tril(jnp.ones((s, s), bool)), logits, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(logits, axis=-1), v)


@pytest.mark.parametrize("hd,hd_v,S,layout,pair", [
    (24, 16, 256, "hbds", attention.S_MINOR),
    (24, 16, 256, "bshd", attention.S_MINOR),
    (256, 128, 128, "bhsd", attention.HD_MINOR),
], ids=["24-16-hbds", "24-16-bshd", "256-128-bhsd"])
def test_flash_at_unequal_widths_equals_the_einsum(hd, hd_v, S, layout, pair,
                                                   monkeypatch):
    """Forward and the three gradients in interpret mode, with the caller's
    scale, q·k at ``hd`` and v at ``hd_v`` read as they are."""
    monkeypatch.setattr(attention, "_decisions", {})
    B, H, scale = 1, 2, 0.37
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = (jax.random.normal(kk, (B, H, S, hd)) for kk in keys[:2])
    v = jax.random.normal(keys[2], (B, H, S, hd_v))
    w = jax.random.normal(keys[3], (B, H, S, hd_v))
    to = lambda x: attention._relayout(x, "bhsd", layout)

    def flash(q, k, v):
        o = attention.flash_attention(to(q), to(k), to(v), scale=scale,
                                      block_q=128, block_k=128,
                                      interpret=True, layout=layout)
        assert o.shape == to(w).shape
        return jnp.sum(o * to(w))

    def plain(q, k, v):
        return jnp.sum(_einsum_attention(q, k, v, scale) * w)

    with jax.default_matmul_precision("highest"):
        mine = jax.value_and_grad(flash, (0, 1, 2))(q, k, v)
        want = jax.value_and_grad(plain, (0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(want), strict=True):
        np.testing.assert_allclose(a, b, rtol=2e-4,
                                   atol=2e-4 * float(jnp.abs(b).max()))
    assert attention.kernel_layout(hd, hd_v) == pair
    events = attention.flash_tiling_decisions()
    assert {(d["kernel"], d["hd"], d["hd_v"], d["layout"]) for d in events} \
        == {("fwd", hd, hd_v, pair), ("bwd", hd, hd_v, pair)}


def test_causal_attention_passes_a_scale_through_both_paths():
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 1, 24, 64))   # hbds
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 1, 24, 64))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 1, 16, 64))
    outs = [parts.causal_attention(q, k, v, impl, layout="hbds", scale=0.11)
            for impl in ("xla", "pallas")]
    assert outs[0].shape == v.shape
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-3, atol=2e-3)
    other = parts.causal_attention(q, k, v, "xla", layout="hbds")
    assert float(jnp.abs(other - outs[0]).max()) > 1e-2


# ------------------------------------------------------------- YaRN and RoPE
def test_yarn_frequencies_and_the_softmax_scale_are_the_published_ones():
    """The 32 frequencies of the published rope_scaling (factor 40 over
    4,096, beta 32 / 1, theta 10,000, 64 channels): pairs below the
    correction range's low 10 keep ``10000^(-2i/64)``, from its high 23 on
    they are divided by 40, between the two blended — numbers written out
    here; m = 0.1 · 0.707 · ln 40 + 1 and the softmax scale 192^-½ · m²."""
    cfg = ds.DeepseekV2Config()
    mine = ds.rope_inv_freq(cfg)
    ref = np.asarray(reference.yarn_inv_freq(_sizes(cfg)))
    assert mine.shape == ref.shape == (32,)
    np.testing.assert_allclose(mine, ref, rtol=1e-6)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(mine[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(mine[23:], plain[23:] / 40.0, rtol=1e-6)
    want = {0: 1.0, 10: 0.0562341325, 11: 0.0390069, 16: 0.0055,
            22: 1.7782794e-04, 23: 3.3338e-05, 31: 3.3338e-06}
    for i, f in want.items():
        assert mine[i] == pytest.approx(f, rel=2e-4), i
    ramp = (np.arange(32) - 10) / 13.0
    np.testing.assert_allclose(
        mine[11:23], plain[11:23] * (ramp[11:23] / 40 + 1 - ramp[11:23]),
        rtol=1e-5)
    m = 0.1 * 0.707 * math.log(40.0) + 1.0
    assert m * m == pytest.approx(1.5896, abs=5e-5)
    assert cfg.softmax_scale == pytest.approx(1.5896 / math.sqrt(192), rel=1e-4)
    assert reference.softmax_scale(_sizes(cfg)) == pytest.approx(
        cfg.softmax_scale, rel=1e-9)
    # factor 1 is plain RoPE and the plain 1 / sqrt(width)
    off = ds.DeepseekV2Config(rope_factor=1.0)
    np.testing.assert_allclose(ds.rope_inv_freq(off), plain, rtol=1e-6)
    assert off.softmax_scale == pytest.approx(1 / math.sqrt(192))


@pytest.mark.parametrize("s_minor", [False, True], ids=["hd-minor", "s-minor"])
def test_rope_over_a_span_with_given_frequencies(s_minor):
    """``parts.rope`` with frequencies and a channel span: the span's
    channels as the plain call rotates them alone, the rest untouched; with
    neither the call is what it was."""
    S, lo, hi = 16, 8, 20
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, S, 24))
    pos = jnp.arange(S)
    freqs = np.linspace(1.0, 0.01, (hi - lo) // 2).astype(np.float32)
    xs = jnp.swapaxes(x, -1, -2) if s_minor else x
    out = parts.rope(xs, pos, 0.0, s_minor, inv_freq=freqs, span=(lo, hi))
    out = jnp.swapaxes(out, -1, -2) if s_minor else out
    alone = parts.rope(x[..., lo:hi], pos, 0.0, inv_freq=freqs, span=(0, 12))
    np.testing.assert_allclose(out[..., lo:hi], alone, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(out[..., :lo], x[..., :lo])
    np.testing.assert_array_equal(out[..., hi:], x[..., hi:])
    # by hand: pairs (i, i + 6) of the span
    ang = np.arange(S)[:, None] * freqs
    a, b = np.asarray(x[..., lo:lo + 6]), np.asarray(x[..., lo + 6:hi])
    np.testing.assert_allclose(
        alone, np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                               b * np.cos(ang) + a * np.sin(ang)], -1),
        rtol=1e-4, atol=1e-5)
    # the plain call: theta alone, every channel, as the llama block asks
    theta = 10000.0
    plain = parts.rope(xs, pos, theta, s_minor)
    same = parts.rope(
        xs, pos, theta, s_minor, span=(0, 24),
        inv_freq=1.0 / theta ** (np.arange(12, dtype=np.float32) / 12))
    np.testing.assert_allclose(plain, same, rtol=1e-5, atol=1e-6)


def test_the_published_rotary_order_maps_onto_the_programs():
    """The published checkpoint's rotary columns are interleaved and its code
    de-interleaves them; the program's are stored de-interleaved. Permuting
    the program's rotary columns of W_q and W_kva INTO the published order
    and telling the reference so gives the program's numbers."""
    cfg = ds.deepseek_v2_tiny(dtype=jnp.float32)
    tokens, targets = _batch(cfg)
    params = _params(cfg, seed=3)
    r, half = cfg.qk_rope_dim, cfg.qk_rope_dim // 2
    # published[2i] = program[i], published[2i + 1] = program[i + half]
    to_published = np.stack([np.arange(half), half + np.arange(half)],
                            axis=1).reshape(r)

    def published(stack):
        wq = stack["wq"]
        wq = jnp.concatenate([wq[..., :cfg.qk_nope_dim],
                              wq[..., cfg.qk_nope_dim:][..., to_published]], -1)
        wkv_a = stack["wkv_a"]
        wkv_a = jnp.concatenate([wkv_a[..., :cfg.kv_lora_rank],
                                 wkv_a[..., cfg.kv_lora_rank:][..., to_published]], -1)
        return {**stack, "wq": wq, "wkv_a": wkv_a}

    theirs = {**params, "blocks": [{k: published(s) for k, s in g.items()}
                                   for g in params["blocks"]]}
    with jax.default_matmul_precision("highest"):
        mine = ds.loss_fn(params, tokens, targets, cfg)
        ref_half = reference.loss(params, tokens, targets, _sizes(cfg))
        ref_pub = reference.loss(theirs, tokens, targets,
                                 _sizes(cfg, rope_pairing="interleaved"))
        wrong = reference.loss(params, tokens, targets,
                               _sizes(cfg, rope_pairing="interleaved"))
    np.testing.assert_allclose(mine, ref_half, rtol=2e-6)
    np.testing.assert_allclose(ref_pub, ref_half, rtol=2e-6)
    assert abs(float(wrong - ref_half)) > 2e-6 * float(ref_half)


# ------------------------------------------- precision: what a limit must see
def test_bf16_program_is_near_the_reference_and_a_coarser_one_is_not():
    """The family's comparison at tiny sizes: the bf16 program (float32 where
    the configuration says float32) is near the reference; the reference
    with float8 operands is further, so is one whose routed experts are left
    out; and the cell's control — the router's scores, the softmaxes and the
    loss in bf16 — chooses sets further from the float32 router's than the
    program's are (its gradient, everything else float32, is NEARER at these
    sizes: what refuses it is measured at the cell's, PERF.md §6)."""
    from benchmarks.families.nemotron_h import grad_error

    cfg = ds.deepseek_v2_tiny(seq_len=256)
    tokens, targets = _batch(cfg)
    params = _params(cfg, seed=1)
    (loss, grads), (ref_loss, ref_grads, reports) = _program_and_reference(
        cfg, params, tokens, targets)
    assert abs(float(loss) - float(ref_loss)) < 1e-3 * float(ref_loss)
    mine = grad_error(_norms(grads), _norms(ref_grads))["total"]
    assert mine < 2e-2
    margin = max(float(r["worst_margin"]) for r in reports)
    assert margin < 0.02

    def switched(**switches):
        with jax.default_matmul_precision("highest"):
            return jax.grad(lambda p: reference.loss(
                p, tokens, targets, _sizes(cfg, **switches)))(params)

    coarse = switched(operand_dtype=jnp.float8_e4m3fn)
    none = switched(drop_routed=True)
    assert grad_error(_norms(coarse), _norms(ref_grads))["total"] > 1.5 * mine
    assert grad_error(_norms(none), _norms(ref_grads))["total"] > 1.5 * mine
    with jax.default_matmul_precision("highest"):
        own = reference.loss_and_routing(
            params, tokens, targets, _sizes(cfg, stats_dtype=jnp.bfloat16))[1]
        told = reference.loss_and_routing(
            params, tokens, targets, _sizes(cfg), [r["own"] for r in own])[1]
    assert max(float(r["worst_margin"]) for r in told) > 1.5 * margin


def _rehearsal():
    from benchmarks.harness import spec

    cell, config, mix = spec.load_cell(CELL)
    with open(os.path.join(ROOT, "benchmarks", "testdata",
                           "rehearse-deepseek_v2.json")) as f:
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


# ------------------------------------- the configuration, the cell, the family
def _cell():
    from benchmarks.harness import spec

    cell, config, _ = spec.load_cell(CELL)
    return cell, config


def test_the_configuration_holds_every_published_width_and_states_its_cut():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the model catalog is not on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f]
    published = next(r for r in rows if r["name"] == "DeepSeek-V2-Lite")
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
            config["vocab_size"]) == (5, 16, 12800)
    assert len(config["reduced"]) == 3 and len(config["assumed"]) >= 5
    assert any("aux_loss_alpha 0.001" in a for a in config["assumed"])
    assert (cell["seq_len"], cell["per_chip_batch"], cell["remat"],
            cell["reference_rows"], cell["reference_grad"]) == (
        8192, 4, True, 4, True)


def test_the_cells_parameters_and_the_familys_arithmetic():
    """811,885,056 parameters, counted by the program from abstract shapes
    and by the family from the file; the two counts of a token's operations
    agree; the deployment's 3,072 tokens a held expert and layer."""
    cell, config = _cell()
    cfg = family.program_config(config, cell)
    shapes = family.shapes(config, cell)
    assert ds.param_count(cfg) == shapes["params"] == 811_885_056
    assert f"{shapes['params']:,}" in config["deployment"]
    assert cfg.pattern == "DEEEE" and cfg.held == moe.Held(0, 16)
    assert (cfg.qk_dim, cfg.v_head_dim, cfg.seq_len) == (192, 128, 8192)
    assert family.train_flops_per_token(shapes) == pytest.approx(
        ds.flops_per_token(cfg), rel=1e-12)
    tokens = cell["per_chip_batch"] * cell["seq_len"]
    assert tokens * cfg.top_k / cfg.n_experts == 3072
    assert moe.row_buffer(tokens, 64, 6, 16) == 61440     # 1.25 x 49,152
    call = family.flash_attn_call(shapes)
    rows = 4 * 16 * 8192
    assert call["flops"] == 5 * rows * 8192 * (4 * 192 + 3 * 128)
    assert call["bytes"] == 5 * (rows * 2 * (6 * 192 + 6 * 128) + 8 * rows)
    experts = family.experts_call(shapes)
    assert experts["flops"] == 9 * 4 * 2 * (tokens * 1.5) * 2048 * 1408


def test_the_family_refuses_a_program_without_the_model(monkeypatch):
    import importlib.util

    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name == "ray_tpu.models.deepseek_v2"
        else real(name, *a))
    with pytest.raises(SystemExit, match="cannot run a cell of family "
                                         "deepseek_v2"):
        family.shapes(*reversed(_cell()))


@pytest.mark.parametrize("axis", ["ep", "tp", "pp", "cp"])
def test_a_mesh_the_family_cannot_run_on_is_refused(axis):
    class Mesh:
        shape = {axis: 2}

    with pytest.raises(NotImplementedError, match=f"{axis} > 1"):
        ds.mesh_rules(ds.deepseek_v2_tiny(), Mesh())


def test_the_pattern_its_groups_and_what_the_rule_may_keep():
    cfg = ds.deepseek_v2_tiny(n_layer=5, attention_impl="pallas")
    assert cfg.pattern == "DEEEE"
    assert blocks.pattern_groups(cfg.pattern) == [("D", 1), ("E", 4)]
    assert ds.DeepseekV2Config(first_layer=3, n_layer=2).pattern == "EE"
    base, kinds = ds.kind_shards(cfg, 2, cfg.seq_len, None)
    assert set(kinds) == {"D", "E"}
    flat = [n for k in kinds.values() for c in k.candidates for n in c.names]
    assert len(flat) == len(set(flat))          # a name is one kind's
    assert set(flat) <= set(names.RESIDUALS)
    for name in (names.RES_MLA_C, names.RES_MLA_KPE, names.RES_FLASH_O,
                 names.RES_MOE_SCORES, names.RES_MOE_SHARED_GATE):
        assert name in flat
    # the latent and the one k_pe: 40 numbers a token here, priced at their
    # projection, against 4 x (24 + 16) of k and v
    latent = next(c for k in kinds.values() for c in k.candidates
                  if c.names == (names.RES_MLA_C, names.RES_MLA_KPE))
    tokens = 2 * cfg.seq_len
    applied = kinds["E"].applications                   # spread over them
    assert abs(latent.nbytes * applied - 5 * tokens * 40 * 2) < applied
    # every name the rule may keep is a name the traced step carries
    tokens_, targets = _batch(cfg)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda p: ds.loss_fn(p, tokens_, targets, cfg)))(_params(cfg)))
    for name in flat:
        assert f"name={name}" in jaxpr, name


def _cell_shards(rows=None, **overrides):
    """(the cell's program config on the flash pair, kind_shards' two
    results) at the cell's own shapes, or with ``rows`` rows and
    ``overrides`` of the config: arithmetic, nothing is traced."""
    cell, config = _cell()
    cfg = dataclasses.replace(family.program_config(config, cell),
                              attention_impl="pallas", **overrides)
    return (cfg,) + ds.kind_shards(cfg, rows or cell["per_chip_batch"],
                                   cfg.seq_len, None)


def _phase(cfg, base, kinds):
    return max(blocks.backward_phases(base, kinds,
                                      blocks.pattern_groups(cfg.pattern)),
               key=lambda p: p.nbytes)


def test_the_cells_decision_from_its_shapes_keeps_the_flash_outputs_first():
    """PR 56: `deepseek-v2-lite-l5.dataset`'s decision, from its shapes and a
    v5e's bytes_limit alone. An expert layer's block is the LARGEST moment of
    its backward — the routed passes', with what latent attention's backward
    waits to read — where the sum of all three stood at 6.18 GB: the phase
    `4 x scan(E)` is 5.0–5.4 GB (the compiled step takes 5.0 beside its
    resident bytes), so the rule has about a GB and spends it on the flash
    kernel's o and lse first, the router's scores, the latent and its k_pe —
    and on nothing of a GB a name."""
    cfg, base, kinds = _cell_shards()
    assert (base.batch, base.seq, base.head_rows, base.mlp_rows, base.flash
            ) == (4, 8192, 256, 512, True)
    phase = _phase(cfg, base, kinds)
    assert phase.name == "4 x scan(E)" and 5.0e9 <= phase.nbytes <= 5.4e9
    # attention's waiting operands, the half's stream and routing, the
    # passes' rows and weights, the carried cotangent: written out
    T, D, rows = 4 * 8192, 2048, 61440
    assert kinds["E"].block_bytes == (
        T * D * 2
        + 2 * (T * (2 * D + 2 * 16 * 192 + 2 * 16 * 128) + 13_762_560)
        + T * 16 * 4
        + T * D * 12 + T * 64 * 12
        + 2 * rows * (2 * D + 6 * 1408) + 6 * 3 * 16 * D * 1408)
    assert kinds["D"].block_bytes < kinds["E"].block_bytes
    params = jax.eval_shape(lambda: ds.init(cfg, jax.random.PRNGKey(0)))
    # (float32 parameters and gradients, bfloat16 moments: 12 B a parameter)
    resident = 3 * sum(x.size * x.dtype.itemsize
                       for x in jax.tree.leaves(params))
    policy = blocks.choose_remat_policy_kinds(
        tuple(kinds.values()), phase.nbytes, family.V5E_BYTES_LIMIT, resident)
    assert policy.saved[:2] == (names.RES_FLASH_O, names.RES_FLASH_LSE)
    assert {names.RES_MOE_SCORES, names.RES_MLA_C, names.RES_MLA_KPE} < set(
        policy.saved)
    assert not {names.RES_Q, names.RES_K, names.RES_V, names.RES_MID,
                names.RES_MOE_SHARED_GATE, names.RES_MOE_SHARED_UP} & set(
        policy.saved)
    assert 0.85e9 < policy.saved_bytes <= policy.budget_bytes < 1.2e9


@pytest.mark.parametrize("grown", [
    dict(rows=8), dict(seq_len=16384), dict(held_count=32),
    dict(d_expert=2816), dict(n_head=32), dict(held_count=1, shared="whole"),
], ids=lambda g: "-".join(g))
def test_a_blocks_estimate_grows_with_every_shape_it_is_made_from(
        grown, monkeypatch):
    """The estimate is arithmetic from the shapes, not a number fitted to the
    cell: more rows, a longer sequence, more held experts, wider experts or
    more heads each make the expert layer's block larger — and where the
    shared expert's moment is the larger of the half's two (one held expert),
    a shared expert taken whole makes it larger than one taken in chunks."""
    grown = dict(grown)
    whole = grown.pop("shared", None)
    with monkeypatch.context() as m:
        if whole:
            # one hidden tensor of the cell's shared expert is 184.5 MB:
            # under this bound the baseline takes it in chunks
            m.setattr(parts, "MLP_CHUNK_BYTES", 2 ** 26)
            assert parts.mlp_rows(4, 8192, 2048, 2 * 1408, 2) < 8192
        before = _cell_shards(**(grown if whole else {}))[2]["E"].block_bytes
    cfg, base, kinds = _cell_shards(**grown)
    assert kinds["E"].block_bytes > before
    assert _phase(cfg, base, kinds).name == "4 x scan(E)"


# --------------------------------------------------------- the benchmark's files
def _benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_names_the_new_cell_alone_and_imports_no_program(name):
    entry = next(m for m in _benchmark()["per_layer"] if m["name"] == name)
    # (PR 57's cell, the family's second caller, joined the lists of the
    # readers that read any family)
    assert entry["workloads"][0] == CELL
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
    assert [c["name"] for c in b["configs"]][6] == CONFIG
    assert b["workloads"][7] == {
        **b["workloads"][7], "name": CELL, "config": CONFIG,
        "traffic": "dataset", "chips": 1}
    assert len(b["configs"]) >= 7 and len(b["workloads"]) >= 8
    readers = [m["name"] for m in b["per_layer"]]
    first = readers.index(NEW_READERS[0])
    assert readers[first:first + len(NEW_READERS)] == list(NEW_READERS)
    for name in SHARED_READERS:
        entry = next(m for m in b["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"]
    # the rate and the set-up time, not the p90; the reader of ALL Mosaic
    # time is not this cell's flash time
    p90 = next(m for m in b["end_to_end"] if m["name"] == "step_ms_p90")
    assert CELL not in p90["workloads"]
    for name in ("flash_attn_ms_per_step", "flash_attn_roofline",
                 "mfu_device"):
        entry = next(m for m in b["per_layer"] if m["name"] == name)
        assert CELL not in entry["workloads"]
    for entry in b["configs"] + b["workloads"]:
        assert len(entry["why"]) <= 200


def test_the_new_family_files_import_no_program_at_module_level():
    for name in ("deepseek_v2", "deepseek_v2_reference"):
        path = os.path.join(ROOT, "benchmarks", "families", name + ".py")
        with open(path) as f:
            tree = ast.parse(f.read(), path)
        level = tree.body if name == "deepseek_v2" else list(ast.walk(tree))
        for node in level:
            if isinstance(node, ast.ImportFrom):
                assert (node.module or "").split(".")[0] != "ray_tpu"
            elif isinstance(node, ast.Import):
                assert all(a.name.split(".")[0] != "ray_tpu" for a in node.names)


_RECORDED = {
    "lfm2_moe": ("lfm2-24b-a2b-l5.dataset",
                 "lfm2-24b-a2b-l5.dataset.1step.scoped.program.json.gz"),
    "gpt2": ("gpt2-124m.dataset",
             "gpt2-124m.dataset.10steps.scoped.xplane.pb.gz"),
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


@pytest.mark.parametrize("family_name", sorted(_RECORDED))
@pytest.mark.parametrize("name", ("mla_latent_ms_per_step",
                                  "moe_aux_ms_per_step"))
def test_a_new_scopes_reader_reads_nothing_from_another_cells_trace(
        name, family_name):
    """A program without the scope — every trace recorded before PR 55, and
    the parent's — gives the reader nothing to read: None, no raise."""
    reader = importlib.import_module(f"benchmarks.layer_metrics.{name}")
    assert reader.read(_recorded_facts(family_name)) is None
