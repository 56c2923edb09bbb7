"""Mixture-of-experts layer + expert parallelism over the ep mesh axis.

Parity: SURVEY §2.10 expert parallelism (new TPU-native work, GShard-style
einsum dispatch — the reference has no TPU MoE).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


def test_moe_top1_matches_dense_expert_reference():
    """With top_k=1 and unconstrained capacity, each token's output must
    equal its routed expert's MLP applied to it (numpy reference)."""
    from ray_tpu.ops.moe import moe_init, moe_mlp

    rng = jax.random.PRNGKey(0)
    B, S, D, F, E = 2, 8, 16, 32, 4
    params = jax.tree_util.tree_map(
        lambda p: p[0],  # layer 0
        moe_init(rng, 1, D, F, E, param_dtype=jnp.float32),
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D), jnp.float32)

    y, aux = moe_mlp(x, params, top_k=1, capacity_factor=float(E),
                     dtype=jnp.float32)
    assert float(aux) > 0

    xt = np.asarray(x).reshape(-1, D)
    logits = xt @ np.asarray(params["router_w"])
    choice = logits.argmax(-1)
    ref = np.zeros_like(xt)
    for t in range(xt.shape[0]):
        e = choice[t]
        h = xt[t] @ np.asarray(params["fc_w"])[e] + np.asarray(params["fc_b"])[e]
        h = 0.5 * h * (1 + np.tanh(np.sqrt(2 / np.pi) * (h + 0.044715 * h**3)))
        ref[t] = h @ np.asarray(params["out_w"])[e] + np.asarray(params["out_b"])[e]
    np.testing.assert_allclose(
        np.asarray(y).reshape(-1, D), ref, rtol=2e-4, atol=2e-4
    )


def test_moe_capacity_drops_overflow_tokens():
    from ray_tpu.ops.moe import moe_mlp, moe_init

    B, S, D, F, E = 1, 8, 8, 16, 2
    params = jax.tree_util.tree_map(
        lambda p: p[0], moe_init(jax.random.PRNGKey(0), 1, D, F, E,
                                 param_dtype=jnp.float32)
    )
    # force every token to expert 0: positive inputs + an all-positive
    # expert-0 router column (logit_0 = 10*sum(x) > 0 = logit_1)
    params = dict(params)
    params["router_w"] = jnp.zeros((D, 2)).at[:, 0].set(10.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (B, S, D),
                                  jnp.float32)) + 0.1
    y, _ = moe_mlp(x, params, top_k=1, capacity_factor=0.5, dtype=jnp.float32)
    # capacity = ceil(8/2*0.5) = 2 slots on expert 0: later tokens dropped
    out = np.asarray(y)[0]
    nonzero = (np.abs(out) > 1e-8).any(axis=-1)
    assert nonzero[:2].all() and not nonzero[2:].any()


def test_moe_gpt2_trains_and_grads_flow():
    from ray_tpu.models import gpt2

    cfg = gpt2.gpt2_tiny(moe_experts=4, moe_top_k=2)
    params = gpt2.init(cfg, jax.random.PRNGKey(0))
    assert "moe" in params["blocks"]
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 512)
    tgt = jax.random.randint(jax.random.PRNGKey(2), (2, 128), 0, 512)
    loss, grads = jax.value_and_grad(
        lambda p: gpt2.loss_fn(p, tok, tgt, cfg)
    )(params)
    assert np.isfinite(float(loss))
    g = grads["blocks"]["moe"]["fc_w"]
    assert float(jnp.abs(g).sum()) > 0, "expert grads must flow"
    g_router = grads["blocks"]["moe"]["router_w"]
    assert float(jnp.abs(g_router).sum()) > 0, "router grads must flow"


def test_moe_expert_parallel_over_ep_mesh():
    """pjit the MoE train step over an ep=2 mesh: expert params shard on ep
    and a step executes (XLA inserts the dispatch all-to-all)."""
    from ray_tpu.models import gpt2
    from ray_tpu.parallel import mesh as mesh_lib
    from ray_tpu.train.train_step import (
        default_optimizer,
        make_gpt2_train_step,
        synthetic_batch,
    )

    if len(jax.devices()) < 4:
        pytest.skip("needs the 8-device CPU mesh (conftest sets XLA flags)")
    spec = mesh_lib.MeshSpec(dp=2, ep=2, tp=2)
    mesh = mesh_lib.make_mesh(spec, jax.devices()[:8])
    cfg = gpt2.gpt2_tiny(moe_experts=4, moe_top_k=2)
    bundle = make_gpt2_train_step(
        cfg, mesh=mesh, optimizer=default_optimizer(total_steps=10),
        rng=jax.random.PRNGKey(0),
    )
    fcw = bundle.state["params"]["blocks"]["moe"]["fc_w"]
    assert "ep" in str(fcw.sharding), f"experts not ep-sharded: {fcw.sharding}"
    batch = synthetic_batch(cfg, global_batch=4, seed=1)
    state, metrics = bundle.step_fn(bundle.state, batch)
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("n, operands, ops", [
    (2, 0, 1),              # one compare-exchange
    (64, 2, 3360),          # a row's 64 scores with an index: 21 stages of 32
    (512, 2, 57600),
    (8 * 4096 * 16, 2, 249036800),      # the pairs' keys with their gates
])
def test_sort_ops_counts_a_bitonic_networks_compare_exchanges(n, operands,
                                                              ops):
    from ray_tpu.ops import moe

    assert moe.sort_ops(n, operands) == ops
    stages = int(np.log2(n)) * (int(np.log2(n)) + 1) // 2
    assert ops == n // 2 * stages * (1 + 2 * operands)


# --------------------------------- the grouped products' own kernels (PR 60)
def _dense_grouped(x, w, sizes):
    """x·W by group, one dense product an expert (float32), zeros past the
    last group."""
    out = np.zeros((x.shape[0], w.shape[2]), np.float32)
    lo = 0
    for e, n in enumerate(sizes):
        out[lo:lo + n] = np.asarray(x[lo:lo + n], np.float32) @ np.asarray(
            w[e], np.float32)
        lo += n
    return out


_GROUPED_ROWS = 1024         # four row tiles of 256, the rule's


@pytest.mark.parametrize("sizes", [
    [0, 400, 0, 200],        # empty groups, the first among them
    [40, 600, 10, 200],      # groups smaller than a row tile
    [260, 260, 260, 120],    # every group straddles a tile's edge
    [128, 128, 128, 128],    # rows past the last group: half the buffer
    [0, 1024, 0, 0],         # all rows in one group
    [256, 256, 256, 256],    # whole tiles
    [0, 0, 0, 0],            # no pair at all
], ids=["empty", "small", "straddle", "past-the-last", "one-group",
        "whole-tiles", "no-pairs"])
@pytest.mark.parametrize("K, N, impl", [
    (128, 384, "pallas"),    # an odd multiple of 128
    (256, 192, "compiler"),  # no multiple of 128: the compiler's kernel
], ids=["3x128", "192"])
def test_grouped_dot_and_its_gradients_match_a_dense_product_an_expert(
        sizes, K, N, impl):
    """`ops/grouped_matmul.grouped_dot` — the program's Pallas kernels,
    interpreted here, where the rule chose them — against one dense product
    an expert and against `lax.ragged_dot`: the product, the gradient to the
    input and the gradient to the weights, over group sizes that leave
    groups empty, put several in one row tile, straddle tiles, leave rows
    past the last group and put every row in one group."""
    from jax import lax

    from ray_tpu.ops import grouped_matmul as gm

    rows, held = _GROUPED_ROWS, len(sizes)
    kx, kw, kd = jax.random.split(jax.random.PRNGKey(3), 3)
    valid = (np.arange(rows) < sum(sizes))[:, None]
    x = jnp.where(valid, jax.random.normal(kx, (rows, K), jnp.float32), 0)
    d = jnp.where(valid, jax.random.normal(kd, (rows, N), jnp.float32), 0)
    w = jax.random.normal(kw, (held, K, N), jnp.float32)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    assert {tuple(gm.grouped_tiling(form, rows, held, K, N, 4))[:2]
            for form in gm.FORMS} == {(impl, 256 if impl == "pallas" else 0)}
    with jax.default_matmul_precision("highest"):
        o, vjp = jax.vjp(lambda x, w: gm.grouped_dot(x, w, group_sizes), x, w)
        d_x, d_w = vjp(d)
        o_c, vjp_c = jax.vjp(lambda x, w: lax.ragged_dot(x, w, group_sizes),
                             x, w)
        d_x_c, d_w_c = vjp_c(d)
    want = _dense_grouped(x, w, sizes)
    np.testing.assert_allclose(np.where(valid, o, 0), want, rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(
        np.where(valid, d_x, 0),
        _dense_grouped(d, np.swapaxes(w, 1, 2), sizes), rtol=1e-5, atol=1e-4)
    lo, want_w = 0, np.zeros(w.shape, np.float32)
    for e, n in enumerate(sizes):
        want_w[e] = np.asarray(x[lo:lo + n]).T @ np.asarray(d[lo:lo + n])
        lo += n
    np.testing.assert_allclose(d_w, want_w, rtol=1e-5, atol=1e-4)
    for ours, theirs in ((np.where(valid, o, 0), np.where(valid, o_c, 0)),
                         (np.where(valid, d_x, 0), np.where(valid, d_x_c, 0)),
                         (d_w, d_w_c)):
        np.testing.assert_allclose(ours, theirs, rtol=1e-5, atol=1e-4)


def test_grouped_dot_rounds_a_float32_sum_once_in_bfloat16():
    """bf16 operands: the kernels sum in float32 and round the output once,
    as `preferred_element_type=x.dtype` makes the compiler's — the two agree
    to one unit in bf16's last place, in all three forms."""
    from jax import lax

    from ray_tpu.ops import grouped_matmul as gm

    rows, held, K, N = 512, 4, 256, 128
    sizes = jnp.asarray([100, 156, 0, 200], jnp.int32)
    kx, kw, kd = jax.random.split(jax.random.PRNGKey(4), 3)
    valid = (jnp.arange(rows) < 456)[:, None]
    x = jnp.where(valid, jax.random.normal(kx, (rows, K), jnp.bfloat16), 0)
    d = jnp.where(valid, jax.random.normal(kd, (rows, N), jnp.bfloat16), 0)
    w = jax.random.normal(kw, (held, K, N), jnp.bfloat16)
    o, vjp = jax.vjp(lambda x, w: gm.grouped_dot(x, w, sizes), x, w)
    o_c, vjp_c = jax.vjp(lambda x, w: lax.ragged_dot(
        x, w, sizes, preferred_element_type=x.dtype), x, w)
    assert o.dtype == jnp.bfloat16
    for ours, theirs in zip((o,) + vjp(d), (o_c,) + vjp_c(d)):
        assert ours.dtype == theirs.dtype == jnp.bfloat16
        if ours.shape[0] == rows:       # a row past the last group is no one's
            ours, theirs = (jnp.where(valid, a, 0) for a in (ours, theirs))
        ours, theirs = (np.asarray(a, np.float32) for a in (ours, theirs))
        assert np.max(np.abs(ours - theirs)) <= 2 ** -7 * np.max(np.abs(theirs))


@pytest.mark.parametrize("cell, rows, held, K, N", [
    ("deepseek-v2-lite-l5", 61440, 16, 2048, 1408),
    ("xing4.0-29b-a4b-l5", 5120, 8, 3584, 1024),
    ("lfm2-24b-a2b-l5", 40960, 16, 2048, 1536),
    ("nemotron-3-super-120b-l11", 14336, 8, 1024, 2688),
])
def test_grouped_tiling_at_the_four_expert_cells_shapes(cell, rows, held, K,
                                                        N):
    """The rule's choices where they matter — the four expert cells' row
    buffers and experts, bf16, both of an expert's matrix shapes: the
    program's kernel in all three forms, 256 rows a visit (what the chip
    measured best, PERF.md §6, PR 60), a VMEM estimate past Mosaic's default
    and within what the call may state; and what it leaves to the compiler:
    a width that is not whole lane tiles, rows that are no whole tile."""
    from ray_tpu.ops import grouped_matmul as gm
    from ray_tpu.ops.attention import VMEM_BUDGET_BYTES, VMEM_CEILING_BYTES
    from ray_tpu.ops.moe import row_buffer

    assert rows == {"deepseek-v2-lite-l5": row_buffer(32768, 64, 6, 16),
                    "xing4.0-29b-a4b-l5": row_buffer(8192, 64, 4, 8),
                    "lfm2-24b-a2b-l5": row_buffer(32768, 64, 4, 16),
                    "nemotron-3-super-120b-l11": row_buffer(32768, 512, 22, 8)
                    }[cell]
    for k, n in ((K, N), (N, K)):
        for form in gm.FORMS:
            t = gm.grouped_tiling(form, rows, held, k, n, 2)
            assert (t.impl, t.row_tile) == (gm.PALLAS, 256), (form, t)
            assert VMEM_BUDGET_BYTES < t.vmem_estimate
            assert (t.vmem_estimate + t.vmem_estimate // 2
                    <= VMEM_CEILING_BYTES), (form, t)
            assert t in [gm.GroupedTiling(**{f: d[f] for f in t._fields})
                         for d in gm.grouped_tiling_decisions()
                         if (d["form"], d["rows"], d["K"], d["N"]) == (
                             form, rows, k, n)]
    assert gm.grouped_tiling("gmm", rows, held, K, N + 64, 2).impl == gm.COMPILER
    assert gm.grouped_tiling("tgmm", rows + 8, held, K, N, 2).impl == gm.COMPILER
    assert gm.grouped_tiling("gmm_t", rows + 128, held, K, N, 2).row_tile == 128
    # operands of two dtypes; a step laid out on four devices
    assert gm.grouped_tiling("gmm", rows, held, K, N, 0).impl == gm.COMPILER
    assert gm.grouped_tiling("gmm", rows, held, K, N, 2, 4).impl == gm.COMPILER
