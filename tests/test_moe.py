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
