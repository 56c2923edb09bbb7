"""GPT-2 model + sharded train step on an 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt2, llama
from ray_tpu.parallel import mesh as mesh_lib
from ray_tpu.parallel import sharding as sharding_lib
from ray_tpu.train.train_step import (
    _opt_state_shardings,
    default_optimizer,
    make_gpt2_train_step,
    make_train_step,
    synthetic_batch,
)


def test_param_count_124m():
    cfg = gpt2.gpt2_124m()
    n = gpt2.param_count(cfg)
    # 124.4M with the standard vocab; padding to 50304 adds ~36k rows
    assert 123e6 < n < 126e6, n


def test_forward_shapes_and_finite():
    cfg = gpt2.gpt2_tiny()
    params = gpt2.init(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, cfg.seq_len), jnp.int32)
    logits = gpt2.forward(params, tokens, cfg)
    assert logits.shape == (2, cfg.seq_len, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


def test_causality():
    """Changing a future token must not affect earlier logits."""
    cfg = gpt2.gpt2_tiny(dtype=jnp.float32)
    params = gpt2.init(cfg, jax.random.PRNGKey(1))
    t1 = jnp.zeros((1, cfg.seq_len), jnp.int32)
    t2 = t1.at[0, -1].set(7)  # change only the last token
    l1 = gpt2.forward(params, t1, cfg)
    l2 = gpt2.forward(params, t2, cfg)
    np.testing.assert_allclose(l1[0, :-1], l2[0, :-1], atol=1e-5)
    assert not np.allclose(l1[0, -1], l2[0, -1])


def test_chunked_loss_matches_monolithic():
    """The blockwise cross-entropy (loss_chunk) must equal the full-logits
    path: same loss value (both f32 softmax), gradients to within one bf16
    ulp (the fused monolithic path — ops/cross_entropy.py — recomputes the
    backward softmax from the saved logsumexp rather than a saved log-prob
    residual, so bf16-cast grads can differ in the last place)."""
    cfg_m = gpt2.gpt2_tiny(loss_chunk=0, seq_len=256)
    cfg_c = gpt2.gpt2_tiny(loss_chunk=64, seq_len=256)
    params = gpt2.init(cfg_m, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg_m.vocab_size, (2, 256)).astype(np.int32)
    tgt = np.roll(toks, -1, 1).copy()
    tgt[:, -1] = -1
    tgt[0, 5:9] = -1  # masked rows exercised
    l1, g1 = jax.value_and_grad(gpt2.loss_fn)(params, toks, tgt, cfg_m)
    l2, g2 = jax.value_and_grad(gpt2.loss_fn)(params, toks, tgt, cfg_c)
    assert float(abs(l1 - l2)) < 1e-5
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=1e-3
        )


def test_loss_decreases_single_device():
    cfg = gpt2.gpt2_tiny()
    bundle = make_gpt2_train_step(
        cfg,
        optimizer=default_optimizer(lr=1e-3, warmup=1, total_steps=50),
        rng=jax.random.PRNGKey(0),
    )
    batch = synthetic_batch(cfg, global_batch=4, seed=0)
    state = bundle.state
    losses = []
    for _ in range(8):
        state, metrics = bundle.step_fn(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses


@pytest.mark.parametrize(
    "spec",
    [
        mesh_lib.MeshSpec(dp=8),
        mesh_lib.MeshSpec(fsdp=8),
        mesh_lib.MeshSpec(dp=2, fsdp=2, tp=2),
        mesh_lib.MeshSpec(fsdp=4, tp=2),
    ],
    ids=["dp8", "fsdp8", "dp2fsdp2tp2", "fsdp4tp2"],
)
def test_sharded_train_step_matches_meshes(spec, cpu_mesh8):
    """The same train step must run and give a finite loss under any mesh."""
    cfg = gpt2.gpt2_tiny()
    mesh = mesh_lib.make_mesh(spec, cpu_mesh8)
    bundle = make_gpt2_train_step(cfg, mesh=mesh, rng=jax.random.PRNGKey(0))
    batch = synthetic_batch(cfg, global_batch=8)
    state, metrics = bundle.step_fn(bundle.state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(jax.device_get(state["step"])) == 1


def test_dp_vs_single_device_loss_match(cpu_mesh8):
    """Data-parallel mesh must compute the same loss as one device (SPMD is a
    pure layout change)."""
    cfg = gpt2.gpt2_tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    batch = synthetic_batch(cfg, global_batch=8)

    b1 = make_gpt2_train_step(cfg, rng=jax.random.PRNGKey(3))
    _, m1 = b1.step_fn(b1.state, batch)

    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(dp=8), cpu_mesh8)
    b8 = make_gpt2_train_step(cfg, mesh=mesh, rng=jax.random.PRNGKey(3))
    _, m8 = b8.step_fn(b8.state, batch)

    np.testing.assert_allclose(
        float(m1["loss"]), float(m8["loss"]), rtol=2e-5
    )


def test_pallas_under_sharded_jit_matches_xla(cpu_mesh8):
    """attention_impl="pallas" in the model on a multi-device mesh: the flash
    kernels run under shard_map on each device's [B/fsdp, H/tp, S, hd] shard
    (interpreted here) and the step agrees with the XLA einsum attention."""
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(fsdp=2, tp=2), cpu_mesh8[:4])
    batch = synthetic_batch(gpt2.gpt2_tiny(), global_batch=4)
    got = {}
    for impl in ("pallas", "xla"):
        cfg = gpt2.gpt2_tiny(
            attention_impl=impl, dtype=jnp.float32, param_dtype=jnp.float32
        )
        b = make_gpt2_train_step(cfg, mesh=mesh, rng=jax.random.PRNGKey(5))
        _, m = b.step_fn(b.state, batch)
        got[impl] = (float(m["loss"]), float(m["grad_norm"]))
    np.testing.assert_allclose(got["pallas"], got["xla"], rtol=1e-4)


def test_step_fn_compiles_once():
    """The state a bundle is born with is placed like the state step_fn
    returns, so the second call does not recompile the step."""
    cfg = gpt2.gpt2_tiny()
    b = make_gpt2_train_step(cfg, rng=jax.random.PRNGKey(0))
    batch = jax.device_put(synthetic_batch(cfg, global_batch=2), b.data_sharding)
    state, _ = b.step_fn(b.state, batch)
    state, _ = b.step_fn(state, batch)
    assert b.step_fn._cache_size() == 1


def test_opt_state_shardings_tell_same_shaped_parameters_apart(cpu_mesh8):
    """Adam's moments take their own parameter's sharding, not that of the
    first parameter with the same shape (abstract parameters, as the
    benchmark's rehearsal compile passes them)."""
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(fsdp=4, tp=2), cpu_mesh8)
    axes = {"up": ("embed", "mlp"), "down": ("mlp", "embed")}
    param_sh = sharding_lib.tree_shardings(mesh, axes)
    assert param_sh["up"] != param_sh["down"]
    params = {k: jax.ShapeDtypeStruct((64, 64), jnp.float32) for k in axes}
    clip, adam, decay, schedule = _opt_state_shardings(
        default_optimizer(), params, param_sh, mesh)
    assert adam.mu == param_sh and adam.nu == param_sh
    assert adam.count == schedule.count == mesh_lib.replicated(mesh)
    assert not jax.tree.leaves((clip, decay))


@pytest.mark.parametrize("model, cfg, spec", [
    (gpt2, gpt2.gpt2_tiny(), mesh_lib.MeshSpec(dp=2, fsdp=2, tp=2)),
    (llama, llama.llama_tiny(), mesh_lib.MeshSpec(dp=2, tp=2)),
], ids=["gpt2", "llama"])
def test_moments_are_placed_as_their_parameters(model, cfg, spec, cpu_mesh8):
    mesh = mesh_lib.make_mesh(spec, cpu_mesh8[:spec.num_devices])
    state = make_train_step(model, cfg, mesh=mesh).state
    adam = state["opt_state"][1]
    placed = lambda tree: jax.tree.map(lambda x: x.sharding, tree)
    assert placed(adam.mu) == placed(adam.nu) == placed(state["params"])
    assert len({str(s.spec) for s in jax.tree.leaves(placed(adam.mu))}) > 1
