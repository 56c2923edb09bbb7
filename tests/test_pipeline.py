"""Pipeline parallelism (parallel/pipeline.py).

The reference has no PP at all (SURVEY §2.10 "absent — must be built new"),
so there is no behavior to mirror; these tests pin the contract instead:
a pp>1 mesh computes THE SAME function as pp=1 — same loss, same grads —
with the layer stack sharded over pp and a GPipe microbatch schedule.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt2, llama
from ray_tpu.parallel import mesh as mesh_lib
from ray_tpu.parallel.pipeline import pipeline_apply, stages_from_layers
from ray_tpu.train.train_step import (
    make_gpt2_train_step, make_train_step, synthetic_batch,
)


def test_pipeline_apply_matches_sequential(cpu_mesh8):
    """pipeline_apply == applying the stages one after another."""
    P_, L, D = 4, 8, 16
    rng = np.random.default_rng(0)
    layers = jnp.asarray(rng.normal(size=(L, D, D)) * 0.3, jnp.float32)
    x = jnp.asarray(rng.normal(size=(8, D)), jnp.float32)

    def stage_fn(ws, h):
        def body(h, w):
            return jnp.tanh(h @ w), None

        h, _ = jax.lax.scan(body, h, ws)
        return h

    # sequential reference: all L layers in order
    expect = stage_fn(layers, x)

    spec = mesh_lib.MeshSpec(pp=P_, dp=2)
    mesh = mesh_lib.make_mesh(spec, cpu_mesh8)
    got = jax.jit(
        lambda ws, x: pipeline_apply(
            stage_fn, stages_from_layers(ws, P_), x,
            num_stages=P_, num_microbatches=4, mesh=mesh,
        )
    )(layers, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), rtol=1e-5, atol=1e-6)


def test_pipeline_apply_grads_match(cpu_mesh8):
    P_, L, D = 2, 4, 8
    rng = np.random.default_rng(1)
    layers = jnp.asarray(rng.normal(size=(L, D, D)) * 0.3, jnp.float32)
    x = jnp.asarray(rng.normal(size=(4, D)), jnp.float32)

    def stage_fn(ws, h):
        def body(h, w):
            return jnp.tanh(h @ w), None

        h, _ = jax.lax.scan(body, h, ws)
        return h

    def loss_seq(ws):
        return jnp.sum(stage_fn(ws, x) ** 2)

    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(pp=P_), cpu_mesh8[:P_])

    def loss_pp(ws):
        y = pipeline_apply(
            stage_fn, stages_from_layers(ws, P_), x,
            num_stages=P_, num_microbatches=2, mesh=mesh,
        )
        return jnp.sum(y ** 2)

    g_seq = jax.grad(loss_seq)(layers)
    g_pp = jax.jit(jax.grad(loss_pp))(layers)
    np.testing.assert_allclose(np.asarray(g_pp), np.asarray(g_seq), rtol=1e-4, atol=1e-6)


@pytest.fixture
def f32_cfg():
    # f32 end to end for a tight pp-vs-no-pp comparison
    return gpt2.gpt2_tiny(dtype=jnp.float32, pipeline_microbatches=4)


def _loss_and_gnorm(cfg, mesh, batch):
    bundle = make_gpt2_train_step(cfg, mesh=mesh, rng=jax.random.PRNGKey(0))
    _, m = bundle.step_fn(bundle.state, batch)
    return float(m["loss"]), float(m["grad_norm"]), bundle


def test_gpt2_pp2_matches_pp1(cpu_mesh8, f32_cfg):
    """Full train step on a dp2/pp2 mesh == single-device step: same loss &
    grad norm on identical data (same init seed), layer stack pp-sharded."""
    batch = synthetic_batch(f32_cfg, global_batch=8)

    mesh1 = mesh_lib.single_device_mesh(cpu_mesh8[0])
    loss1, g1, _ = _loss_and_gnorm(f32_cfg, mesh1, batch)

    mesh2 = mesh_lib.make_mesh(mesh_lib.MeshSpec(dp=2, pp=2), cpu_mesh8[:4])
    loss2, g2, bundle = _loss_and_gnorm(f32_cfg, mesh2, batch)

    assert np.isfinite(loss2)
    np.testing.assert_allclose(loss2, loss1, rtol=1e-5)
    np.testing.assert_allclose(g2, g1, rtol=1e-4)
    # the stacked layer dim must actually be sharded over pp
    qkv = bundle.state["params"]["blocks"]["qkv_w"]
    assert "pp" in str(qkv.sharding.spec), qkv.sharding


def test_gpt2_pp_with_tp(cpu_mesh8):
    """pp composes with tp on the same mesh (GSPMD handles tp inside stages)."""
    cfg = gpt2.gpt2_tiny(dtype=jnp.float32, pipeline_microbatches=2)
    batch = synthetic_batch(cfg, global_batch=4)

    mesh1 = mesh_lib.single_device_mesh(cpu_mesh8[0])
    loss1, _, _ = _loss_and_gnorm(cfg, mesh1, batch)

    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(pp=2, tp=2, dp=2), cpu_mesh8)
    loss, _, _ = _loss_and_gnorm(cfg, mesh, batch)
    np.testing.assert_allclose(loss, loss1, rtol=1e-5)


def test_pipeline_microbatch_validation(cpu_mesh8):
    cfg = gpt2.gpt2_tiny(dtype=jnp.float32, pipeline_microbatches=3)
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(pp=2), cpu_mesh8[:2])
    bundle = make_gpt2_train_step(cfg, mesh=mesh, rng=jax.random.PRNGKey(0))
    batch = synthetic_batch(cfg, global_batch=4)  # 4 % 3 != 0
    with pytest.raises(ValueError, match="not divisible"):
        bundle.step_fn(bundle.state, batch)


@pytest.mark.parametrize("model, cfg", [
    (gpt2, gpt2.gpt2_tiny(dtype=jnp.float32, moe_experts=4, moe_top_k=2)),
    (llama, llama.llama_tiny(dtype=jnp.float32)),
], ids=["gpt2-moe", "llama"])
def test_pipeline_moe_unsupported(cpu_mesh8, model, cfg):
    """What a model cannot pipeline it refuses when the step is built."""
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(pp=2), cpu_mesh8[:2])
    with pytest.raises(NotImplementedError, match="pipeline"):
        make_train_step(model, cfg, mesh=mesh, rng=jax.random.PRNGKey(0))
