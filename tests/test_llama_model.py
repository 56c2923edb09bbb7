"""LLaMA-family model: shapes, learning, sharding, and HF numerics parity.

The HF-parity test is the anchor: our RoPE layout (rotate_half), GQA
repetition, RMSNorm, and SwiGLU must reproduce transformers'
LlamaForCausalLM logits on identical weights.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama


def test_forward_shapes_and_loss_decreases():
    cfg = llama.llama_tiny(dtype=jnp.float32)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (2, 64)).astype(np.int32)
    tgt = np.roll(toks, -1, 1).copy()
    tgt[:, -1] = -1

    logits = llama.forward(params, toks, cfg)
    assert logits.shape == (2, 64, cfg.padded_vocab)

    import optax

    opt = optax.adam(1e-3)
    state = opt.init(params)
    loss_g = jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn(p, toks, tgt, cfg)
    ))
    l0, g = loss_g(params)
    for _ in range(20):
        l, g = loss_g(params)
        upd, state = opt.update(g, state)
        params = optax.apply_updates(params, upd)
    assert float(l) < float(l0) * 0.9


def test_gqa_equals_mha_when_kv_heads_match():
    """n_kv_head == n_head must reduce to standard attention."""
    cfg_g = llama.llama_tiny(dtype=jnp.float32, n_kv_head=4)
    params = llama.init(cfg_g, jax.random.PRNGKey(1))
    toks = np.arange(32, dtype=np.int32)[None, :] % cfg_g.vocab_size
    out = llama.forward(params, toks, cfg_g)
    assert np.all(np.isfinite(np.asarray(out, np.float32)))


def test_tp_fsdp_mesh_matches_single_device(cpu_mesh8):
    """Sharded forward over a tp2/fsdp2 mesh == single-device logits."""
    from ray_tpu.parallel import mesh as mesh_lib
    from ray_tpu.parallel import sharding as sharding_lib

    cfg = llama.llama_tiny(dtype=jnp.float32)
    params = llama.init(cfg, jax.random.PRNGKey(2))
    toks = (np.arange(64, dtype=np.int32)[None, :] % cfg.vocab_size)
    ref = np.asarray(llama.forward(params, toks, cfg))

    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(tp=2, fsdp=2), cpu_mesh8[:4])
    shardings = sharding_lib.tree_shardings(mesh, llama.logical_axes(cfg))
    sharded = jax.tree.map(jax.device_put, params, shardings)
    out = jax.jit(lambda p, t: llama.forward(p, t, cfg))(sharded, toks)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)


def test_hf_numerics_parity():
    """Logits match transformers' LlamaForCausalLM on identical weights."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    cfg = llama.llama_tiny(dtype=jnp.float32)
    hf_cfg = transformers.LlamaConfig(
        vocab_size=cfg.vocab_size,
        hidden_size=cfg.d_model,
        intermediate_size=cfg.d_ff,
        num_hidden_layers=cfg.n_layer,
        num_attention_heads=cfg.n_head,
        num_key_value_heads=cfg.n_kv_head,
        max_position_embeddings=cfg.seq_len,
        rms_norm_eps=cfg.rms_eps,
        rope_theta=cfg.rope_theta,
        tie_word_embeddings=False,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf = transformers.LlamaForCausalLM(hf_cfg).eval()

    params = llama.params_from_hf(hf, cfg)
    rng = np.random.default_rng(3)
    toks = rng.integers(0, cfg.vocab_size, (2, 48)).astype(np.int32)

    with torch.no_grad():
        ref = hf(torch.from_numpy(toks.astype(np.int64))).logits.numpy()
    ours = np.asarray(
        llama.forward(params, toks, cfg)[:, :, : cfg.vocab_size], np.float32
    )
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_llama_train_step_on_mesh(cpu_mesh8):
    """Full sharded train step (train_step.make_train_step) on a
    dp2/tp2 mesh: loss finite, decreases, params stay sharded."""
    from ray_tpu.parallel import mesh as mesh_lib
    from ray_tpu.train.train_step import make_train_step

    cfg = llama.llama_tiny(dtype=jnp.float32)
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(dp=2, tp=2), cpu_mesh8[:4])
    bundle = make_train_step(llama, cfg, mesh=mesh,
                             rng=jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (4, 64)).astype(np.int32)
    tgt = np.roll(toks, -1, 1).copy()
    tgt[:, -1] = -1
    state = bundle.state
    losses = []
    for _ in range(8):
        state, m = bundle.step_fn(state, {"tokens": toks, "targets": tgt})
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    wq = state["params"]["blocks"]["wq"]
    assert "tp" in str(wq.sharding.spec), wq.sharding
