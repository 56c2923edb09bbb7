"""LLaMA-family model: shapes, learning, sharding, and HF numerics parity.

The HF-parity test is the anchor: our RoPE layout (rotate_half), GQA
repetition, RMSNorm, and SwiGLU must reproduce transformers'
LlamaForCausalLM logits on identical weights.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, parts


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


# --------------------------------------------------------------------------- #
# The head in chunks: a chunk's gradient is made where its loss is (PR 39)
# --------------------------------------------------------------------------- #

_B, _S, _D, _V, _ROWS = 2, 64, 32, 40, 16


def _whole_sequence_loss(x, targets, lm_head):
    """The plain formulation: all the logits at once, float32, log_softmax
    and a pick; the mean over the heads of each head's mean over its valid
    targets."""
    P = targets.shape[-1]
    logits = jnp.einsum("bsd,dv->bsv", x, lm_head, precision="highest")
    logp = jax.nn.log_softmax(logits.reshape(logits.shape[:2] + (P, -1)), -1)
    mask = targets >= 0
    nll = -jnp.take_along_axis(
        logp, jnp.where(mask, targets, 0)[..., None], axis=-1)[..., 0]
    return jnp.mean(jnp.sum(nll * mask, (0, 1))
                    / jnp.maximum(jnp.sum(mask, (0, 1)), 1))


def _head_case(heads, ignore):
    k = jax.random.split(jax.random.PRNGKey(heads), 3)
    x = jax.random.normal(k[0], (_B, _S, _D), jnp.float32)
    lm_head = 0.3 * jax.random.normal(k[1], (_D, heads * _V), jnp.float32)
    targets = np.array(jax.random.randint(k[2], (_B, _S), 0, _V))
    for rows, cols in ignore:
        targets[rows, cols] = -1
    return x, parts.head_targets(jnp.asarray(targets), heads), lm_head


@pytest.mark.parametrize("heads,ignore,cotangent", [
    (1, (), 1.0),
    (8, (), 1.0),
    # chunk 1 of 4 (positions 16–31) holds no target in any row
    (1, ((slice(None), slice(16, 32)),), 1.0),
    # row 0 ends at 40, inside chunk 2 (32–47); with eight heads the later
    # heads' targets end earlier still
    (8, ((0, slice(40, None)),), 1.0),
    # the MTP module's loss arrives times its weight
    (8, ((1, slice(50, None)),), 0.1),
], ids=["one-head", "eight-heads", "a-chunk-all-ignored",
        "a-row-ends-mid-chunk", "cotangent-0.1"])
def test_chunked_head_makes_the_whole_sequences_loss_and_gradients(
        heads, ignore, cotangent):
    """ops/cross_entropy.chunked_head_xent — each chunk's gradient made beside
    its loss, for a unit cotangent, the backward scaling both — against
    jax.value_and_grad of the plain whole-sequence formulation, float32."""
    from ray_tpu.ops.cross_entropy import chunked_head_xent

    x, targets, lm_head = _head_case(heads, ignore)
    loss, (d_x, d_head) = jax.value_and_grad(
        lambda x, w: cotangent * chunked_head_xent(x, targets, w, _ROWS),
        argnums=(0, 1))(x, lm_head)
    want, (want_x, want_head) = jax.value_and_grad(
        lambda x, w: cotangent * _whole_sequence_loss(x, targets, w),
        argnums=(0, 1))(x, lm_head)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    np.testing.assert_allclose(d_x, want_x, rtol=1e-4, atol=1e-8)
    np.testing.assert_allclose(d_head, want_head, rtol=1e-4, atol=1e-8)
    # the undifferentiated call is the same number
    np.testing.assert_allclose(
        cotangent * chunked_head_xent(x, targets, lm_head, _ROWS), loss,
        rtol=1e-6)


def test_chunked_head_multiplies_a_chunks_logits_once(monkeypatch):
    """Through parts.lm_head_loss: called without differentiation the
    chunked head's program holds ONE matmul a chunk (the primal does no
    gradient work); differentiated, three — logits, d x, d lm_head — in the
    one forward scan, no `checkpoint` and no second scan behind it. The
    trace says which it was (`model/head_loss`)."""
    from ray_tpu.ops import cross_entropy
    from ray_tpu.tracing import names

    monkeypatch.setattr(cross_entropy, "_decisions", {})
    monkeypatch.setattr(parts, "HEAD_CHUNK_BYTES", _B * _ROWS * _V * 4)
    cfg = llama.llama_tiny(dtype=jnp.float32, vocab_size=_V)
    x, targets, lm_head = _head_case(1, ())
    assert parts.head_rows(_B, _S, _V, 1) == _ROWS

    def head(x, w):
        return parts.lm_head_loss(x, targets[..., 0], w, cfg.dtype,
                                  cfg.n_pred_heads)

    primal = str(jax.make_jaxpr(head)(x, lm_head))
    grad = str(jax.make_jaxpr(jax.grad(head, argnums=(0, 1)))(x, lm_head))
    assert (primal.count("dot_general"), primal.count("scan[")) == (1, 1)
    assert (grad.count("dot_general"), grad.count("scan[")) == (3, 1)
    assert "checkpoint" not in grad and "remat" not in grad
    by_grad = {d["grad_in_forward"]: d
               for d in cross_entropy.head_loss_decisions()}
    assert set(by_grad) == {False, True}
    assert tuple(by_grad[True]) == names.HEAD_LOSS_ARGS
    assert by_grad[False]["residual_bytes"] == 0
    assert by_grad[True] == dict(
        batch=_B, rows=_ROWS, chunks=_S // _ROWS, columns=_V, heads=1,
        grad_in_forward=True,
        residual_bytes=x.size * 4 + lm_head.size * 4)
    # one head whose whole-sequence logits fit takes them whole
    monkeypatch.setattr(parts, "HEAD_CHUNK_BYTES", _B * _S * _V * 4)
    assert parts.head_rows(_B, _S, _V, 1) == 0
    assert "scan[" not in str(jax.make_jaxpr(      # (a new function: no
        lambda x, w: head(x, w))(x, lm_head))      # trace of `head` is reused)


@pytest.mark.parametrize("n_kv_head", [4, 2], ids=["mha", "gqa"])
def test_narrow_heads_go_s_minor_through_rope_and_either_attention(
        n_kv_head, monkeypatch):
    """PR 48: a head narrower than a lane tile (llama_tiny's) is projected
    [H, B, hd, S] (`parts.head_layout`), rotated along dim -2 and attended
    to in that order — by the S-minor flash kernels (interpreted here) with
    no transpose at their edge, or by XLA's einsums. Loss and gradients
    equal the hd-minor block's, which a width of 128 still takes."""
    cfg = llama.llama_tiny(dtype=jnp.float32, n_kv_head=n_kv_head)
    assert parts.head_layout(cfg.head_dim) == "hbds"
    assert parts.head_layout(128) == "bhsd"
    params = llama.init(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(3)
    toks = rng.integers(0, cfg.vocab_size, (2, cfg.seq_len)).astype(np.int32)
    tgt = np.roll(toks, -1, 1)

    def run(impl):
        c = llama.llama_tiny(dtype=jnp.float32, n_kv_head=n_kv_head,
                             attention_impl=impl)
        fn = jax.value_and_grad(lambda p: llama.loss_fn(p, toks, tgt, c))
        jaxpr = str(jax.make_jaxpr(fn)(params))
        return fn(params), jaxpr

    (loss_x, grads_x), _ = run("xla")
    (loss_p, grads_p), jaxpr = run("pallas")
    rows = 2 * cfg.n_head
    assert f"f32[{rows},{cfg.head_dim},{cfg.seq_len}]" in jaxpr  # the kernels'
    monkeypatch.setattr(parts, "head_layout", lambda hd: "bhsd")
    (loss_h, grads_h), _ = run("xla")
    for loss, grads in ((loss_p, grads_p), (loss_h, grads_h)):
        np.testing.assert_allclose(loss, loss_x, rtol=1e-5)
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_x)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-5)


def _toy_swiglu(seed=0, B=2, S=16, D=8, F=24):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(k[0], (B, S, D), jnp.float32)
    ws = tuple(jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[0])
               for key, shape in zip(k[1:], ((D, F), (D, F), (F, D))))
    return x, ws


def test_the_shared_swiglu_is_its_three_products_written_out():
    h, (w_gate, w_up, w_down) = _toy_swiglu()
    want = (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down
    got = parts.swiglu(h, w_gate, w_up, w_down)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the accumulator is float32 whatever the operands are
    half = parts.swiglu(*(a.astype(jnp.bfloat16)
                          for a in (h, w_gate, w_up, w_down)))
    assert half.dtype == jnp.float32
    np.testing.assert_allclose(half, want, rtol=0.1, atol=0.1)


@pytest.mark.parametrize("rows", [16, 8, 4, 1])
def test_a_half_in_row_chunks_is_the_half_in_value_and_gradient(rows):
    x, ws = _toy_swiglu(seed=1)

    def half(x, ws):        # works each row alone: a norm, the MLP, a residual
        return parts.residual_add(
            x, parts.swiglu(parts.rmsnorm(x, jnp.ones(x.shape[-1]), 1e-6), *ws))

    def chunked(x, ws):
        return parts.in_row_chunks(lambda c: half(c, ws), x, rows)

    np.testing.assert_allclose(chunked(x, ws), half(x, ws), rtol=1e-6,
                               atol=1e-6)
    want = jax.grad(lambda x, ws: jnp.sum(jnp.sin(half(x, ws))),
                    argnums=(0, 1))(x, ws)
    got = jax.grad(lambda x, ws: jnp.sum(jnp.sin(chunked(x, ws))),
                   argnums=(0, 1))(x, ws)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
    # all the rows at once is the function itself: no loop, no checkpoint
    loops = str(jax.make_jaxpr(chunked)(x, ws)).count("scan")
    assert loops == (0 if rows == 16 else 1)
