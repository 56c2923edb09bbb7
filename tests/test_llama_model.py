"""LLaMA-family model: shapes, learning, sharding, and HF numerics parity.

The HF-parity test is the anchor: our RoPE layout (rotate_half), GQA
repetition, RMSNorm, and SwiGLU must reproduce transformers'
LlamaForCausalLM logits on identical weights.
"""

import itertools

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
    # the most a chunk's logits take — while that leaves it tokens enough
    monkeypatch.setattr(parts, "HEAD_CHUNK_BYTES", _B * _ROWS * _V * 4)
    monkeypatch.setattr(parts, "HEAD_CHUNK_TOKENS", _B * _ROWS)
    cfg = llama.llama_tiny(dtype=jnp.float32, vocab_size=_V)
    x, targets, lm_head = _head_case(1, ())
    assert parts.head_rows(_B, _S, _V, 1) == _ROWS
    # a wider head's chunk keeps its tokens and takes more bytes; a narrower
    # one's grows to the bytes
    assert parts.head_rows(_B, _S, 4 * _V, 1) == _ROWS
    assert parts.head_rows(_B, _S, _V // 2, 2) == 2 * _ROWS

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
    assert by_grad[False]["carry_bytes_a_step"] == 0
    # every chunk reads and writes the float32 d lm_head whole
    assert by_grad[True] == dict(
        batch=_B, rows=_ROWS, chunks=_S // _ROWS, columns=_V, heads=1,
        grad_in_forward=True,
        residual_bytes=x.size * 4 + lm_head.size * 4,
        carry_bytes_a_step=(_S // _ROWS) * 2 * _D * _V * 4)
    # one head whose whole-sequence logits fit takes them whole, however
    # small the carry; more heads than one still go in chunks (of the
    # whole sequence, here)
    monkeypatch.setattr(parts, "HEAD_CHUNK_BYTES", _B * _S * _V * 4)
    assert parts.head_rows(_B, _S, _V, 1) == 0
    assert parts.head_rows(_B, _S, _V // 2, 2) == _S
    assert "scan[" not in str(jax.make_jaxpr(      # (a new function: no
        lambda x, w: head(x, w))(x, lm_head))      # trace of `head` is reused)


# the cells that take parts.lm_head_loss, as benchmarks/configs and
# benchmarks/cells state them: rows of the batch (a looped model's head call
# takes every pass's), sequence, the heads' columns together, heads, passes;
# then the tokens a chunk holds and the chunks under the flat 64 MiB alone
# (PR 64) and with the least tokens a chunk (PR 65)
_CELL_HEADS = {
    "ouro-2.6b-l8": (1, 8192, 49152, 1, 4, (256, 128), (1024, 32)),
    "qwen3-next-80b-a3b-l4": (4, 8192, 18992, 1, 1, (512, 64), (1024, 32)),
    "nemotron-3-super-120b-l11": (8, 4096, 16384, 1, 1, (1024, 32), (1024, 32)),
    "xing4.0-29b-a4b-l5": (1, 8192, 16384, 1, 1, (1024, 8), (1024, 8)),
    "deepseek-v2-lite-l5": (4, 8192, 12800, 1, 1, (1024, 32), (1024, 32)),
    "minicpm-sala-9b-l4": (1, 16384, 9216, 1, 1, (1024, 16), (1024, 16)),
    "lfm2-24b-a2b-l5": (8, 4096, 8192, 1, 1, (2048, 16), (2048, 16)),
    "evabyte-6.5b-l4": (1, 32768, 8 * 320, 8, 1, (4096, 8), (4096, 8)),
}


@pytest.mark.parametrize("cell", list(_CELL_HEADS))
def test_a_chunk_of_the_head_holds_tokens_enough_to_hide_its_carry(
        cell, monkeypatch):
    """PR 65: a chunk's float32 logits stay under HEAD_CHUNK_BYTES (the
    chip's fast memory holds them) unless that leaves it fewer than
    HEAD_CHUNK_TOKENS tokens (its d lm_head product then waits for the
    float32 carry every chunk reads and writes): one function of the
    shapes, the same answer from parts.head_rows, from lm_head_loss's
    weighted branch and from llama's block shard (whose looped model's batch
    is the passes' together)."""
    from ray_tpu.ops import cross_entropy

    B, S, V, heads, passes, before, after = _CELL_HEADS[cell]
    flat = parts.rows_under(S, passes * B * V * 4, parts.HEAD_CHUNK_BYTES)
    assert (passes * B * flat, S // flat) == before
    rows = parts.head_chunk_rows(passes * B, S, V)
    assert (passes * B * rows, S // rows) == after
    assert rows >= flat and passes * B * rows >= parts.HEAD_CHUNK_TOKENS
    # a cell whose 64 MiB held tokens enough keeps its chunk
    if before[0] >= parts.HEAD_CHUNK_TOKENS:
        assert after == before
    # no cell takes its sequence whole, before or after
    assert flat < S or heads > 1
    if passes == 1:
        assert parts.head_rows(B, S, V, heads) == rows
    # what the step itself traces: lm_head_loss hands the op these rows
    seen = []
    monkeypatch.setattr(cross_entropy, "chunked_head_xent",
                        lambda x, t, w, rows, *a: seen.append(rows))
    D = 256
    x = jax.ShapeDtypeStruct((passes * B, S, D), jnp.bfloat16)
    targets = jax.ShapeDtypeStruct((passes * B, S), jnp.int32)
    head = jax.ShapeDtypeStruct((D, V), jnp.float32)
    if passes > 1:
        weights = jax.ShapeDtypeStruct((passes * B, S), jnp.float32)
        jax.eval_shape(lambda x, t, w, p: parts.lm_head_loss(
            x, t, w, jnp.bfloat16, weights=p), x, targets, head, weights)
    else:
        jax.eval_shape(lambda x, t, w: parts.lm_head_loss(
            x, t, w, jnp.bfloat16, heads), x, targets, head)
    assert seen == [rows]
    # and what the remat rule prices: the llama family's shard
    if cell.startswith(("ouro", "evabyte")):
        cfg = llama.LlamaConfig(
            vocab_size=V // heads, seq_len=S, n_layer=2, n_head=2,
            n_kv_head=2, d_model=D, d_ff=2 * D, n_pred_heads=heads,
            ut_steps=passes, exit_gate=passes > 1)
        assert llama._head_rows(cfg, B, S) == rows


@pytest.mark.parametrize("heads", [1, 8])
def test_the_rule_moves_no_threshold_and_shortens_no_chunk(heads):
    """Whether ONE head takes its sequence whole is decided against
    HEAD_CHUNK_BYTES as it was; where the head goes in chunks, a chunk is
    never shorter than the flat limit gave, at any shape, and longer only
    where that held fewer than HEAD_CHUNK_TOKENS tokens."""
    least = parts.HEAD_CHUNK_TOKENS
    for B, S, V in itertools.product(
            (1, 4, 8, 32), (1024, 4096, 32768, 3 * 1024),
            (320, 8192, 16384, 50304, 151936)):
        flat = parts.rows_under(S, B * V * 4, parts.HEAD_CHUNK_BYTES)
        rows = parts.head_rows(B, S, V, heads)
        if heads == 1 and flat == S:
            assert rows == 0, (B, S, V)
            continue
        assert rows >= flat and S % rows == 0, (B, S, V)
        assert rows == parts.head_chunk_rows(B, S, V)
        if B * flat >= least or flat == S:
            assert rows == flat, (B, S, V)
        else:
            # the largest power-of-two fraction with no more tokens than
            # the least (the whole sequence where even that has fewer)
            assert B * rows <= least or rows % 2, (B, S, V)
            assert rows == S or 2 * B * rows > least, (B, S, V)


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["unweighted", "weighted"])
@pytest.mark.parametrize("longer", [4, 16])
def test_a_longer_chunk_is_the_same_sums(longer, weighted):
    """PR 65 changes how many addends a chunk's partial sums hold and
    nothing else: the loss, d x, d lm_head and the weights' cotangent of a
    chunk 4 and 16 times as long are the short chunk's to float32
    rounding."""
    from ray_tpu.ops.cross_entropy import chunked_head_xent

    short = _S // 16
    x, targets, lm_head = _head_case(1, ((0, slice(40, None)),))
    weights = None
    if weighted:
        weights = jax.random.uniform(jax.random.PRNGKey(5), targets.shape,
                                     jnp.float32)

    def run(rows):
        if not weighted:
            return jax.value_and_grad(
                lambda x, w: chunked_head_xent(x, targets, w, rows),
                argnums=(0, 1))(x, lm_head)
        return jax.value_and_grad(
            lambda x, w, p: chunked_head_xent(x, targets, w, rows, p),
            argnums=(0, 1, 2))(x, lm_head, weights)

    want, want_g = run(short)
    got, got_g = run(longer * short)
    assert len(got_g) == (3 if weighted else 2)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for g, w in zip(got_g, want_g):
        assert g.dtype == w.dtype == jnp.float32
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-7)


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
