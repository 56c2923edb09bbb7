"""Flash-attention kernel numerics vs the XLA reference path (CPU interpret).

Reference for *behavior* is plain softmax attention; the reference repo has no
flash/SP implementation at all (SURVEY.md §2.10), so these are fresh numerics.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention
from ray_tpu.ops.attention import (
    flash_attention,
    flash_attention_with_lse,
)


def ref_attention(q, k, v, causal=True):
    S, Skv = q.shape[1], k.shape[1]
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, Skv), dtype=bool))
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))


def make_qkv(key, B=2, S=256, H=4, hd=64, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, S, H, hd), dtype)
    k = jax.random.normal(k2, (B, S, H, hd), dtype)
    v = jax.random.normal(k3, (B, S, H, hd), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal):
    q, k, v = make_qkv(jax.random.PRNGKey(0))
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = ref_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_forward_nondivisible_block_fallback():
    # S=160 not divisible by 64 → _pick_block halves until it divides
    q, k, v = make_qkv(jax.random.PRNGKey(1), S=160)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = ref_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_reference(causal):
    q, k, v = make_qkv(jax.random.PRNGKey(2), B=1, S=128, H=2, hd=32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
        return jnp.sum(jnp.sin(o.astype(jnp.float32)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(ref_attention(q, k, v, causal=causal)))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4,
            err_msg=f"d{name} mismatch",
        )


def test_lse_and_offsets():
    """Global offsets: computing attention of a q chunk against a kv chunk at
    a rotated position must equal the corresponding slice of full attention."""
    B, S, H, hd = 1, 128, 2, 32
    q, k, v = make_qkv(jax.random.PRNGKey(3), B=B, S=S, H=H, hd=hd)
    half = S // 2

    # full causal attention, second half of queries
    ref = ref_attention(q, k, v, causal=True)[:, half:]

    # ring-style: q2 against kv chunk 0 (fully visible) and kv chunk 1 (causal)
    q2 = q[:, half:]
    o_a, lse_a = flash_attention_with_lse(
        q2, k[:, :half], v[:, :half], half, 0, block_q=32, block_k=32
    )
    o_b, lse_b = flash_attention_with_lse(
        q2, k[:, half:], v[:, half:], half, half, block_q=32, block_k=32
    )
    # merge partials by lse
    m = jnp.maximum(lse_a, lse_b)
    wa = jnp.exp(lse_a - m)[..., None]   # [B,H,Sq,1]
    wb = jnp.exp(lse_b - m)[..., None]
    oa = jnp.moveaxis(o_a.astype(jnp.float32), 1, 2)  # [B,H,S,hd]
    ob = jnp.moveaxis(o_b.astype(jnp.float32), 1, 2)
    merged = (oa * wa + ob * wb) / (wa + wb)
    merged = jnp.moveaxis(merged, 2, 1)
    np.testing.assert_allclose(
        np.asarray(merged), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_fully_masked_chunk_is_zero_weight():
    """A kv chunk entirely in the future must come back with lse ≈ -inf and
    contribute nothing after the merge."""
    B, S, H, hd = 1, 64, 1, 32
    q, k, v = make_qkv(jax.random.PRNGKey(4), B=B, S=S, H=H, hd=hd)
    # kv offset far beyond all queries
    o, lse = flash_attention_with_lse(
        q, k, v, 0, 10_000, block_q=32, block_k=32
    )
    assert np.all(np.asarray(lse) < -1e29)
    np.testing.assert_array_equal(np.asarray(o), 0.0)


@pytest.mark.parametrize("B,H", [(2, 4), (1, 5)])
def test_merged_rows_match_reference(B, H):
    """Batch and head are merged into the one dim of rows the grid walks:
    any head count, several blocks a row, must match the numerics of the
    reference, fwd and grad."""
    q, k, v = make_qkv(jax.random.PRNGKey(7), B=B, H=H)

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
        return jnp.sum(o.astype(jnp.float32) ** 2), o

    (l, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    ref = ref_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    def loss_ref(q, k, v):
        return jnp.sum(ref_attention(q, k, v, causal=True) ** 2)

    ref_grads = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for g, rg in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(rg),
                                   atol=5e-4, rtol=5e-4)


# --------------------------------------------------------------------------- #
# The tiling rule (ops/attention.choose_tiling)
# --------------------------------------------------------------------------- #

# (Sq, Skv, hd) as one device's kernel sees them
TILING_SHAPES = {
    "gpt2-124m": (1024, 1024, 64),
    "gpt2-xl-shard": (1024, 1024, 64),
    "llama-2k": (2048, 2048, 128),
    "llama-4k": (4096, 4096, 128),
    "ring-chunk": (512, 1024, 64),
    "ring-chunk-hd128": (2048, 4096, 128),
    "long-8k-hd64": (8192, 8192, 64),
    "short-256": (256, 256, 64),
    "odd-seq": (160, 160, 64),
    "tiny-hd32": (64, 64, 32),
    "tiny-hd64": (64, 64, 64),
}


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
@pytest.mark.parametrize("case", sorted(TILING_SHAPES))
def test_choose_tiling_fits_the_shapes_it_is_given(case, kernel):
    Sq, Skv, hd = TILING_SHAPES[case]
    t = attention.choose_tiling(kernel, Sq, Skv, hd, 2)
    assert Sq % t.block_q == 0 and Skv % t.block_k == 0
    assert 0 < t.vmem_estimate <= attention.VMEM_BUDGET_BYTES
    assert t.vmem_estimate == attention.vmem_estimate(
        kernel, t.block_q, t.block_k, Sq, Skv, hd, 2)
    # the target tile wherever it divides the sequence and fits; a smaller
    # one only where the estimate says the target does not fit
    want = (attention._pick_block(Sq, 512), attention._pick_block(Skv, 512))
    if (t.block_q, t.block_k) != want:
        assert attention.vmem_estimate(
            kernel, *want, Sq, Skv, hd, 2) > attention.VMEM_BUDGET_BYTES

    # explicit keywords override the rule, each on its own
    e = attention.choose_tiling(kernel, Sq, Skv, hd, 2, block_q=32, block_k=16)
    assert (e.block_q, e.block_k) == (32, 16)
    e = attention.choose_tiling(kernel, Sq, Skv, hd, 2, block_q=32)
    assert e.block_q == 32 and Skv % e.block_k == 0
    e = attention.choose_tiling(kernel, Sq, Skv, hd, 2, block_k=32)
    assert e.block_k == 32 and Sq % e.block_q == 0


def test_choose_tiling_shrinks_the_tile_before_it_gives_up():
    """The backward's whole-row blocks at S = 8,192, hd = 64 leave no room
    for a 512 × 512 f32 tile: the rule halves the kv tile, then the q tile."""
    t = attention.choose_tiling("bwd", 8192, 8192, 64, 2)
    assert (t.block_q, t.block_k) < (512, 512)
    assert t.block_q >= 128 and t.block_k >= 128


@pytest.mark.parametrize("kernel,S,hd", [
    ("bwd", 8192, 128),       # whole-row q/do/dq blocks alone are over
    ("bwd", 16384, 64),
    ("fwd", 65536, 128),      # whole-row k/v
])
def test_choose_tiling_raises_when_nothing_fits(kernel, S, hd):
    with pytest.raises(ValueError) as err:
        attention.choose_tiling(kernel, S, S, hd, 2)
    msg = str(err.value)
    assert f"Sq={S}" in msg and f"Skv={S}" in msg and f"hd={hd}" in msg
    assert "estimated at" in msg and str(attention.VMEM_BUDGET_BYTES) in msg
    # a caller who fixes both tiles is not second-guessed: Mosaic is the judge
    assert attention.choose_tiling(kernel, S, S, hd, 2, block_q=128,
                                   block_k=128).vmem_estimate \
        > attention.VMEM_BUDGET_BYTES


def test_tiling_decision_is_recorded_once_per_distinct_choice():
    from ray_tpu.tracing import get_buffer, names

    buf = get_buffer()
    buf.drain(10 ** 6)
    attention._decisions.clear()
    q, k, v = make_qkv(jax.random.PRNGKey(5), B=2, S=256, H=12, hd=64)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True))

    for _ in range(2):          # traced twice, recorded once
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    component, name = names.FLASH_TILING.split("/")
    events = [e for e in buf.drain(10 ** 6)[0]
              if e["name"] == name and e["component"] == component]
    assert [e["args"]["kernel"] for e in events] == ["fwd", "bwd"]
    chosen = attention.choose_tiling("fwd", 256, 256, 64, 4)
    assert events[0]["args"] == {
        "kernel": "fwd", "rows": 24, "Sq": 256, "Skv": 256, "hd": 64,
        **chosen._asdict()}
    assert tuple(events[0]["args"]) == names.FLASH_TILING_ARGS
    assert [e["args"] for e in events] == attention.flash_tiling_decisions()


@pytest.mark.parametrize("heads,S,hd", [(25, 128, 32), (12, 128, 32),
                                        (2, 1024, 64)])
def test_chosen_tiling_matches_reference(heads, S, hd):
    """flash_attention with NO explicit tile — the rule's choice, at either
    head count and at the 512-wide tile the chip runs (masked and unmasked
    blocks, a skipped one) — against the XLA reference, values and
    gradients."""
    B = 2 if S < 1024 else 1
    q, k, v = make_qkv(jax.random.PRNGKey(11), B=B, S=S, H=heads, hd=hd)

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True)
        return jnp.sum(jnp.sin(o.astype(jnp.float32))), o

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref_attention(q, k, v, causal=True)),
        atol=2e-5, rtol=2e-5)

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(ref_attention(q, k, v, causal=True)))

    ref_grads = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for g, rg, name in zip(grads, ref_grads, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(rg),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")
