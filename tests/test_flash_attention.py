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


# The two kernel pairs (ops/attention.kernel_layout): which one a head width
# takes is the rule's, so a test that wants the OTHER pair at that width —
# the hd-minor pair at hd 64 and 32 (the ring's chunk kernels run it there),
# the S-minor pair at 128 — steers the rule, in the test.
PAIRS = [attention.HD_MINOR, attention.S_MINOR]


@pytest.fixture(params=PAIRS)
def pair(request, monkeypatch):
    monkeypatch.setattr(attention, "kernel_layout",
                        lambda hd, hd_v=None: request.param)
    return request.param


def flash(q, k, v, layout="bshd", **kw):
    """flash_attention on [B, S, H, hd] arrays handed over in ``layout``."""
    to = tuple("bshd".index(c) for c in layout)
    back = tuple(layout.index(c) for c in "bshd")
    o = flash_attention(*(jnp.transpose(x, to) for x in (q, k, v)),
                        layout=layout, **kw)
    return jnp.transpose(o, back)


@pytest.mark.parametrize("hd", [64, 32])
@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal, hd, pair):
    q, k, v = make_qkv(jax.random.PRNGKey(0), hd=hd)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = ref_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_forward_nondivisible_block_fallback(pair):
    # S=160 not divisible by 64 → _pick_block halves until it divides
    q, k, v = make_qkv(jax.random.PRNGKey(1), S=160)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = ref_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("hd", [64, 32])
@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_reference(causal, hd, pair):
    q, k, v = make_qkv(jax.random.PRNGKey(2), B=1, S=128, H=2, hd=hd)

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


@pytest.mark.parametrize("layout", attention.LAYOUTS)
@pytest.mark.parametrize("B,H", [(2, 4), (1, 5)])
def test_merged_rows_match_reference(B, H, layout, pair):
    """Batch and head are merged into the one dim of rows the grid walks:
    any head count, several blocks a row, either of the two leading — every
    layout a caller may hand over, to either pair (its own order as it is,
    any other transposed at the edge) — must match the numerics of the
    reference, fwd and grad."""
    q, k, v = make_qkv(jax.random.PRNGKey(7), B=B, H=H)

    def loss(q, k, v):
        o = flash(q, k, v, layout, causal=True, block_q=64, block_k=64)
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


@pytest.mark.parametrize("layout", PAIRS)
@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
@pytest.mark.parametrize("case", sorted(TILING_SHAPES))
def test_choose_tiling_fits_the_shapes_it_is_given(case, kernel, layout):
    Sq, Skv, hd = TILING_SHAPES[case]
    t = attention.choose_tiling(kernel, Sq, Skv, hd, 2, layout=layout)
    assert Sq % t.block_q == 0 and Skv % t.block_k == 0
    assert 0 < t.vmem_estimate <= attention.VMEM_BUDGET_BYTES
    assert t.vmem_estimate == attention.vmem_estimate(
        kernel, t.block_q, t.block_k, Sq, Skv, hd, 2, layout)
    # the target tile wherever it divides the sequence and fits; a smaller
    # one only where the estimate says the target does not fit
    want = (attention._pick_block(Sq, 512), attention._pick_block(Skv, 512))
    if (t.block_q, t.block_k) != want:
        assert attention.vmem_estimate(
            kernel, *want, Sq, Skv, hd, 2, layout) > attention.VMEM_BUDGET_BYTES

    # explicit keywords override the rule, each on its own
    e = attention.choose_tiling(kernel, Sq, Skv, hd, 2, block_q=32,
                                block_k=16, layout=layout)
    assert (e.block_q, e.block_k) == (32, 16)
    e = attention.choose_tiling(kernel, Sq, Skv, hd, 2, block_q=32,
                                layout=layout)
    assert e.block_q == 32 and Skv % e.block_k == 0
    e = attention.choose_tiling(kernel, Sq, Skv, hd, 2, block_k=32,
                                layout=layout)
    assert e.block_k == 32 and Sq % e.block_q == 0


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
@pytest.mark.parametrize("case", sorted(TILING_SHAPES))
def test_s_minor_estimate_counts_the_blocks_it_is_given_unpadded(case, kernel):
    """An S-minor block is [hd, tile]: the sequence fills the lanes, so at
    these shapes (hd a multiple of the 16 bf16 sublanes, tiles of whole lane
    tiles or the whole row) the estimate is the blocks' own bytes, counted
    here by hand — where the hd-minor estimate pays a whole lane tile for
    half a head."""
    Sq, Skv, hd = TILING_SHAPES[case]
    t = attention.choose_tiling(kernel, Sq, Skv, hd, 2,
                                layout=attention.S_MINOR)
    bq, bk = t.block_q, t.block_k
    lanes = lambda n: -(-n // 128) * 128
    stat = lambda n: 8 * lanes(n) * 4            # a [1, n] f32 row: 8 sublanes
    if kernel == "fwd":
        io = (2 * hd * lanes(bq) + 2 * hd * lanes(Skv)) * 2 + stat(bq)
        live = -(-bk // 8) * 8 * lanes(bq) * 4 + hd * lanes(bq) * 4
    else:       # q, o, do, dq rows; k, v, dk, dv blocks; lse
        io = (4 * hd * lanes(Sq) + 4 * hd * lanes(bk)) * 2 + stat(Sq)
        live = (hd * lanes(Sq) * 4 + -(-bk // 8) * 8 * lanes(bq) * 4
                + 2 * hd * lanes(bk) * 4)
    assert t.vmem_estimate == 2 * io + live
    if hd < 128 and min(Sq, Skv) >= 128:
        assert t.vmem_estimate < attention.vmem_estimate(
            kernel, bq, bk, Sq, Skv, hd, 2, attention.HD_MINOR)


def test_choose_tiling_shrinks_the_tile_before_it_gives_up():
    """The backward's whole-row blocks at S = 8,192, hd = 64 leave no room
    for a 512 × 512 f32 tile: the rule halves the kv tile, then the q tile.
    That is the hd-minor pair's [8192, 64] rows, each padded to 128 lanes;
    the S-minor rows are [64, 8192], half of that, and keep the target."""
    t = attention.choose_tiling("bwd", 8192, 8192, 64, 2)
    assert (t.block_q, t.block_k) < (512, 512)
    assert t.block_q >= 128 and t.block_k >= 128
    t = attention.choose_tiling("bwd", 8192, 8192, 64, 2,
                                layout=attention.S_MINOR)
    assert (t.block_q, t.block_k) == (512, 512)


# (kernel, Sq = Skv, hd, hd_v, layout) whose whole-row blocks alone pass the
# default budget — refused until PR 55 (ROADMAP D16) —: the tiles are back at
# their target and the CALL asks Mosaic for its estimate and half again
PAST_THE_DEFAULT_BUDGET = [
    ("bwd", 8192, 128, 128, attention.HD_MINOR),   # the Nemotron cell's width
    ("bwd", 16384, 64, 64, attention.HD_MINOR),
    ("fwd", 32768, 128, 128, attention.HD_MINOR),
    ("bwd", 32768, 64, 64, attention.S_MINOR),
    ("bwd", 8192, 192, 128, attention.S_MINOR),    # latent attention, the
    ("bwd", 16384, 192, 128, attention.S_MINOR),   # DeepSeek-V2 cell's rows
]
# ... and what D16 still refuses: an estimate whose half again passes
# VMEM_CEILING_BYTES
PAST_THE_CEILING = [
    ("bwd", 32768, 128, 128, attention.HD_MINOR),
    ("bwd", 32768, 192, 128, attention.S_MINOR),
    ("bwd", 65536, 64, 64, attention.S_MINOR),
    ("fwd", 131072, 128, 128, attention.HD_MINOR),
    ("fwd", 131072, 192, 128, attention.S_MINOR),
]


@pytest.mark.parametrize("kernel,S,hd,hd_v,layout", PAST_THE_DEFAULT_BUDGET)
def test_choose_tiling_asks_for_its_estimate_past_the_default_budget(
        kernel, S, hd, hd_v, layout):
    t = attention.choose_tiling(kernel, S, S, hd, 2, layout=layout, hd_v=hd_v)
    assert (t.block_q, t.block_k) == (512, 512)
    assert attention.VMEM_BUDGET_BYTES < t.vmem_estimate
    asked = attention._compiler_params(t).vmem_limit_bytes
    assert asked == t.vmem_estimate + t.vmem_estimate // 2 \
        <= attention.VMEM_CEILING_BYTES
    # no tiling of the halving walk fits the default budget: what was refused
    smallest = attention.vmem_estimate(kernel, 128, 128, S, S, hd, 2, layout,
                                       hd_v)
    assert smallest > attention.VMEM_BUDGET_BYTES
    # a shape inside the default budget asks for nothing
    inside = attention.choose_tiling(kernel, 1024, 1024, hd, 2, layout=layout,
                                     hd_v=hd_v)
    assert attention._compiler_params(inside) is None


def test_the_cells_rows_at_192_and_128():
    """The DeepSeek-V2 cell's kernels (S-minor, q·k 192, v 128): the forward
    of an 8,192-token row fits the default budget at the target tiles, its
    backward asks for 29.4 MiB and half again; rows of 4,096 fit the default
    budget, the backward with a halved kv tile."""
    got = {(k, S): attention.choose_tiling(k, S, S, 192, 2,
                                           layout=attention.S_MINOR, hd_v=128)
           for k in ("fwd", "bwd") for S in (4096, 8192)}
    assert [(t.block_q, t.block_k) for t in got.values()] == [
        (512, 512), (512, 512), (512, 256), (512, 512)]
    MiB = 2 ** 20
    assert got["fwd", 8192].vmem_estimate == 12484608 < 16 * MiB
    assert got["bwd", 8192].vmem_estimate == 30801920
    assert got["bwd", 4096].vmem_estimate <= 16 * MiB
    # equal widths: what the rule always said
    assert attention.vmem_estimate("bwd", 512, 512, 4096, 4096, 64, 2,
                                   attention.S_MINOR, 64) \
        == attention.vmem_estimate("bwd", 512, 512, 4096, 4096, 64, 2,
                                   attention.S_MINOR)


@pytest.mark.parametrize("kernel,S,hd,hd_v,layout", PAST_THE_CEILING)
def test_choose_tiling_raises_when_nothing_fits(kernel, S, hd, hd_v, layout):
    with pytest.raises(ValueError) as err:
        attention.choose_tiling(kernel, S, S, hd, 2, layout=layout, hd_v=hd_v)
    msg = str(err.value)
    assert f"Sq={S}" in msg and f"Skv={S}" in msg and f"hd={hd}" in msg
    assert f"hd_v={hd_v}" in msg and layout in msg
    assert "estimated at" in msg and str(attention.VMEM_CEILING_BYTES) in msg
    # a caller who fixes both tiles is not second-guessed: Mosaic is the judge
    assert attention.choose_tiling(kernel, S, S, hd, 2, block_q=128,
                                   block_k=128, layout=layout, hd_v=hd_v
                                   ).vmem_estimate \
        > attention.VMEM_CEILING_BYTES * 2 // 3


@pytest.mark.parametrize("hd", [64, 128])
def test_tiling_decision_is_recorded_once_per_distinct_choice(hd):
    """... and says which pair the kernel belongs to: the S-minor one at 64,
    the hd-minor one at 128 (kernel_layout, from the width alone)."""
    from ray_tpu.tracing import get_buffer, names

    buf = get_buffer()
    buf.drain(10 ** 6)
    attention._decisions.clear()
    q, k, v = make_qkv(jax.random.PRNGKey(5), B=2, S=256, H=12, hd=hd)
    layout = attention.kernel_layout(hd)
    assert layout == {64: attention.S_MINOR, 128: attention.HD_MINOR}[hd]

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True))

    for _ in range(2):          # traced twice, recorded once
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    component, name = names.FLASH_TILING.split("/")
    events = [e for e in buf.drain(10 ** 6)[0]
              if e["name"] == name and e["component"] == component]
    assert [e["args"]["kernel"] for e in events] == ["fwd", "bwd"]
    chosen = attention.choose_tiling("fwd", 256, 256, hd, 4, layout=layout)
    assert events[0]["args"] == {
        "kernel": "fwd", "rows": 24, "Sq": 256, "Skv": 256, "hd": hd,
        **chosen._asdict(), "layout": layout, "hd_v": hd,
        # (no window: one 256-token tile, the triangle's one pair; PR 66)
        "window": 0, "tiles_visited": 1, "tiles_causal": 1}
    assert all(e["args"]["layout"] == layout for e in events)
    assert tuple(events[0]["args"]) == names.FLASH_TILING_ARGS
    assert [e["args"] for e in events] == attention.flash_tiling_decisions()


@pytest.mark.parametrize("heads,S,hd", [(25, 128, 32), (12, 128, 32),
                                        (2, 1024, 64), (1, 2048, 64)])
def test_chosen_tiling_matches_reference(heads, S, hd, pair):
    """flash_attention with NO explicit tile — the rule's choice, at either
    head count and at the 512-wide tile the chip runs (masked and unmasked
    blocks, a skipped one; four tiles a side at 2,048) — against the XLA
    reference, values and gradients, for either pair."""
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
