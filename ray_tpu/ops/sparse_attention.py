"""Block-sparse causal attention with a learned-free, per-query choice of key
blocks (MiniCPM4 / InfLLM-v2's ``sparse_config``): every query token is GIVEN
``top_k`` blocks of ``block`` keys — the first ``init_blocks``, the blocks of
its last ``window`` tokens, and the highest-scoring others — and attends over
them, causally, with the softmax of ordinary attention. The choice is shared
by the ``g`` query heads of one key-value head.

Two parts, two scopes (tracing/names.py):

- ``sparse_select`` (XLA, under SPARSE_SELECT): compressed keys
  ``K^c_j = mean(k[stride·j : stride·j + kernel])``; for token t and each head
  ``p_t = softmax_j(q_t·K^c_j / √hd)`` over the compressed keys whose every
  token is ≤ t; summed over the group's heads; a block's score is the max over
  the compressed keys that overlap it; ``lax.top_k``. Scores are float32 at
  the highest matmul precision, so that two blocks change places by rounding
  and not by bf16's; a run of query rows at a time, so that no
  [heads, S, S / stride] tensor exists. It carries no gradient.
- the attention over the chosen blocks and its backward, three Pallas kernels
  (``sparse_attn_fwd``, ``sparse_attn_bwd_dq``, ``sparse_attn_bwd_dkv``). A
  tile is ``block_q`` tokens × the group's ``g`` heads — ``g · block_q`` rows
  of one product, against ONE tile of the one key-value head's keys — and who
  was given what comes as a 0/1 table [blocks, S] a (row, key-value head),
  spread over the tile by a product with a 0/1 matrix (the MXU's, as
  ops/mamba2.py spreads its columns). The grid walks (query tile, key tile)
  pairs, the running softmax in VMEM scratch: VMEM holds tiles, never a row,
  so the row length is not bounded by it (the flash kernels of
  ops/attention.py hold whole rows and stay as they are: ROADMAP D16). A
  scalar-prefetch table says how many (token, block) choices fall in each
  pair of tiles: a pair nobody chose is skipped, and so is a pair in the
  future. With 64 of at most 256 blocks given, as at 16,384-token rows, most
  pairs hold a choice and the walk is dense: ≤ 2× the given keys' operations,
  at g · block_q rows a product where a gather of each token's own blocks
  has g = 16 — and dk, dv need no scatter. Logits are held transposed
  (keys down the sublanes), as the flash kernels hold theirs.

A chip that holds one key-value head with its group computes exactly that
head's part; the out-projection is linear, so the shares add up
(tests/test_minicpm_sala.py).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as PSpec

from ray_tpu.ops.attention import (
    VMEM_BUDGET_BYTES, VMEM_CEILING_BYTES, batch_head_axes, record_decision,
    resolve_attention, vmem_block_bytes)
from ray_tpu.parallel import mesh as mesh_lib
from ray_tpu.tracing import names as scopes

_NEG_INF = -1e30          # a masked logit: exp() of it is an exact 0
_FORCED = 1e4             # a forced block's score: a sum of g softmaxes is ≤ g
# float32 logits of one run of query rows in the selection stay under this
_SELECT_CHUNK_BYTES = 2 ** 26


class SparseSizes(NamedTuple):
    """MiniCPM4's ``sparse_config``, in tokens (``top_k`` in blocks)."""
    block: int = 64           # keys a block
    kernel: int = 32          # tokens a compressed key is the mean of
    stride: int = 16          # tokens between two compressed keys
    top_k: int = 64           # blocks a query is given
    init_blocks: int = 1      # always given: the row's first blocks
    window: int = 2048        # always given: the blocks of the last tokens
    dense_len: int = 8192     # rows up to this long take plain attention

    def check(self, S: int) -> None:
        if self.kernel % self.stride or self.block % self.stride:
            raise ValueError(f"stride {self.stride} must divide kernel "
                             f"{self.kernel} and block {self.block}")
        if S % self.block or S < self.kernel:
            raise ValueError(f"a row of {S} tokens is not a whole number of "
                             f"{self.block}-key blocks")


def window_blocks(sizes: SparseSizes) -> int:
    """The most blocks the last ``window`` tokens of a query touch."""
    return -(-(sizes.window - 1) // sizes.block) + 1


def kept_share(S: int, sizes: SparseSizes) -> float:
    """The share of a row's visible (token, key) pairs that are given: token t
    sees t + 1 keys and is given at most top_k blocks of them."""
    given = min(sizes.top_k * sizes.block, S)
    kept = given * (given + 1) / 2 + (S - given) * given
    return kept / (S * (S + 1) / 2)


# --------------------------------------------------------------------------- #
# The selection (XLA)
# --------------------------------------------------------------------------- #

def compressed_keys(k: jax.Array, sizes: SparseSizes) -> jax.Array:
    """k [B, KH, S, hd] → float32 [B, KH, n_c, hd], the mean of each window of
    ``kernel`` tokens, ``stride`` apart (whole windows only)."""
    total = lax.reduce_window(
        k.astype(jnp.float32), 0.0, lax.add, (1, 1, sizes.kernel, 1),
        (1, 1, sizes.stride, 1), "VALID")
    return total / sizes.kernel


def block_scores(q: jax.Array, kc: jax.Array, first: int, S: int,
                 sizes: SparseSizes) -> jax.Array:
    """q [B, KH, g, rows, hd] (tokens ``first`` … of the row) and the
    compressed keys → float32 [B, KH, rows, S / block]: each block's score,
    _FORCED for a block that is given whatever it scores and −1 for one the
    token does not see."""
    rows, hd = q.shape[3], q.shape[4]
    n_c, NB = kc.shape[2], S // sizes.block
    t = first + jnp.arange(rows)
    logits = jnp.einsum("bkgrd,bkcd->bkgrc", q.astype(jnp.float32), kc,
                        precision=lax.Precision.HIGHEST) / math.sqrt(hd)
    seen = (jnp.arange(n_c)[None, :] * sizes.stride + sizes.kernel - 1
            <= t[:, None])                                       # [rows, n_c]
    logits = jnp.where(seen, logits, _NEG_INF)
    p = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    p = jnp.where(seen, p, 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    p = jnp.sum(p, axis=2)                                       # the group's
    # a block's score: the max over the compressed keys that overlap it
    # (per + extra of them, per apart, the first `extra` before the block)
    per, extra = sizes.block // sizes.stride, sizes.kernel // sizes.stride - 1
    score = lax.reduce_window(
        jnp.pad(p, ((0, 0),) * 3 + ((extra, NB * per - n_c),),
                constant_values=-1.0),
        -1.0, lax.max, (1, 1, 1, per + extra), (1, 1, 1, per), "VALID")
    b = jnp.arange(NB)[None, :]
    own = (t // sizes.block)[:, None]
    forced = (b < sizes.init_blocks) | (
        b >= jnp.maximum(t - sizes.window + 1, 0)[:, None] // sizes.block)
    return jnp.where(b > own, -1.0, jnp.where(forced, _FORCED, score))


@jax.named_scope(scopes.SPARSE_SELECT)
def sparse_select(q: jax.Array, k: jax.Array, sizes: SparseSizes) -> jax.Array:
    """q [B, H, S, hd], k [B, KH, S, hd] → int32 [B, KH, S, top_k]: the key
    blocks each token is given, shared by the H / KH query heads of a
    key-value head (the highest score first; where a token sees fewer than
    top_k blocks the rest are blocks it does not see, which the causal mask
    drops). No gradient."""
    B, H, S, hd = q.shape
    KH = k.shape[1]
    sizes.check(S)
    q, k = lax.stop_gradient(q), lax.stop_gradient(k)
    kc = compressed_keys(k, sizes)
    n_c, top = kc.shape[2], min(sizes.top_k, S // sizes.block)
    rows = S
    while rows % 2 == 0 and rows > sizes.block and (
            B * H * rows * n_c * 4 > _SELECT_CHUNK_BYTES):
        rows //= 2
    qg = q.reshape(B, KH, H // KH, S // rows, rows, hd)

    def of_chunk(args):
        i, qc = args
        score = block_scores(qc, kc, i * rows, S, sizes)
        return lax.top_k(score, top)[1].astype(jnp.int32)

    ids = lax.map(of_chunk, (jnp.arange(S // rows), jnp.moveaxis(qg, 3, 0)))
    return jnp.moveaxis(ids, 0, 2).reshape(B, KH, S, top)


def chosen_table(ids: jax.Array, S: int, block: int) -> jax.Array:
    """ids [B, KH, S, top] → bf16 0/1 [B, KH, S / block, S]: whether token t
    was given block b, blocks down the sublanes (the kernels' layout)."""
    NB = S // block
    hit = ids[:, :, None, :, :] == jnp.arange(NB)[None, None, :, None, None]
    return jnp.any(hit, axis=-1).astype(jnp.bfloat16)


# --------------------------------------------------------------------------- #
# Tiling
# --------------------------------------------------------------------------- #

class SparseTiling(NamedTuple):
    block_q: int              # tokens of queries a tile (× g heads: its rows)
    block_k: int              # keys a tile
    vmem_estimate: int


_decisions: Dict[tuple, Dict[str, Any]] = {}
_TILE_Q = 128                 # whole 128-lane tiles a head: the heads of a
                              # tile stand side by side along the lanes
_TILES_K = (512, 256, 128)


def sparse_tiling_decisions() -> List[Dict[str, Any]]:
    """Every distinct tiling this process has traced a sparse-attention kernel
    with, as the ``ops/sparse_tiling`` events carry them."""
    return list(_decisions.values())


def _vmem_estimate(kernel: str, bq: int, bk: int, g: int, hd: int, NB: int,
                   a: int) -> int:
    """VMEM bytes one grid step needs: every in/out block twice, the scratch
    once, and the float32 [block_k, g·block_q] tiles the body holds at once
    (logits, probabilities, and in the backward dP and dS). An upper bound."""
    blk, R = vmem_block_bytes, g * bq
    tile = blk((bk, R), 4)
    q_blk, kv_blk = g * blk((bq, hd), a), blk((bk, hd), a)
    io = q_blk + 2 * kv_blk + blk((NB, bq), 2)
    if kernel == "fwd":
        io += q_blk + blk((g, bq), 4)                     # o, lse
        scratch, live = blk((hd, R), 4) + 2 * blk((1, R), 4), 3 * tile
    elif kernel == "bwd_dq":
        io += 2 * q_blk + 2 * blk((g, bq), 4)             # do, dq; lse, delta
        scratch, live = blk((hd, R), 4), 5 * tile
    else:
        io += q_blk + 2 * blk((g, bq), 4) + 2 * kv_blk    # do; dk, dv
        scratch, live = 2 * blk((bk, hd), 4), 5 * tile
    return 2 * io + scratch + live


def choose_sparse_tiling(kernel: str, rows: int, S: int, g: int, hd: int,
                         sizes: SparseSizes, dtype_bytes: int) -> SparseTiling:
    """THE rule for how a sparse-attention kernel (``"fwd"``, ``"bwd_dq"``,
    ``"bwd_dkv"``) tiles its work: ``block_q`` tokens of queries × the group's
    g heads against ``block_k`` keys. block_q is 128 (the heads' columns of a
    tile are then whole lane tiles) or the row where it is shorter; block_k
    the largest of 512, 256, 128 that divides the row, is whole key blocks and
    whose estimate fits half of what a kernel may be given. Recorded once a
    distinct decision (``ops/sparse_tiling``)."""
    if kernel not in ("fwd", "bwd_dq", "bwd_dkv"):
        raise ValueError(f"unknown sparse-attention kernel {kernel!r}")
    bq = _TILE_Q if S % _TILE_Q == 0 else S
    NB = S // sizes.block
    fit = [bk for bk in _TILES_K + (S,)
           if bk <= S and S % bk == 0 and bk % sizes.block == 0
           and _vmem_estimate(kernel, bq, bk, g, hd, NB, dtype_bytes)
           <= VMEM_CEILING_BYTES // 2]
    if not fit:
        raise ValueError(
            f"sparse attention {kernel}: no key tile of a {S}-token row in "
            f"{sizes.block}-key blocks fits VMEM with {g} heads a group at "
            f"hd={hd}")
    bk = fit[0]
    tiling = SparseTiling(bq, bk, _vmem_estimate(kernel, bq, bk, g, hd, NB,
                                                 dtype_bytes))
    record_decision(_decisions, scopes.SPARSE_TILING, dict(zip(
        scopes.SPARSE_TILING_ARGS,
        (kernel, rows, S, g, hd, sizes.block, min(sizes.top_k, NB))
        + tuple(tiling))))
    return tiling


def _params(estimate: int):
    """Every grid here is (rows, key-value heads, tiles, tiles of the other
    kind), the last the axis the scratch accumulates along."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=None if estimate <= VMEM_BUDGET_BYTES else min(
            VMEM_CEILING_BYTES, estimate + estimate // 2))


# --------------------------------------------------------------------------- #
# The kernels. All hold a tile's logits transposed: [block_k, g · block_q],
# head h's tokens in lanes h·block_q … (h+1)·block_q.
# --------------------------------------------------------------------------- #

def _bias(sel_ref, i, j, *, bq: int, bk: int, g: int, block: int):
    """What tile (i, j)'s logits take on, float32 [bk, g·bq]: 0 where the
    key's block was given to the row's token and the key is not after it,
    _NEG_INF elsewhere (a logit is lost in it: their sum is _NEG_INF)."""
    NB = sel_ref.shape[0]
    key = j * bk + lax.broadcasted_iota(jnp.int32, (bk, NB), 0)
    first = block * lax.broadcasted_iota(jnp.int32, (bk, NB), 1)
    spread = ((key >= first) & (key < first + block)).astype(sel_ref.dtype)
    given = jnp.dot(spread, sel_ref[...],
                    preferred_element_type=jnp.float32)          # [bk, bq]
    causal = (j * bk + lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
              <= i * bq + lax.broadcasted_iota(jnp.int32, (bk, bq), 1))
    bias = jnp.where((given > 0.5) & causal, 0.0, _NEG_INF)
    return jnp.concatenate([bias] * g, axis=1) if g > 1 else bias


def _rows_of(ref, g: int):
    """[g, bq] float32 statistics → one [1, g·bq] row, heads side by side."""
    if g == 1:
        return ref[...]
    return jnp.concatenate([ref[h:h + 1, :] for h in range(g)], axis=1)


def _live(cnt_ref, i, j, *, nq: int, nk: int, bq: int, bk: int):
    """Tile pair (i, j) holds a choice and is not in the future (grid
    (B, KH, ·, ·); the caller names its last two axes)."""
    flat = ((pl.program_id(0) * pl.num_programs(1) + pl.program_id(1))
            * nq + i) * nk + j
    return (cnt_ref[flat] > 0) & (j * bk <= i * bq + bq - 1)


def _logits(q_ref, k_ref, scale: float, g: int):
    """(the tile's queries [g·bq, hd] scaled, s^T [bk, g·bq] float32)."""
    bq, hd = q_ref.shape[1], q_ref.shape[2]
    qs = q_ref[...].reshape(g * bq, hd) * jnp.asarray(scale, q_ref.dtype)
    return qs, lax.dot_general(k_ref[...], qs, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _fwd_kernel(cnt_ref, q_ref, k_ref, v_ref, sel_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, g, block, nq, nk):
    i, j = pl.program_id(2), pl.program_id(3)
    bq, hd = q_ref.shape[1], q_ref.shape[2]
    bk = k_ref.shape[0]

    @pl.when(j == 0)
    def _first():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(_live(cnt_ref, i, j, nq=nq, nk=nk, bq=bq, bk=bk))
    def _tile():
        _, s = _logits(q_ref, k_ref, scale, g)
        s = s + _bias(sel_ref, i, j, bq=bq, bk=bk, g=g, block=block)
        m = m_scr[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        alpha = jnp.exp(m - m_new)
        # a row with no key in a tile it meets before its first holds
        # exp(0) = 1 a key there; the first real key's alpha = 0 wipes it
        # (every token is given its own block, so one comes)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=0, keepdims=True)
        v = v_ref[...]
        acc_scr[...] = acc_scr[...] * alpha + lax.dot_general(
            v, p.astype(v.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [hd, g·bq]
        m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _last():
        l = l_scr[...]
        o = (acc_scr[...] / l).T.astype(o_ref.dtype)          # [g·bq, hd]
        o_ref[...] = o.reshape(g, bq, hd)
        lse = m_scr[...] + jnp.log(l)
        for h in range(g):
            lse_ref[h:h + 1, :] = lse[:, h * bq:(h + 1) * bq]


def _p_and_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sel_ref, i, j,
              *, scale, g, block):
    """The backward's shared part for tile (i, j): (scaled queries, dO, p^T,
    dS^T [bk, g·bq] in the operands' dtype)."""
    bq, hd = q_ref.shape[1], q_ref.shape[2]
    bk = k_ref.shape[0]
    qs, s = _logits(q_ref, k_ref, scale, g)
    s = s + _bias(sel_ref, i, j, bq=bq, bk=bk, g=g, block=block)
    p = jnp.exp(s - _rows_of(lse_ref, g))
    do = do_ref[...].reshape(g * bq, hd)
    dp = lax.dot_general(v_ref[...], do, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    ds = (p * (dp - _rows_of(delta_ref, g))).astype(qs.dtype)
    return qs, do, p.astype(do.dtype), ds


def _bwd_dq_kernel(cnt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   sel_ref, dq_ref, dq_scr, *, scale, g, block, nq, nk):
    i, j = pl.program_id(2), pl.program_id(3)
    bq, hd = q_ref.shape[1], q_ref.shape[2]
    bk = k_ref.shape[0]

    @pl.when(j == 0)
    def _first():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(_live(cnt_ref, i, j, nq=nq, nk=nk, bq=bq, bk=bk))
    def _tile():
        _, _, _, ds = _p_and_ds(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                delta_ref, sel_ref, i, j, scale=scale, g=g,
                                block=block)
        k = k_ref[...]
        dq_scr[...] += lax.dot_general(
            k * jnp.asarray(scale, k.dtype), ds, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [hd, g·bq]

    @pl.when(j == nk - 1)
    def _last():
        dq_ref[...] = dq_scr[...].T.astype(dq_ref.dtype).reshape(g, bq, hd)


def _bwd_dkv_kernel(cnt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    sel_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, scale, g,
                    block, nq, nk):
    j, i = pl.program_id(2), pl.program_id(3)
    bq = q_ref.shape[1]
    bk = k_ref.shape[0]

    @pl.when(i == 0)
    def _first():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(_live(cnt_ref, i, j, nq=nq, nk=nk, bq=bq, bk=bk))
    def _tile():
        qs, do, p, ds = _p_and_ds(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                  delta_ref, sel_ref, i, j, scale=scale, g=g,
                                  block=block)
        dv_scr[...] += jnp.dot(p, do, preferred_element_type=jnp.float32)
        dk_scr[...] += jnp.dot(ds, qs, preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _last():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


# --------------------------------------------------------------------------- #
# The calls
# --------------------------------------------------------------------------- #

def _tile_counts(sel, bq: int, bk: int, block: int):
    """sel [B, KH, NB, S] → int32 [B·KH·nq·nk]: the (token, block) choices in
    each (query tile, key tile) pair (the scalar-prefetch operand)."""
    B, KH, NB, S = sel.shape
    c = sel.astype(jnp.float32).reshape(B, KH, NB * block // bk, bk // block,
                                        S // bq, bq).sum((3, 5))
    return jnp.swapaxes(c, 2, 3).astype(jnp.int32).reshape(-1)


def _specs(bq: int, bk: int, g: int, hd: int, NB: int, q_first: bool):
    """BlockSpecs over grid (B, KH, query tiles, key tiles) (``q_first``) or
    (B, KH, key tiles, query tiles). A tile pair in the future is skipped by
    the kernel; its blocks are the nearest pair's that is not, so that nothing
    is fetched for it."""
    def ij(a, b):
        i, j = (a, b) if q_first else (b, a)
        if q_first:
            return i, jnp.minimum(j, (i * bq + bq - 1) // bk)
        return jnp.maximum(i, (j * bk) // bq), j

    q = pl.BlockSpec((None, g, bq, hd),
                     lambda b, h, x, y, *_: (b, h, ij(x, y)[0], 0))
    kv = pl.BlockSpec((None, None, bk, hd),
                      lambda b, h, x, y, *_: (b, h, ij(x, y)[1], 0))
    stat = pl.BlockSpec((None, None, g, bq),
                        lambda b, h, x, y, *_: (b, h, 0, ij(x, y)[0]))
    sel = pl.BlockSpec((None, None, NB, bq),
                       lambda b, h, x, y, *_: (b, h, 0, ij(x, y)[0]))
    return q, kv, stat, sel


def _shapes(q, k):
    B, H, S, hd = q.shape
    KH = k.shape[1]
    return B, H, S, hd, KH, H // KH


def _forward_call(q, k, v, sel, sizes: SparseSizes, interpret: bool):
    """q [B, H, S, hd]; k, v [B, KH, S, hd]; sel [B, KH, NB, S] →
    (o [B, H, S, hd], lse [B, H, S] float32)."""
    B, H, S, hd, KH, g = _shapes(q, k)
    NB = sel.shape[2]
    bq, bk, estimate = choose_sparse_tiling("fwd", B * KH, S, g, hd, sizes,
                                            q.dtype.itemsize)
    nq, nk = S // bq, S // bk
    q_spec, kv_spec, stat_spec, sel_spec = _specs(bq, bk, g, hd, NB, True)
    kernel = functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(hd), g=g,
                               block=sizes.block, nq=nq, nk=nk)
    R = g * bq
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, KH, nq, nk),
            in_specs=[q_spec, kv_spec, kv_spec, sel_spec],
            out_specs=[q_spec, stat_spec],
            scratch_shapes=[pltpu.VMEM((1, R), jnp.float32),
                            pltpu.VMEM((1, R), jnp.float32),
                            pltpu.VMEM((hd, R), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, KH, g, S), jnp.float32)],
        compiler_params=_params(estimate),
        interpret=interpret, name=scopes.SPARSE_ATTN_FWD_KERNEL,
    )(_tile_counts(sel, bq, bk, sizes.block), q, k, v, sel)
    return o, lse.reshape(B, H, S)


def _backward_call(q, k, v, sel, o, lse, do, sizes: SparseSizes,
                   interpret: bool):
    B, H, S, hd, KH, g = _shapes(q, k)
    NB = sel.shape[2]
    scale = 1.0 / math.sqrt(hd)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
                    ).reshape(B, KH, g, S)
    lse = lse.reshape(B, KH, g, S)
    out = []
    for name, kernel_name in (("bwd_dq", scopes.SPARSE_ATTN_BWD_DQ_KERNEL),
                              ("bwd_dkv", scopes.SPARSE_ATTN_BWD_DKV_KERNEL)):
        dq_pass = name == "bwd_dq"
        bq, bk, estimate = choose_sparse_tiling(name, B * KH, S, g, hd, sizes,
                                                q.dtype.itemsize)
        nq, nk = S // bq, S // bk
        q_spec, kv_spec, stat_spec, sel_spec = _specs(bq, bk, g, hd, NB,
                                                      dq_pass)
        R = g * bq
        body = functools.partial(
            _bwd_dq_kernel if dq_pass else _bwd_dkv_kernel, scale=scale, g=g,
            block=sizes.block, nq=nq, nk=nk)
        out.append(pl.pallas_call(
            body,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(B, KH, nq, nk) if dq_pass else (B, KH, nk, nq),
                in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec,
                          stat_spec, sel_spec],
                out_specs=[q_spec] if dq_pass else [kv_spec, kv_spec],
                scratch_shapes=[pltpu.VMEM((hd, R), jnp.float32)] if dq_pass
                else [pltpu.VMEM((bk, hd), jnp.float32)] * 2),
            out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)] if dq_pass
            else [jax.ShapeDtypeStruct(k.shape, k.dtype),
                  jax.ShapeDtypeStruct(v.shape, v.dtype)],
            compiler_params=_params(estimate), interpret=interpret,
            name=kernel_name,
        )(_tile_counts(sel, bq, bk, sizes.block), q, k, v, do, lse, delta,
          sel))
    (dq,), (dk, dv) = out
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _attend(q, k, v, sel, sizes, interpret):
    return _forward_call(q, k, v, sel, sizes, interpret)[0]


def _attend_fwd(q, k, v, sel, sizes, interpret):
    o, lse = _forward_call(q, k, v, sel, sizes, interpret)
    # by name: a checkpoint policy that keeps both spares the backward a
    # second forward call
    o = checkpoint_name(o, scopes.RES_SPARSE_O)
    lse = checkpoint_name(lse, scopes.RES_SPARSE_LSE)
    return o, (q, k, v, sel, o, lse)


def _attend_bwd(sizes, interpret, res, do):
    q, k, v, sel, o, lse = res
    dq, dk, dv = _backward_call(q, k, v, sel, o, lse, do, sizes, interpret)
    return dq, dk, dv, jnp.zeros_like(sel)


_attend.defvjp(_attend_fwd, _attend_bwd)


def attend_chosen(q, k, v, ids, sizes: SparseSizes, interpret: bool):
    """Attention of q [B, H, S, hd] over the blocks ``ids`` [B, KH, S, top]
    gives each token, of k, v [B, KH, S, hd] → [B, H, S, hd]."""
    sel = lax.stop_gradient(chosen_table(ids, q.shape[2], sizes.block))
    return _attend(q, k, v, sel, sizes, interpret)


@jax.named_scope(scopes.SPARSE_ATTENTION)
def sparse_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     sizes: SparseSizes):
    """Causal attention of q [B, H, S, hd] over the key blocks sparse_select
    gives each token of k, v [B, KH, S, hd] → ([B, H, S, hd], the chosen
    ids [B, KH, S, top]). Under a mesh
    (parallel/mesh.current_mesh) each device takes its own rows, and its own
    key-value heads with their groups where tp divides them; the kernels
    compile on a TPU and interpret elsewhere (attention.resolve_attention)."""
    mesh = mesh_lib.current_mesh()
    _, interpret = resolve_attention(mesh=mesh)
    select = functools.partial(sparse_select, sizes=sizes)
    attend = functools.partial(attend_chosen, sizes=sizes, interpret=interpret)
    if mesh is not None:
        batch_axes, head_ax = batch_head_axes(mesh, q.shape[0], k.shape[1])
        spec = PSpec(batch_axes, head_ax, None, None)
        select, attend = (
            jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * n, out_specs=spec,
                          check_vma=False)
            for fn, n in ((select, 2), (attend, 4)))
    # named between the two: a checkpoint policy that keeps the ids spares
    # the backward the scoring pass and the `top_k` (an integer residual
    # named INSIDE a shard_map is one jax.checkpoint cannot keep)
    ids = checkpoint_name(select(q, k), scopes.RES_SPARSE_IDS)
    return attend(q, k, v, ids), ids
