"""EVA attention for TPU: exact softmax inside a window, one learned summary a
chunk of every earlier window, one normaliser over both (Zheng et al.,
"Efficient Attention via Control Variates", arXiv:2302.04542, with the sampled
projection replaced by a learned one, as the released EvaByte has it).

For head h with learned vectors phi, mu in R^hd, keys and queries already
rotated, scale s = hd^-1/2, window w and chunk c:

- summaries (``eva_prep_kv``): for chunk j over positions T_j = {c·j … c·j+c-1},
  ``alpha = softmax over T_j of (s · phi·k_m)``, ``kt_j = sum alpha_m k_m + mu``,
  ``vt_j = sum alpha_m v_m``. Memory-bound (a c-way softmax and two weighted
  sums over k and v once); XLA, differentiated by AD.
- aggregation (``eva_agg_fwd`` / ``eva_agg_bwd``, Pallas): query t in window
  i = t // w sees its own window's keys m, i·w <= m <= t, and the summaries
  of every chunk of every EARLIER window, j < i·(w/c) — none of its own
  window's — under one softmax.

Design, after ops/attention.py (which this module takes its tile rule, its
VMEM block arithmetic and its transposed-logits layout from):

- [B, H, S, hd] in and out, batch and head merged into rows [R, S, hd].
- Forward: grid (rows, q tiles). A q tile lies inside one window (the tile
  divides w), so its two key sets are two loops with one running (m, l, acc):
  the summaries 0 … i·(w/c) in blocks (only the last, partial one masked, by
  summary index — visibility is by window, not by position), then the
  window's own keys up to the diagonal (only the straddling block masked).
  In VMEM: the q tile, the window's k and v ([w, hd]), the row's summaries
  ([S/c, hd]: a sixteenth of a row at c = 16, as large as one window at
  S = 32,768) — a function of the tile, the window and S/c, never [S, hd].
- Backward: grid (rows, windows), ONE fused kernel as the flash backward is.
  A window's local keys see only that window's queries, so dk and dv are
  complete per grid step; dq of the window accumulates in a [hd, w] f32
  scratch over both loops; the summaries' gradients add up over every later
  window in a whole-row f32 scratch, written once at the row's last window.
- Precision: bf16 operands, f32 logits, softmax statistics and accumulators
  (the released config's ``mixedp_attn``).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.attention import (
    _NEG_INF, VMEM_BUDGET_BYTES, VMEM_CEILING_BYTES, Tiling, _pick_block,
    batch_head_axes, choose_tiling, record_decision, resolve_attention,
    vmem_block_bytes)
from ray_tpu.tracing import names


# --------------------------------------------------------------------------- #
# The XLA formulation: the summary pass (the op's own), and the aggregation
# as plain einsums for tests to compare the kernels against
# --------------------------------------------------------------------------- #

@jax.named_scope(names.EVA_PREP_KV)
def eva_prep_kv(k, v, phi, mu, *, chunk: int) -> Tuple[jax.Array, jax.Array]:
    """k, v [B, H, S, hd]; phi, mu [H, hd] → the chunk summaries
    (kt, vt) [B, H, S/chunk, hd], in k's dtype; statistics in f32."""
    B, H, S, hd = k.shape
    scale = 1.0 / math.sqrt(hd)
    kc = k.reshape(B, H, S // chunk, chunk, hd).astype(jnp.float32)
    vc = v.reshape(B, H, S // chunk, chunk, hd).astype(jnp.float32)
    phi = phi.astype(jnp.float32)
    logits = jnp.einsum("bhjcd,hd->bhjc", kc, phi) * scale
    alpha = jax.nn.softmax(logits, axis=-1)
    kt = jnp.einsum("bhjc,bhjcd->bhjd", alpha, kc) + mu.astype(
        jnp.float32)[None, :, None, :]
    vt = jnp.einsum("bhjc,bhjcd->bhjd", alpha, vc)
    return kt.astype(k.dtype), vt.astype(v.dtype)


def eva_agg_xla(q, k, v, kt, vt, *, window: int, chunk: int):
    """The aggregation as XLA computes it from its definition: per window, one
    softmax over [summaries of earlier windows ; the window's own keys]. For
    tests and for meshes the kernels do not run on; S×(w + S/c) logits."""
    B, H, S, hd = q.shape
    nw, cpw, N = S // window, window // chunk, S // chunk
    scale = 1.0 / math.sqrt(hd)
    qw = q.reshape(B, H, nw, window, hd)
    kw = k.reshape(B, H, nw, window, hd)
    vw = v.reshape(B, H, nw, window, hd)
    local = jnp.einsum("bhiqd,bhikd->bhiqk", qw, kw,
                       preferred_element_type=jnp.float32) * scale
    causal = jnp.tril(jnp.ones((window, window), bool))
    local = jnp.where(causal, local, _NEG_INF)
    remote = jnp.einsum("bhiqd,bhjd->bhiqj", qw, kt,
                        preferred_element_type=jnp.float32) * scale
    seen = (jnp.arange(N)[None, :] < (jnp.arange(nw) * cpw)[:, None])  # [nw, N]
    remote = jnp.where(seen[None, None, :, None, :], remote, _NEG_INF)
    logits = jnp.concatenate([remote, local], axis=-1)
    probs = jax.nn.softmax(logits, axis=-1)
    o = (jnp.einsum("bhiqj,bhjd->bhiqd", probs[..., :N].astype(v.dtype), vt,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhiqk,bhikd->bhiqd", probs[..., N:].astype(v.dtype), vw,
                      preferred_element_type=jnp.float32))
    return o.reshape(B, H, S, hd).astype(q.dtype)


# --------------------------------------------------------------------------- #
# Tiling: attention.choose_tiling's, on the window; the VMEM the summaries add
# --------------------------------------------------------------------------- #

_decisions: Dict[tuple, Dict[str, Any]] = {}


def eva_tiling_decisions() -> List[Dict[str, Any]]:
    """Every distinct tiling this process has traced an EVA kernel with, as
    the ``ops/eva_tiling`` events carry them."""
    return list(_decisions.values())


def _tiling(kernel: str, rows: int, S: int, hd: int, dtype_bytes: int,
            window: int, chunk: int) -> Tuple[Tiling, int]:
    """(tiling, summary block) of an EVA kernel: the q/kv tile is the flash
    rule's for a sequence of one window (the local part IS causal flash
    attention on [w, hd]); the summaries are walked in blocks of the kv tile.
    The estimate adds what the summaries hold in VMEM — both rows, their two
    gradient rows and f32 accumulators in the backward — to the rule's own.
    Recorded once a distinct decision (``ops/eva_tiling``)."""
    t = choose_tiling(kernel, window, window, hd, dtype_bytes)
    N = S // chunk
    blk = vmem_block_bytes
    extra = 2 * blk((N, hd), dtype_bytes)                  # kt, vt
    if kernel == "bwd":
        # dkt, dvt out and their f32 accumulators; the window's k, v, dk, dv
        # stand whole where the flash backward has kv tiles
        extra += 2 * blk((N, hd), dtype_bytes) + 2 * blk((N, hd), 4)
        extra += 4 * (blk((window, hd), dtype_bytes)
                      - blk((t.block_k, hd), dtype_bytes))
    t = Tiling(t.block_q, t.block_k, t.vmem_estimate + 2 * extra)
    record_decision(_decisions, names.EVA_TILING, dict(zip(
        names.EVA_TILING_ARGS,
        (kernel, rows, S, S, hd) + tuple(t) + (window, chunk))))
    return t, _pick_block(N, t.block_k)


def _compiler_params(estimate: int):
    """Mosaic's scoped-VMEM limit for a kernel whose estimate passes the
    default: the estimate and half again, under the ceiling."""
    if estimate <= VMEM_BUDGET_BYTES:
        return None
    return pltpu.CompilerParams(
        vmem_limit_bytes=min(VMEM_CEILING_BYTES, estimate + estimate // 2))


# --------------------------------------------------------------------------- #
# Forward kernel
# --------------------------------------------------------------------------- #

def _agg_fwd_kernel(
    q_ref, k_ref, v_ref, kt_ref, vt_ref,   # [bq,hd] [w,hd] [w,hd] [N,hd] [N,hd]
    o_ref, lse_ref,                        # [bq, hd], [1, bq]
    *, scale: float, block_q: int, block_k: int, block_r: int, window: int,
    chunks_per_window: int,
):
    q_start = pl.program_id(1) * block_q
    win = q_start // window
    q_local = q_start - win * window          # the tile's offset in its window
    n_remote = win * chunks_per_window        # summaries this window sees
    # logits transposed, [keys, bq], as in attention._fwd_kernel
    qs = q_ref[...] * jnp.asarray(scale, q_ref.dtype)
    hd = qs.shape[-1]

    def online(carry, kb, vb, keep):
        m, l, acc = carry                   # [1, bq], [1, bq], [hd, bq]
        s = lax.dot_general(kb, qs, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        if keep is not None:
            s = jnp.where(keep, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        a = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * a + jnp.sum(p, axis=0, keepdims=True)
        acc = acc * a + lax.dot_general(
            vb, p.astype(vb.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    def remote(masked):
        def body(ri, carry):
            sl = pl.ds(ri * block_r, block_r)
            keep = None
            if masked:                      # by summary index: whole rows
                keep = ri * block_r + lax.broadcasted_iota(
                    jnp.int32, (block_r, block_q), 0) < n_remote
            return online(carry, kt_ref[sl, :], vt_ref[sl, :], keep)
        return body

    def local(masked):
        def body(ki, carry):
            sl = pl.ds(ki * block_k, block_k)
            keep = None
            if masked:
                keep = (q_local + lax.broadcasted_iota(
                    jnp.int32, (block_k, block_q), 1)
                    >= ki * block_k + lax.broadcasted_iota(
                        jnp.int32, (block_k, block_q), 0))
            return online(carry, k_ref[sl, :], v_ref[sl, :], keep)
        return body

    carry = (jnp.full((1, block_q), _NEG_INF, jnp.float32),
             jnp.zeros((1, block_q), jnp.float32),
             jnp.zeros((hd, block_q), jnp.float32))
    r_full = n_remote // block_r
    r_all = (n_remote + block_r - 1) // block_r
    carry = lax.fori_loop(0, r_full, remote(False), carry)
    carry = lax.fori_loop(r_full, r_all, remote(True), carry)
    k_full = (q_local + 1) // block_k
    k_all = (q_local + block_q - 1) // block_k + 1
    carry = lax.fori_loop(0, k_full, local(False), carry)
    m, l, acc = lax.fori_loop(k_full, k_all, local(True), carry)
    # every query sees itself: l > 0
    o_ref[...] = (acc / l).T.astype(o_ref.dtype)
    lse_ref[...] = m + jnp.log(l)


def _agg_forward(q, k, v, kt, vt, *, window: int, chunk: int,
                 interpret: bool):
    """q, k, v [B, H, S, hd]; kt, vt [B, H, S/chunk, hd] →
    (o [B, H, S, hd], lse [B, H, S])."""
    B, H, S, hd = q.shape
    R, N = B * H, S // chunk
    t, br = _tiling("fwd", R, S, hd, q.dtype.itemsize, window, chunk)
    bq, bk = t.block_q, t.block_k
    per_win = window // bq
    win_block = pl.BlockSpec((None, window, hd),
                             lambda g, i: (g, i // per_win, 0))
    row = pl.BlockSpec((None, N, hd), lambda g, i: (g, 0, 0))
    kernel = functools.partial(
        _agg_fwd_kernel, scale=1.0 / math.sqrt(hd), block_q=bq, block_k=bk,
        block_r=br, window=window, chunks_per_window=window // chunk)
    o, lse = pl.pallas_call(
        kernel,
        grid=(R, S // bq),
        in_specs=[pl.BlockSpec((None, bq, hd), lambda g, i: (g, i, 0)),
                  win_block, win_block, row, row],
        out_specs=[pl.BlockSpec((None, bq, hd), lambda g, i: (g, i, 0)),
                   pl.BlockSpec((None, 1, bq), lambda g, i: (g, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((R, S, hd), q.dtype),
                   jax.ShapeDtypeStruct((R, 1, S), jnp.float32)],
        compiler_params=_compiler_params(t.vmem_estimate),
        interpret=interpret,
        name=names.EVA_AGG_FWD_KERNEL,
    )(q.reshape(R, S, hd), k.reshape(R, S, hd), v.reshape(R, S, hd),
      kt.reshape(R, N, hd), vt.reshape(R, N, hd))
    return o.reshape(B, H, S, hd), lse.reshape(B, H, S)


# --------------------------------------------------------------------------- #
# Backward kernel
# --------------------------------------------------------------------------- #

def _agg_bwd_kernel(
    q_ref, k_ref, v_ref, kt_ref, vt_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dk_ref, dv_ref, dkt_ref, dvt_ref,
    dq_acc, dkt_acc, dvt_acc,
    *, scale: float, block_q: int, block_k: int, block_r: int, window: int,
    chunks_per_window: int,
):
    """One window of one row: the five matmuls of the flash backward for each
    (key block, q tile) pair, first over the summaries the window sees, then
    over its own keys below the diagonal."""
    win = pl.program_id(1)
    n_remote = win * chunks_per_window
    nq = window // block_q
    scale_c = jnp.asarray(scale, q_ref.dtype)
    hd = q_ref.shape[-1]

    @pl.when(win == 0)
    def _init():
        dkt_acc[...] = jnp.zeros_like(dkt_acc)
        dvt_acc[...] = jnp.zeros_like(dvt_acc)

    dq_acc[...] = jnp.zeros_like(dq_acc)

    def pair(kb, vb, kb_scaled, qi, carry, keep):
        dk, dv = carry
        sl = pl.ds(qi * block_q, block_q)
        qs = q_ref[sl, :] * scale_c
        do = do_ref[sl, :]
        s = lax.dot_general(kb, qs, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [bk, bq]
        if keep is not None:
            s = jnp.where(keep(qi), s, _NEG_INF)
        p = jnp.exp(s - lse_ref[:, sl])
        dv = dv + lax.dot_general(
            p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = lax.dot_general(vb, do, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[:, sl])).astype(qs.dtype)
        dk = dk + lax.dot_general(ds, qs, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dq_acc[:, sl] += lax.dot_general(
            kb_scaled, ds, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                   # [hd, bq]
        return dk, dv

    def zeros(rows):
        return (jnp.zeros((rows, hd), jnp.float32),
                jnp.zeros((rows, hd), jnp.float32))

    # ---- the summaries of earlier windows: every query of the window sees
    # the same ones, so a block is masked (by summary index) or it is not
    def remote(masked):
        def body(ri, _):
            sl = pl.ds(ri * block_r, block_r)
            kb, vb = kt_ref[sl, :], vt_ref[sl, :]
            keep = None
            if masked:
                seen = ri * block_r + lax.broadcasted_iota(
                    jnp.int32, (block_r, block_q), 0) < n_remote
                keep = lambda qi: seen
            dkt, dvt = lax.fori_loop(
                0, nq,
                lambda qi, c: pair(kb, vb, kb * scale_c, qi, c, keep),
                zeros(block_r))
            dkt_acc[sl, :] += dkt
            dvt_acc[sl, :] += dvt
            return 0
        return body

    r_full = n_remote // block_r
    r_all = (n_remote + block_r - 1) // block_r
    lax.fori_loop(0, r_full, remote(False), 0)
    lax.fori_loop(r_full, r_all, remote(True), 0)

    # ---- the window's own keys: causal, as attention._fused_bwd_kernel
    def local(ki, _):
        sl = pl.ds(ki * block_k, block_k)
        kb, vb = k_ref[sl, :], v_ref[sl, :]
        kb_scaled = kb * scale_c
        k_start = ki * block_k
        # q tiles before `first` see none of the block, [first, whole) cross
        # the diagonal, from `whole` on every query sees every key of it
        first = k_start // block_q
        whole = jnp.minimum(
            (k_start + block_k - 1 + block_q - 1) // block_q, nq)

        def keep(qi):
            return (qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
                >= k_start + lax.broadcasted_iota(
                    jnp.int32, (block_k, block_q), 0))

        carry = lax.fori_loop(
            first, whole,
            lambda qi, c: pair(kb, vb, kb_scaled, qi, c, keep), zeros(block_k))
        dk, dv = lax.fori_loop(
            whole, nq,
            lambda qi, c: pair(kb, vb, kb_scaled, qi, c, None), carry)
        dk_ref[sl, :] = dk.astype(dk_ref.dtype)
        dv_ref[sl, :] = dv.astype(dv_ref.dtype)
        return 0

    lax.fori_loop(0, window // block_k, local, 0)
    dq_ref[...] = dq_acc[...].T.astype(dq_ref.dtype)

    @pl.when(win == pl.num_programs(1) - 1)
    def _write_summaries():
        dkt_ref[...] = dkt_acc[...].astype(dkt_ref.dtype)
        dvt_ref[...] = dvt_acc[...].astype(dvt_ref.dtype)


def _agg_backward(q, k, v, kt, vt, o, lse, do, *, window: int, chunk: int,
                  interpret: bool):
    """→ (dq, dk, dv [B, H, S, hd], dkt, dvt [B, H, S/chunk, hd])."""
    B, H, S, hd = q.shape
    R, N = B * H, S // chunk
    t, br = _tiling("bwd", R, S, hd, q.dtype.itemsize, window, chunk)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(R, 1, S)
    win_block = pl.BlockSpec((None, window, hd), lambda g, i: (g, i, 0))
    row = pl.BlockSpec((None, N, hd), lambda g, i: (g, 0, 0))
    stat = pl.BlockSpec((None, 1, window), lambda g, i: (g, 0, i))
    kernel = functools.partial(
        _agg_bwd_kernel, scale=1.0 / math.sqrt(hd), block_q=t.block_q,
        block_k=t.block_k, block_r=br, window=window,
        chunks_per_window=window // chunk)
    rs = lambda x, n: x.reshape(R, n, hd)
    dq, dk, dv, dkt, dvt = pl.pallas_call(
        kernel,
        grid=(R, S // window),
        in_specs=[win_block, win_block, win_block, row, row, win_block,
                  stat, stat],
        out_specs=[win_block, win_block, win_block, row, row],
        out_shape=[jax.ShapeDtypeStruct((R, S, hd), q.dtype),
                   jax.ShapeDtypeStruct((R, S, hd), k.dtype),
                   jax.ShapeDtypeStruct((R, S, hd), v.dtype),
                   jax.ShapeDtypeStruct((R, N, hd), kt.dtype),
                   jax.ShapeDtypeStruct((R, N, hd), vt.dtype)],
        scratch_shapes=[pltpu.VMEM((hd, window), jnp.float32),
                        pltpu.VMEM((N, hd), jnp.float32),
                        pltpu.VMEM((N, hd), jnp.float32)],
        compiler_params=_compiler_params(t.vmem_estimate),
        interpret=interpret,
        name=names.EVA_AGG_BWD_KERNEL,
    )(rs(q, S), rs(k, S), rs(v, S), rs(kt, N), rs(vt, N), rs(do, S),
      lse.reshape(R, 1, S), delta)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dkt.reshape(kt.shape), dvt.reshape(vt.shape))


# --------------------------------------------------------------------------- #
# The op
# --------------------------------------------------------------------------- #

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _eva_agg(q, k, v, kt, vt, window, chunk, interpret):
    return _eva_agg_fwd(q, k, v, kt, vt, window, chunk, interpret)[0]


def _eva_agg_fwd(q, k, v, kt, vt, window, chunk, interpret):
    o, lse = _agg_forward(q, k, v, kt, vt, window=window, chunk=chunk,
                          interpret=interpret)
    # by name, so that a checkpoint policy that keeps both spares the
    # backward a second forward call
    o = checkpoint_name(o, names.RES_EVA_O)
    lse = checkpoint_name(lse, names.RES_EVA_LSE)
    return o, (q, k, v, kt, vt, o, lse)


def _eva_agg_bwd(window, chunk, interpret, res, do):
    q, k, v, kt, vt, o, lse = res
    return _agg_backward(q, k, v, kt, vt, o, lse, do, window=window,
                         chunk=chunk, interpret=interpret)


_eva_agg.defvjp(_eva_agg_fwd, _eva_agg_bwd)


def _check(q, window: int, chunk: int) -> None:
    S = q.shape[2]
    if window % chunk or S % window:
        raise ValueError(
            f"EVA attention needs chunk | window | S; got chunk={chunk} "
            f"window={window} S={S}")


def _eva(q, k, v, phi, mu, *, window: int, chunk: int, interpret: bool):
    kt, vt = eva_prep_kv(k, v, phi, mu, chunk=chunk)
    kt = checkpoint_name(kt, names.RES_EVA_KT)
    vt = checkpoint_name(vt, names.RES_EVA_VT)
    return _eva_agg(q, k, v, kt, vt, window, chunk, interpret)


def eva_attention(q, k, v, phi, mu, *, window: int, chunk: int) -> jax.Array:
    """q, k, v [B, H, S, hd] (rotated), phi, mu [H, hd] → [B, H, S, hd].
    Differentiable in all five. The kernels compile on a TPU and interpret
    elsewhere (attention.resolve_attention's rule)."""
    return eva_attention_sharded(q, k, v, phi, mu, None, window=window,
                                 chunk=chunk)


@jax.named_scope(names.EVA_ATTENTION)
def eva_attention_sharded(q, k, v, phi, mu, mesh, *, window: int,
                          chunk: int) -> jax.Array:
    """eva_attention for callers under jit/GSPMD (the model forward): global
    [B, H, S, hd] in and out, each device its [B/(dp·fsdp), H/tp, S, hd]
    shard and its heads' phi and mu (attention.flash_attention_sharded)."""
    _check(q, window, chunk)
    _, interpret = resolve_attention(mesh=mesh)
    fn = functools.partial(_eva, window=window, chunk=chunk,
                           interpret=interpret)
    if mesh is None:
        return fn(q, k, v, phi, mu)
    batch_axes, head_ax = batch_head_axes(mesh, q.shape[0], q.shape[1])
    spec, vec = P(batch_axes, head_ax, None, None), P(head_ax, None)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec, vec, vec), out_specs=spec,
        check_vma=False)(q, k, v, phi, mu)


def eva_attention_xla(q, k, v, phi, mu, *, window: int, chunk: int):
    """The same function with no kernel: the summary pass and eva_agg_xla."""
    _check(q, window, chunk)
    kt, vt = eva_prep_kv(k, v, phi, mu, chunk=chunk)
    return eva_agg_xla(q, k, v, kt, vt, window=window, chunk=chunk)
