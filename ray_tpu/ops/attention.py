"""Flash attention for TPU as Pallas kernels (fwd + bwd, causal, custom VJP).

This is the perf-critical op the XLA fallback can't match: XLA materializes the
[S, S] probability matrix as a backward residual per layer, forcing full remat
at GPT-2 batch sizes. The kernels below keep the online-softmax
running state (m, l, acc) in VMEM and never write probabilities to HBM; the
backward pass recomputes logits blockwise from (q, k, lse) the flash-attention
way.

Design notes (TPU-first):
- The public API takes [B, S, H, hd] and transposes at the boundary (XLA
  fuses the transpose into the surrounding projection matmuls) or, with
  layout="bhsd", takes head-major tensors as they are. Inside, batch and head
  are merged (a free reshape) into one dim of independent rows, [B·H, S, hd]:
  every block's minor dims are the (seq, head_dim) tile Mosaic requires, and
  the grid walks the rows one at a time whatever the head count.
- The q/kv tile is choose_tiling's decision, from the shapes and an estimate
  of the VMEM the blocks need; callers pass no tile.
- K/V live whole per row in VMEM (S·hd·2B ≈ 128 KiB at S=1024), so the kv
  loop is VMEM-resident with no DMA choreography.
- The logits tile is computed transposed, s^T = k·q^T: softmax statistics are
  lane-dense [1, block_q] rows and their reductions run down the sublanes.
  Logits/softmax accumulate in f32 (MXU native via preferred_element_type);
  p·v and the backward matmuls run bf16→f32.
- The causal mask is computed from GLOBAL positions `q_offset`/`kv_offset`
  (scalar-prefetch args), so the same kernel serves single-device attention
  (offsets 0) and ring attention (per-step rotated offsets, ops/ring_attention).
- Backward = ONE fused kernel (grid over kv blocks, loop q): dk/dv written
  per kv block, dq accumulated in a VMEM-resident whole-row f32 scratch —
  s/p/dp computed once per block pair instead of twice (the split dq + dkv
  formulation costs 7 matmuls and double the exp/mask work; fused is 5).
- lse/delta ride as [B·H, 1, S] so their (1, block) tiles satisfy the minor-
  dim rules and the kernels read them as the rows they are; lse is [B, H, S]
  at the API edge.

No counterpart exists in the reference (it has no flash/SP story at all —
SURVEY.md §2.10); this is new TPU-native code.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ray_tpu.tracing import get_buffer, names

_NEG_INF = -1e30  # mask value: large-negative, not -inf (keeps exp() exact 0)


def _pick_block(seq_len: int, preferred: int) -> int:
    b = min(preferred, seq_len)
    while seq_len % b:
        b //= 2
    return max(b, 1)


def resolve_attention(impl: str = "auto", mesh=None) -> Tuple[str, bool]:
    """THE rule for which attention runs and whether its Pallas kernels are
    interpreted. Returns ``(impl, interpret)``.

    Decided from the devices the computation is laid out on — ``mesh``'s, or
    the default backend's when there is no mesh — never from the caller's
    guess: a CPU mesh on a TPU host must interpret, a TPU mesh must not.
    ``impl="auto"`` becomes ``"ring"`` on a mesh with a cp axis, else the
    compiled flash kernel on TPU (no S×S residuals → no full remat) and the
    XLA einsum elsewhere (flash-in-interpret is slow); an explicit impl is
    kept. Off TPU every Pallas kernel interprets, so tests run the same code.
    """
    devices = mesh.devices.flat if mesh is not None else jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if impl == "auto":
        if mesh is not None and mesh.shape.get("cp", 1) > 1:
            impl = "ring"
        else:
            impl = "pallas" if on_tpu else "xla"
    return impl, not on_tpu


# --------------------------------------------------------------------------- #
# Tiling: the kernels' work partition, chosen here from the shapes
# --------------------------------------------------------------------------- #

class Tiling(NamedTuple):
    block_q: int
    block_k: int
    vmem_estimate: int        # bytes, vmem_estimate() of this choice


# Mosaic's scoped-VMEM limit for one kernel on the chips this runs on; the
# rule holds vmem_estimate() under it.
VMEM_BUDGET_BYTES = 16 * 2 ** 20
# What a kernel may be given beyond that default (the EVA, scan and
# sparse-attention kernels ask for their own estimate and a margin, never for
# more than this): a v5e core has 128 MiB of VMEM.
VMEM_CEILING_BYTES = 96 * 2 ** 20
# The q and kv tile both kernels want, and the smallest the rule falls back
# to: choose_tiling's docstring says where they come from.
_TARGET_TILE = 512
_MIN_TILE = 128


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def vmem_block_bytes(shape, itemsize: int) -> int:
    """Bytes one block takes in VMEM: its last two dims are stored in
    (sublane, lane) tiles of 8 × 128 32-bit words (16 rows of bf16) and padded
    up to whole tiles — a [1024, 64] bf16 block takes what [1024, 128] does."""
    rows, lanes = shape
    sublanes = 8 * 4 // itemsize
    return _round_up(rows, sublanes) * _round_up(lanes, 128) * itemsize


def vmem_estimate(kernel: str, block_q: int, block_k: int,
                  Sq: int, Skv: int, hd: int, dtype_bytes: int) -> int:
    """VMEM bytes one grid step needs, as the rule counts them: every in/out
    block twice (Pallas double-buffers them), the backward's f32 dq
    accumulator once, and one [block_k, block_q] f32 logits tile plus the
    loop's f32 accumulators. An upper bound, not Mosaic's own figure."""
    blk = vmem_block_bytes
    tile = blk((block_k, block_q), 4)
    if kernel == "fwd":
        io = (2 * blk((block_q, hd), dtype_bytes)         # q, o
              + 2 * blk((Skv, hd), dtype_bytes)           # k, v: whole rows
              + blk((1, block_q), 4))                     # lse
        live = tile + blk((hd, block_q), 4)               # s^T; acc^T
    else:
        io = (3 * blk((Sq, hd), dtype_bytes)              # q, do, dq: whole rows
              + 4 * blk((block_k, hd), dtype_bytes)       # k, v, dk, dv
              + 2 * blk((1, Sq), 4))                      # lse, delta
        live = (blk((hd, Sq), 4)                          # dq^T accumulator
                + tile + 2 * blk((block_k, hd), 4))       # s^T; dk, dv
    return 2 * io + live


_decisions: Dict[tuple, Dict[str, Any]] = {}


def flash_tiling_decisions() -> List[Dict[str, Any]]:
    """Every distinct tiling this process has traced a flash kernel with, as
    the ``ops/flash_tiling`` events carry them."""
    return list(_decisions.values())


def record_decision(decisions: Dict[tuple, Dict[str, Any]], event: str,
                    args: Dict[str, Any]) -> None:
    """A static choice has no hit rate; its counter is the choice. Each
    distinct one goes once, as the instant event ``<component>/<name>``, to
    the task-event buffer (→ ``ray_tpu.timeline()``), and into ``decisions``."""
    key = tuple(args.values())
    if key in decisions:
        return
    decisions[key] = args
    component, name = event.split("/")
    get_buffer().record_profile(name, component=component, args=args)


def _record(kernel: str, rows: int, Sq: int, Skv: int, hd: int,
            tiling: Tiling) -> None:
    """The tiling a flash kernel is traced with, with the shapes it was
    given (``ops/flash_tiling``)."""
    record_decision(_decisions, names.FLASH_TILING, dict(zip(
        names.FLASH_TILING_ARGS, (kernel, rows, Sq, Skv, hd) + tuple(tiling))))


def choose_tiling(
    kernel: str, Sq: int, Skv: int, hd: int, dtype_bytes: int, *,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
) -> Tiling:
    """THE rule for how a flash kernel tiles its work. ``kernel`` is ``"fwd"``
    or ``"bwd"``. The keywords are a caller's explicit choices: each is kept
    (clamped to a divisor of its sequence) and the rule fills in the other.
    Raises ``ValueError`` when no tiling of its own fits the budget.

    The constants, fitted on a v5e inside the `gpt2-124m` and `gpt2-xl` train
    steps and standalone at ``[8,16,2048,128]`` (PERF.md §6, PR 25):

    - Tiles 512/512, both kernels, hd 64 and 128. The loop body's fixed cost
      (the matmuls' fill and drain, the chain max → exp → sum → matmul) is
      paid per tile, so smaller tiles lose (256/256: forward +28 %, backward
      +8 % in the step) although they skip more of the causal triangle; 1,024
      on either side loses too (+13–18 %: the f32 tile no longer lives near
      the registers). A sequence the tile does not divide gets the largest
      power-of-two fraction of it that does.
    - One (batch, head) row a grid step. Four rows unrolled into the
      forward's loop body were measured: nothing on `gpt2-124m` (step 73.99
      ms against 74.00), 0.28 % of the `gpt2-xl` step (914.8 against 917.4),
      nothing in the backward — not worth a second loop in each kernel.
    - When a tiling does not fit VMEM_BUDGET_BYTES the rule halves the kv
      tile, then the q tile, in turn, down to 128. What does not shrink that
      way are the whole-row blocks (k/v in the forward; q, do, dq and the f32
      dq accumulator in the backward): they bound the sequence length.
    """
    if kernel not in ("fwd", "bwd"):
        raise ValueError(f"unknown flash kernel {kernel!r}")
    q_ = _pick_block(Sq, block_q or _TARGET_TILE)
    k_ = _pick_block(Skv, block_k or _TARGET_TILE)
    # a caller who fixed both gets them: Mosaic is the judge
    explicit = block_q is not None and block_k is not None
    while True:
        t = Tiling(q_, k_, vmem_estimate(kernel, q_, k_, Sq, Skv, hd,
                                         dtype_bytes))
        if explicit or t.vmem_estimate <= VMEM_BUDGET_BYTES:
            return t
        can_k = block_k is None and k_ > _MIN_TILE
        can_q = block_q is None and q_ > _MIN_TILE
        if can_k and (k_ >= q_ or not can_q):
            k_ = _pick_block(Skv, k_ // 2)
        elif can_q:
            q_ = _pick_block(Sq, q_ // 2)
        else:
            break
    raise ValueError(
        f"flash attention {kernel}: no tiling fits the VMEM budget of "
        f"{VMEM_BUDGET_BYTES} bytes for Sq={Sq} Skv={Skv} hd={hd} "
        f"({dtype_bytes}-byte operands): the smallest tried, block_q="
        f"{t.block_q} block_k={t.block_k}, is estimated at "
        f"{t.vmem_estimate} bytes"
    )


# --------------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------------- #

def _fwd_kernel(
    q_off_ref, kv_off_ref,            # scalar prefetch: global offsets [1]
    q_ref, k_ref, v_ref,              # [bq, hd], [Skv, hd], [Skv, hd]
    o_ref, lse_ref,                   # [bq, hd], [1, bq]
    *, scale: float, causal: bool, block_q: int, block_k: int, kv_len: int,
):
    qi = pl.program_id(1)
    q_global = q_off_ref[0] + qi * block_q

    nk = kv_len // block_k
    if causal:
        # only kv blocks whose global start can be <= the last query row
        last_q = q_global + block_q - 1
        num_blocks = jnp.clip(
            (last_q - kv_off_ref[0]) // block_k + 1, 0, nk
        )
        # blocks whose last column <= the FIRST query row need no mask; only
        # the diagonal-straddling tail pays the iota/select work
        num_full = jnp.clip((q_global - kv_off_ref[0] + 1) // block_k, 0, nk)
    else:
        num_blocks = nk
        num_full = nk

    # The logits tile is held TRANSPOSED, s^T = k·q^T, [block_k, block_q]:
    # the softmax reductions then run down the sublanes (elementwise VPU
    # maxima/sums across vregs) and the running max/sum are [1, block_q]
    # lane-dense rows. With s as [block_q, block_k] every kv block paid two
    # cross-lane (XLU) reductions and a lane broadcast of a [block_q, 1]
    # column — a third of the kernel's time on the v5e.
    #
    # fold the softmax scale into q once — a per-block [bk, bq] f32
    # multiply otherwise rides every inner iteration
    qs = q_ref[...] * jnp.asarray(scale, q_ref.dtype)
    hd = qs.shape[-1]

    def make_body(masked):
        def body(ki, carry):
            m, l, acc = carry               # [1, bq], [1, bq], [hd, bq]
            kv = pl.ds(ki * block_k, block_k)
            s = lax.dot_general(
                k_ref[kv, :], qs, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                               # [bk, bq]
            if masked:
                keep = (
                    q_global + lax.broadcasted_iota(
                        jnp.int32, (block_k, block_q), 1)
                    >= kv_off_ref[0] + ki * block_k + lax.broadcasted_iota(
                        jnp.int32, (block_k, block_q), 0))
                s = jnp.where(keep, s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = l * alpha + jnp.sum(p, axis=0, keepdims=True)
            v = v_ref[kv, :]
            acc = acc * alpha + lax.dot_general(
                v, p.astype(v.dtype), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                               # v^T·p^T = (p·v)^T, [hd, bq]
            return m_new, l, acc
        return body

    carry = (jnp.full((1, block_q), _NEG_INF, jnp.float32),
             jnp.zeros((1, block_q), jnp.float32),
             jnp.zeros((hd, block_q), jnp.float32))
    carry = lax.fori_loop(0, num_full, make_body(False), carry)
    m, l, acc = lax.fori_loop(num_full, num_blocks, make_body(causal), carry)
    # rows with no valid kv (ring attention future chunks): l == 0 →
    # output 0, lse = -inf-ish so the ring merge gives them zero weight.
    l_safe = jnp.where(l > 0, l, 1.0)
    o_ref[...] = (acc / l_safe).T.astype(o_ref.dtype)
    lse_ref[...] = jnp.where(l > 0, m + jnp.log(l_safe), _NEG_INF)


def _mha_forward_bhsd(
    q, k, v, q_offset, kv_offset, *,
    causal: bool, scale: float, interpret: bool,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """q,k,v: [B, H, S, hd] → (o [B,H,S,hd], lse [B,H,S]). Batch and head are
    merged (a free reshape) into the one dim of independent rows the grid
    walks. Tiles the caller leaves None are choose_tiling's."""
    B, H, Sq, hd = q.shape
    Skv = k.shape[2]
    R = B * H
    t = choose_tiling("fwd", Sq, Skv, hd, q.dtype.itemsize,
                      block_q=block_q, block_k=block_k)
    _record("fwd", R, Sq, Skv, hd, t)
    bq, bk = t.block_q, t.block_k
    kv_row = pl.BlockSpec((None, Skv, hd), lambda g, i, *_: (g, 0, 0))

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=bq, block_k=bk, kv_len=Skv,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R, Sq // bq),
            in_specs=[
                pl.BlockSpec((None, bq, hd), lambda g, i, *_: (g, i, 0)),
                kv_row, kv_row,
            ],
            out_specs=[
                pl.BlockSpec((None, bq, hd), lambda g, i, *_: (g, i, 0)),
                pl.BlockSpec((None, 1, bq), lambda g, i, *_: (g, 0, i)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((R, Sq, hd), q.dtype),
            jax.ShapeDtypeStruct((R, 1, Sq), jnp.float32),
        ],
        interpret=interpret,
        name=names.FLASH_FWD_KERNEL,
    )(q_offset, kv_offset, q.reshape(R, Sq, hd), k.reshape(R, Skv, hd),
      v.reshape(R, Skv, hd))
    return o.reshape(B, H, Sq, hd), lse.reshape(B, H, Sq)


# --------------------------------------------------------------------------- #
# Backward
# --------------------------------------------------------------------------- #

def _fused_bwd_kernel(
    q_off_ref, kv_off_ref,
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dk_ref, dv_ref, dq_acc,
    *, scale: float, causal: bool, block_q: int, block_k: int, q_len: int,
):
    """Single-pass backward: grid over kv blocks; dk/dv written per block,
    dq accumulated over the kv grid dim in a whole-row f32 VMEM scratch
    (bf16 accumulation would drift with the number of kv blocks) and written
    once, at the last kv block, into an output block whose index map is
    constant in that dim. Versus the split dq/dkv kernels this computes s, p
    and dp ONCE per (q, kv) block pair — 5 matmuls instead of 7 and half the
    exp/mask VPU work — worth ~25% of backward time at GPT-2 shapes."""
    ki = pl.program_id(1)
    kv_global = kv_off_ref[0] + ki * block_k

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    nq = q_len // block_q
    if causal:
        first = jnp.clip((kv_global - q_off_ref[0]) // block_q, 0, nq)
        first_full = jnp.clip(
            -((q_off_ref[0] - kv_global - block_k + 1) // block_q), 0, nq
        )
    else:
        first = 0
        first_full = 0

    scale_c = jnp.asarray(scale, q_ref.dtype)

    # the logits tile is held transposed (see _fwd_kernel): lse and delta are
    # read as the [1, block_q] rows they are stored as, p^T and ds^T feed dv
    # and dk as plain matmuls with no transpose, and dq accumulates
    # transposed, [hd, Sq] — lane-dense, half the VMEM of a lane-padded
    # [Sq, hd] block.
    k = k_ref[...]
    v = v_ref[...]
    hd = k.shape[-1]
    # dq contribution is ds @ (k*scale): folding the softmax scale into
    # k here is one [bk, hd] multiply per grid step instead of per-pair
    k_scaled = k * scale_c

    def make_body(masked):
        def body(qi, carry):
            dk, dv = carry
            sl = pl.ds(qi * block_q, block_q)
            qs = q_ref[sl, :] * scale_c
            do = do_ref[sl, :]
            lse = lse_ref[:, sl]                         # [1, bq]
            delta = delta_ref[:, sl]
            s = lax.dot_general(
                k, qs, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                            # [bk, bq]
            if masked:
                keep = (
                    q_off_ref[0] + qi * block_q + lax.broadcasted_iota(
                        jnp.int32, (block_k, block_q), 1)
                    >= kv_global + lax.broadcasted_iota(
                        jnp.int32, (block_k, block_q), 0))
                s = jnp.where(keep, s, _NEG_INF)
            p = jnp.exp(s - lse)
            dv = dv + lax.dot_general(
                p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dp = lax.dot_general(
                v, do, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                            # [bk, bq]
            ds = (p * (dp - delta)).astype(qs.dtype)
            dk = dk + lax.dot_general(
                ds, qs, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dq_acc[:, sl] += lax.dot_general(
                k_scaled, ds, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                            # [hd, bq]
            return dk, dv
        return body

    carry = (jnp.zeros((block_k, hd), jnp.float32),
             jnp.zeros((block_k, hd), jnp.float32))
    carry = lax.fori_loop(first, first_full, make_body(causal), carry)
    dk, dv = lax.fori_loop(first_full, nq, make_body(False), carry)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _write_dq():
        dq_ref[...] = dq_acc[...].T.astype(dq_ref.dtype)


def _mha_backward_bhsd(
    q, k, v, o, lse, do, q_offset, kv_offset, *,
    causal: bool, scale: float, interpret: bool,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
):
    """All tensors [B, H, S, hd]; lse [B, H, S]. Returns dq, dk, dv. Rows and
    tiles as in _mha_forward_bhsd, chosen for this kernel separately."""
    B, H, Sq, hd = q.shape
    Skv = k.shape[2]
    R = B * H
    t = choose_tiling("bwd", Sq, Skv, hd, q.dtype.itemsize,
                      block_q=block_q, block_k=block_k)
    _record("bwd", R, Sq, Skv, hd, t)
    bq, bk = t.block_q, t.block_k

    # delta_i = rowsum(dO_i * O_i): cheap elementwise+reduce, XLA fuses it.
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    ).reshape(R, 1, Sq)
    row = pl.BlockSpec((None, Sq, hd), lambda g, i, *_: (g, 0, 0))
    kv_block = pl.BlockSpec((None, bk, hd), lambda g, i, *_: (g, i, 0))
    stat = pl.BlockSpec((None, 1, Sq), lambda g, i, *_: (g, 0, 0))

    fused_kernel = functools.partial(
        _fused_bwd_kernel, scale=scale, causal=causal,
        block_q=bq, block_k=bk, q_len=Sq,
    )
    dq, dk, dv = pl.pallas_call(
        fused_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R, Skv // bk),
            in_specs=[row, kv_block, kv_block, row, stat, stat],
            out_specs=[row, kv_block, kv_block],
            scratch_shapes=[pltpu.VMEM((hd, Sq), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((R, Sq, hd), q.dtype),
            jax.ShapeDtypeStruct((R, Skv, hd), k.dtype),
            jax.ShapeDtypeStruct((R, Skv, hd), v.dtype),
        ],
        interpret=interpret,
        name=names.FLASH_BWD_KERNEL,
    )(q_offset, kv_offset, q.reshape(R, Sq, hd), k.reshape(R, Skv, hd),
      v.reshape(R, Skv, hd), do.reshape(R, Sq, hd), lse.reshape(R, 1, Sq),
      delta)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


# --------------------------------------------------------------------------- #
# Public API ([B, S, H, hd] boundary layout)
# --------------------------------------------------------------------------- #

def _to_bhsd(x):
    return jnp.swapaxes(x, 1, 2)


def _zero_off():
    return jnp.zeros((1,), jnp.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, causal, scale, block_q, block_k, bwd_block_q,
           bwd_block_k, interpret, bhsd):
    o, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k, bwd_block_q,
                      bwd_block_k, interpret, bhsd)
    return o


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, bwd_block_q,
               bwd_block_k, interpret, bhsd):
    if bhsd:
        qt, kt, vt = q, k, v
    else:
        qt, kt, vt = _to_bhsd(q), _to_bhsd(k), _to_bhsd(v)
    o, lse = _mha_forward_bhsd(
        qt, kt, vt, _zero_off(), _zero_off(),
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    # the kernel's two residuals by name: a checkpoint policy that keeps both
    # does not run the forward kernel a second time in the backward
    o = checkpoint_name(o, names.RES_FLASH_O)
    lse = checkpoint_name(lse, names.RES_FLASH_LSE)
    return (o if bhsd else _to_bhsd(o)), (qt, kt, vt, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, bwd_block_q, bwd_block_k,
               interpret, bhsd, res, do):
    qt, kt, vt, o, lse = res
    dq, dk, dv = _mha_backward_bhsd(
        qt, kt, vt, o, lse, do if bhsd else _to_bhsd(do),
        _zero_off(), _zero_off(),
        causal=causal, scale=scale, block_q=bwd_block_q, block_k=bwd_block_k,
        interpret=interpret,
    )
    if bhsd:
        return dq, dk, dv
    return _to_bhsd(dq), _to_bhsd(dk), _to_bhsd(dv)


_flash.defvjp(_flash_fwd, _flash_bwd)


@jax.named_scope(names.FLASH_ATTENTION)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    bwd_block_q: Optional[int] = None,
    bwd_block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    layout: str = "bshd",
) -> jax.Array:
    """Multi-head flash attention. q,k,v: [B, S, H, hd] → [B, S, H, hd]
    (layout="bshd", the default) or [B, H, S, hd] in and out
    (layout="bhsd" — the kernels' native layout; callers that can produce
    head-major tensors directly skip the boundary transposes entirely, worth
    ~3% of a GPT-2 train step on v5e).

    The q/kv tile, forward and backward separately, is choose_tiling's, from
    the shapes. The block_* keywords are explicit overrides of it: block_q /
    block_k the forward's tiles; a bwd_* left None follows its forward twin.

    Differentiable (custom VJP, flash backward). On non-TPU backends the
    kernels run in Pallas interpreter mode so tests validate the same code.
    """
    if layout not in ("bshd", "bhsd"):
        raise ValueError(f"unknown layout {layout!r}")
    if interpret is None:
        _, interpret = resolve_attention()
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _flash(
        q, k, v, causal, scale, block_q, block_k,
        bwd_block_q or block_q, bwd_block_k or block_k,
        interpret, layout == "bhsd",
    )


def batch_head_axes(mesh, batch: int, heads: int):
    """Mesh axes an activation's batch and head dims are split over inside a
    shard_map: batch over whichever of (dp, fsdp) divide it, heads over tp
    when it divides — parallel/sharding.py's activation layout. Axes that do
    not divide are dropped (replicated) so small test shapes work on any
    mesh; model-size shapes shard fully. Returns (batch_axes | None, head_axis
    | None)."""
    batch_axes = []
    rem = batch
    for ax in ("dp", "fsdp"):
        sz = mesh.shape.get(ax, 1)
        if sz > 1 and rem % sz == 0:
            batch_axes.append(ax)
            rem //= sz
    head_ax = "tp" if heads % mesh.shape.get("tp", 1) == 0 else None
    return tuple(batch_axes) or None, head_ax


@jax.named_scope(names.FLASH_ATTENTION)
def flash_attention_sharded(q, k, v, mesh, **kwargs) -> jax.Array:
    """flash_attention for callers under jit/GSPMD (the model forward).
    q, k, v: GLOBAL [B, H, S, hd] in and out; kwargs as flash_attention's.

    GSPMD cannot partition a Mosaic custom call: under a jit over more than
    one device the bare pallas_call does not lower at all ("Mosaic kernels
    cannot be automatically partitioned. Please wrap the call in a
    shard_map"). The shard_map hands each device its own
    [B/(dp·fsdp), H/tp, S, hd] shard. The sequence stays whole per device
    (a cp axis belongs to ring_attention_sharded)."""
    if mesh is None:
        return flash_attention(q, k, v, layout="bhsd", **kwargs)
    if kwargs.get("interpret") is None:
        _, kwargs["interpret"] = resolve_attention(mesh=mesh)
    batch_axes, head_ax = batch_head_axes(mesh, q.shape[0], q.shape[1])
    spec = P(batch_axes, head_ax, None, None)
    fn = jax.shard_map(
        functools.partial(flash_attention, layout="bhsd", **kwargs),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


@jax.named_scope(names.FLASH_ATTENTION)
def flash_attention_with_lse(
    q, k, v, q_offset, kv_offset, *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Forward-only flash attention returning (out [B,S,H,hd], lse [B,H,S])
    with GLOBAL position offsets — the building block for ring attention's
    per-step chunk computation (ops/ring_attention.py merges partials by lse).
    """
    if interpret is None:
        _, interpret = resolve_attention()
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    q_off = jnp.asarray([q_offset], jnp.int32).reshape(1)
    kv_off = jnp.asarray([kv_offset], jnp.int32).reshape(1)
    o, lse = _mha_forward_bhsd(
        _to_bhsd(q), _to_bhsd(k), _to_bhsd(v), q_off, kv_off,
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return _to_bhsd(o), lse


@jax.named_scope(names.FLASH_ATTENTION)
def mha_backward_chunk(
    q, k, v, o, lse, do, q_offset, kv_offset, *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Backward for one (q-chunk, kv-chunk) pair with global offsets; returns
    (dq, dk, dv) contributions (all [B,S,H,hd]). `lse` is the GLOBAL logsumexp
    over all chunks. Used by ring attention's backward ring pass."""
    if interpret is None:
        _, interpret = resolve_attention()
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    q_off = jnp.asarray([q_offset], jnp.int32).reshape(1)
    kv_off = jnp.asarray([kv_offset], jnp.int32).reshape(1)
    dq, dk, dv = _mha_backward_bhsd(
        _to_bhsd(q), _to_bhsd(k), _to_bhsd(v), _to_bhsd(o), lse,
        _to_bhsd(do), q_off, kv_off,
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return _to_bhsd(dq), _to_bhsd(dk), _to_bhsd(dv)
