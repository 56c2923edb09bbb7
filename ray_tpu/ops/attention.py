"""Flash attention for TPU as Pallas kernels (fwd + bwd, causal, custom VJP).

This is the perf-critical op the XLA fallback can't match: XLA materializes the
[S, S] probability matrix as a backward residual per layer, forcing full remat
at GPT-2 batch sizes (see bench.py). The kernels below keep the online-softmax
running state (m, l, acc) in VMEM and never write probabilities to HBM; the
backward pass recomputes logits blockwise from (q, k, lse) the flash-attention
way.

Design notes (TPU-first):
- Kernels operate in [B, H, S, hd] layout so every block's minor dims are the
  (seq, head_dim) tile Mosaic requires ((8,128)-aligned or full-size); the
  public API takes [B, S, H, hd] and transposes at the boundary (XLA fuses the
  transpose into the surrounding projection matmuls).
- K/V live whole per (batch, head) in VMEM (S·hd·2B ≈ 128 KiB at S=1024 —
  VMEM is ~16 MiB), so the kv loop is VMEM-resident with no DMA choreography.
- Logits/softmax accumulate in f32 (MXU native via preferred_element_type);
  p·v and the backward matmuls run bf16→f32.
- The causal mask is computed from GLOBAL positions `q_offset`/`kv_offset`
  (scalar-prefetch args), so the same kernel serves single-device attention
  (offsets 0) and ring attention (per-step rotated offsets, ops/ring_attention).
- Backward = ONE fused kernel (grid over kv blocks, loop q): dk/dv written
  per kv block, dq accumulated in a VMEM-resident whole-row f32 block whose
  index map is constant in the kv grid dim — s/p/dp computed once per block
  pair instead of twice (the split dq + dkv formulation costs 7 matmuls and
  double the exp/mask work; fused is 5).
- lse/delta ride as [B, H, 1, S] so their (1, block) tiles satisfy the minor-
  dim rules; squeezed to [B, H, S] at the API edge.

No counterpart exists in the reference (it has no flash/SP story at all —
SURVEY.md §2.10); this is new TPU-native code.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ray_tpu.tracing import names

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
# Forward
# --------------------------------------------------------------------------- #

def _fwd_kernel(
    q_off_ref, kv_off_ref,            # scalar prefetch: global offsets [1]
    q_ref, k_ref, v_ref,              # [1, bh, bq, hd], [1, bh, Skv, hd] ×2
    *rest,                            # [mask_ref,] o_ref, lse_ref
    scale: float, causal: bool, block_q: int, block_k: int, kv_len: int,
    block_h: int = 1, mask_input: bool = False,
):
    if mask_input:
        mask_ref, o_ref, lse_ref = rest
    else:
        mask_ref = None
        o_ref, lse_ref = rest
    qi = pl.program_id(2)
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

    # heads are independent; processing block_h of them per grid step
    # amortizes the per-step grid/DMA overhead (the attention matmuls are
    # tiny at hd=64 — the kernel is overhead-bound, not FLOP-bound)
    for hh in range(block_h):
        # fold the softmax scale into q once — a per-block [bq, bk] f32
        # multiply otherwise rides every inner iteration
        q = q_ref[0, hh, :, :] * jnp.asarray(scale, q_ref.dtype)
        hd = q.shape[-1]

        m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((block_q, 1), jnp.float32)
        acc0 = jnp.zeros((block_q, hd), jnp.float32)

        def make_body(masked, hh=hh):
            def body(ki, carry):
                m, l, acc = carry
                k = k_ref[0, hh, pl.ds(ki * block_k, block_k), :]
                s = lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                if masked:
                    if mask_input:
                        # additive mask DMA'd per q-block (shared across the
                        # block_h heads): ONE vector add versus the 4 VPU
                        # passes of iota×2 + compare + select — the kernel is
                        # VPU-bound, so mask arithmetic is step time
                        s = s + mask_ref[0, :, pl.ds(ki * block_k, block_k)]
                    else:
                        rows = q_global + lax.broadcasted_iota(
                            jnp.int32, (block_q, block_k), 0
                        )
                        cols = (kv_off_ref[0] + ki * block_k
                                + lax.broadcasted_iota(
                                    jnp.int32, (block_q, block_k), 1))
                        s = jnp.where(rows >= cols, s, _NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new)
                l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
                v = v_ref[0, hh, pl.ds(ki * block_k, block_k), :]
                acc = acc * alpha + lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                return m_new, l, acc
            return body

        carry = lax.fori_loop(0, num_full, make_body(False), (m0, l0, acc0))
        m, l, acc = lax.fori_loop(
            num_full, num_blocks, make_body(causal), carry
        )
        # rows with no valid kv (ring attention future chunks): l == 0 →
        # output 0, lse = -inf-ish so the ring merge gives them zero weight.
        l_safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, hh, :, :] = (acc / l_safe).astype(o_ref.dtype)
        lse = jnp.where(
            l[:, 0] > 0, m[:, 0] + jnp.log(l_safe[:, 0]), _NEG_INF
        )
        lse_ref[0, hh, 0, :] = lse


def _mha_forward_bhsd(
    q, k, v, q_offset, kv_offset, *,
    causal: bool, scale: float, block_q: int, block_k: int,
    interpret: bool, block_h: int = 1, mask_ok: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """q,k,v: [B, H, S, hd] → (o [B,H,S,hd], lse [B,H,S])."""
    B, H, Sq, hd = q.shape
    Skv = k.shape[2]
    bq = _pick_block(Sq, block_q)
    bk = _pick_block(Skv, block_k)
    bh = block_h if block_h > 0 and H % block_h == 0 else 1
    grid = (B, H // bh, Sq // bq)
    # Precomputed additive causal mask, only valid for zero offsets (the
    # single-device path — ring attention passes live offsets and keeps the
    # in-kernel iota mask). Head-independent: one [bq, Skv] plane per
    # q-block index, DMA'd once per grid step and shared by all bh heads.
    # Only worth it when several heads amortize the DMA and the [Sq, Skv]
    # f32 plane stays small — at long sequences (e.g. LLaMA S=4096 → 64 MB)
    # streaming the mask costs more bandwidth than the iota path costs VPU.
    mask_input = causal and mask_ok and bh > 1 and Sq * Skv <= 2 ** 21
    operands = [q_offset, kv_offset, q, k, v]
    in_specs = [
        pl.BlockSpec((1, bh, bq, hd), lambda b, h, i, *_: (b, h, i, 0)),
        pl.BlockSpec((1, bh, Skv, hd), lambda b, h, i, *_: (b, h, 0, 0)),
        pl.BlockSpec((1, bh, Skv, hd), lambda b, h, i, *_: (b, h, 0, 0)),
    ]
    if mask_input:
        rows = jnp.arange(Sq)[:, None]
        cols = jnp.arange(Skv)[None, :]
        mask = jnp.where(rows >= cols, 0.0, _NEG_INF).astype(jnp.float32)
        operands.append(mask.reshape(Sq // bq, bq, Skv))
        in_specs.append(
            pl.BlockSpec((1, bq, Skv), lambda b, h, i, *_: (i, 0, 0))
        )

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=bq, block_k=bk, kv_len=Skv, block_h=bh,
        mask_input=mask_input,
    )
    out_shape = [
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        jax.ShapeDtypeStruct((B, H, 1, Sq), jnp.float32),
    ]
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, bh, bq, hd), lambda b, h, i, *_: (b, h, i, 0)),
                pl.BlockSpec((1, bh, 1, bq), lambda b, h, i, *_: (b, h, 0, i)),
            ],
        ),
        out_shape=out_shape,
        interpret=interpret,
        name=names.FLASH_FWD_KERNEL,
    )(*operands)
    return o, lse[:, :, 0, :]


# --------------------------------------------------------------------------- #
# Backward
# --------------------------------------------------------------------------- #

def _fused_bwd_kernel(
    q_off_ref, kv_off_ref,
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dk_ref, dv_ref,
    *, scale: float, causal: bool, block_q: int, block_k: int, q_len: int,
    block_h: int = 1,
):
    """Single-pass backward: grid over kv blocks; dk/dv written per block,
    dq accumulated into a whole-row VMEM-resident output (its index map is
    constant in the kv grid dim, so Pallas keeps the block live across
    iterations). Versus the split dq/dkv kernels this computes s, p and dp
    ONCE per (q, kv) block pair — 5 matmuls instead of 7 and half the
    exp/mask VPU work — worth ~25% of backward time at GPT-2 shapes."""
    ki = pl.program_id(2)
    nk_total = pl.num_programs(2)
    block_k_ = k_ref.shape[2]
    kv_global = kv_off_ref[0] + ki * block_k_

    @pl.when(ki == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    nq = q_len // block_q
    if causal:
        first = jnp.clip((kv_global - q_off_ref[0]) // block_q, 0, nq)
        first_full = jnp.clip(
            -((q_off_ref[0] - kv_global - block_k_ + 1) // block_q), 0, nq
        )
    else:
        first = 0
        first_full = 0

    scale_c = jnp.asarray(scale, q_ref.dtype)

    # heads are independent; block_h of them per grid step amortizes the
    # per-step grid/DMA overhead (see _fwd_kernel)
    for hh in range(block_h):
        k = k_ref[0, hh, :, :]
        v = v_ref[0, hh, :, :]
        hd = k.shape[-1]
        # dq contribution is ds @ (k*scale): folding the softmax scale into
        # k here is one [bk, hd] multiply per grid step instead of per-pair
        k_scaled = k * scale_c

        def make_body(masked, hh=hh, k=k, v=v, k_scaled=k_scaled):
            def body(qi, carry):
                dk, dv = carry
                qs = q_ref[0, hh, pl.ds(qi * block_q, block_q), :] * scale_c
                do = do_ref[0, hh, pl.ds(qi * block_q, block_q), :]
                lse = lse_ref[0, hh, 0, pl.ds(qi * block_q, block_q)][:, None]
                delta = delta_ref[0, hh, 0, pl.ds(qi * block_q, block_q)][:, None]
                s = lax.dot_general(
                    qs, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                if masked:
                    rows = q_off_ref[0] + qi * block_q + lax.broadcasted_iota(
                        jnp.int32, (block_q, block_k), 0
                    )
                    cols = kv_global + lax.broadcasted_iota(
                        jnp.int32, (block_q, block_k), 1
                    )
                    s = jnp.where(rows >= cols, s, _NEG_INF)
                p = jnp.exp(s - lse)                     # [bq, bk]
                dv = dv + lax.dot_general(
                    p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                dp = lax.dot_general(
                    do, v, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                ds = p * (dp - delta)
                dk = dk + lax.dot_general(
                    ds.astype(qs.dtype), qs, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                sl = pl.ds(qi * block_q, block_q)
                dq_ref[0, hh, sl, :] += lax.dot_general(
                    ds.astype(k.dtype), k_scaled, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ).astype(dq_ref.dtype)
                return dk, dv
            return body

        dk0 = jnp.zeros((block_k_, hd), jnp.float32)
        dv0 = jnp.zeros((block_k_, hd), jnp.float32)
        carry = lax.fori_loop(first, first_full, make_body(causal), (dk0, dv0))
        dk, dv = lax.fori_loop(first_full, nq, make_body(False), carry)
        dk_ref[0, hh, :, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, hh, :, :] = dv.astype(dv_ref.dtype)


def _mha_backward_bhsd(
    q, k, v, o, lse, do, q_offset, kv_offset, *,
    causal: bool, scale: float, block_q: int, block_k: int, interpret: bool,
    block_h: int = 1,
):
    """All tensors [B, H, S, hd]; lse [B, H, S]. Returns dq, dk, dv."""
    B, H, Sq, hd = q.shape
    Skv = k.shape[2]
    bq = _pick_block(Sq, block_q)
    bk = _pick_block(Skv, block_k)
    bh = block_h if block_h > 0 and H % block_h == 0 else 1

    # delta_i = rowsum(dO_i * O_i): cheap elementwise+reduce, XLA fuses it.
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )[:, :, None, :]                       # [B, H, 1, Sq]
    lse4 = lse[:, :, None, :]              # [B, H, 1, Sq]

    fused_kernel = functools.partial(
        _fused_bwd_kernel, scale=scale, causal=causal,
        block_q=bq, block_k=bk, q_len=Sq, block_h=bh,
    )
    # dq accumulates across kv grid steps → f32 output (bf16 accumulation
    # would drift with the number of kv blocks); cast at the end.
    dq_f32, dk, dv = pl.pallas_call(
        fused_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // bh, Skv // bk),
            in_specs=[
                pl.BlockSpec((1, bh, Sq, hd), lambda b, h, i, *_: (b, h, 0, 0)),
                pl.BlockSpec((1, bh, bk, hd), lambda b, h, i, *_: (b, h, i, 0)),
                pl.BlockSpec((1, bh, bk, hd), lambda b, h, i, *_: (b, h, i, 0)),
                pl.BlockSpec((1, bh, Sq, hd), lambda b, h, i, *_: (b, h, 0, 0)),
                pl.BlockSpec((1, bh, 1, Sq), lambda b, h, i, *_: (b, h, 0, 0)),
                pl.BlockSpec((1, bh, 1, Sq), lambda b, h, i, *_: (b, h, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, bh, Sq, hd), lambda b, h, i, *_: (b, h, 0, 0)),
                pl.BlockSpec((1, bh, bk, hd), lambda b, h, i, *_: (b, h, i, 0)),
                pl.BlockSpec((1, bh, bk, hd), lambda b, h, i, *_: (b, h, i, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, jnp.float32),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
        name=names.FLASH_BWD_KERNEL,
    )(q_offset, kv_offset, q, k, v, do, lse4, delta)
    return dq_f32.astype(q.dtype), dk, dv


# --------------------------------------------------------------------------- #
# Public API ([B, S, H, hd] boundary layout)
# --------------------------------------------------------------------------- #

def _to_bhsd(x):
    return jnp.swapaxes(x, 1, 2)


def _zero_off():
    return jnp.zeros((1,), jnp.int32)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11, 12)
)
def _flash(q, k, v, causal, scale, block_q, block_k, bwd_block_q,
           bwd_block_k, interpret, bhsd, block_h, bwd_block_h):
    o, _ = _mha_forward_bhsd(
        q if bhsd else _to_bhsd(q),
        k if bhsd else _to_bhsd(k),
        v if bhsd else _to_bhsd(v),
        _zero_off(), _zero_off(),
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret, block_h=block_h, mask_ok=True,
    )
    return o if bhsd else _to_bhsd(o)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, bwd_block_q,
               bwd_block_k, interpret, bhsd, block_h, bwd_block_h):
    if bhsd:
        qt, kt, vt = q, k, v
    else:
        qt, kt, vt = _to_bhsd(q), _to_bhsd(k), _to_bhsd(v)
    o, lse = _mha_forward_bhsd(
        qt, kt, vt, _zero_off(), _zero_off(),
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret, block_h=block_h, mask_ok=True,
    )
    return (o if bhsd else _to_bhsd(o)), (qt, kt, vt, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, bwd_block_q, bwd_block_k,
               interpret, bhsd, block_h, bwd_block_h, res, do):
    qt, kt, vt, o, lse = res
    dq, dk, dv = _mha_backward_bhsd(
        qt, kt, vt, o, lse, do if bhsd else _to_bhsd(do),
        _zero_off(), _zero_off(),
        causal=causal, scale=scale, block_q=bwd_block_q, block_k=bwd_block_k,
        interpret=interpret, block_h=bwd_block_h,
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
    block_q: int = 512,
    block_k: int = 512,
    bwd_block_q: Optional[int] = None,
    bwd_block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    layout: str = "bshd",
    block_h: int = 1,
    bwd_block_h: Optional[int] = None,
) -> jax.Array:
    """Multi-head flash attention. q,k,v: [B, S, H, hd] → [B, S, H, hd]
    (layout="bshd", the default) or [B, H, S, hd] in and out
    (layout="bhsd" — the kernels' native layout; callers that can produce
    head-major tensors directly skip the boundary transposes entirely, worth
    ~3% of a GPT-2 train step on v5e).

    block_h processes that many heads per grid step (must divide H; falls
    back to 1 otherwise). At small head_dim the kernels are grid-overhead
    bound, not FLOP bound — packing heads amortizes the per-step cost.

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
        interpret, layout == "bhsd", block_h, bwd_block_h or block_h,
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
    block_q: int = 512,
    block_k: int = 512,
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
    block_q: int = 512,
    block_k: int = 512,
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
