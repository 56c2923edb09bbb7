"""The passes over the n-stream carry around a hyper-connected sublayer.

``models/hyper_connections.py`` defines the residual path of n streams: a
sublayer reads the pre-mix ``u = Σ_i H_pre[i] · x[i]`` of the carry x
``[B, S, n·C]`` and its float32 output y is written back to every stream,
``x'[i] = Σ_j H_res[i, j] · x[j] + H_post[i] · y``, by maps that come from the
token's own streams (``m = (vec(x) · Φ) / rms(vec(x))``). All of it is
elementwise work and one thin product over a carry of n·C numbers a token —
28,672 bytes at the Xing4.0 cell's 4 × 3,584 in bf16 — so what it costs is how
often the carry crosses HBM. Left to XLA it crossed once a consumer: the RMS,
the Φ product, the pre-mix and the write-back each read x forward, the
write-back's streams were put together by a ``concatenate``, and backward d x
was three full-width tensors written and then added (PERF.md §6, PR 57).

So the passes are two Pallas kernel pairs behind ``jax.custom_vjp``, a tile
of tokens at the carry's whole width a grid step:

``mix`` (``mhc_mix_fwd`` / ``mhc_mix_bwd``)
    x in ONCE: the Φ product (Φᵀ in the stream's dtype, float32 accumulator),
    the sum of squares, ``m = logits · rsqrt(mean + ε)``, ``H_pre = σ(α_pre ·
    m[0:n] + b_pre)`` and u from the same tile. Out: u, and the normalised
    logits as float32 planes ``[n² + 2n, B, S]`` with the tokens minor, from
    which XLA makes H_post and the Sinkhorn rounds' H_res (16 planes, and XLA
    differentiates them). It also hands x THROUGH — the forward returns x
    itself, no copy — so that the carry's cotangent reaches ONE kernel: the
    backward takes the write-back's ``H_resᵀ · d x'`` as its incoming
    cotangent and writes ``d x = incoming + H_pre ⊗ d u + (d m / rms) · Φᵀ −
    the RMS's own term`` summed in float32, once; d Φ accumulates in one
    resident float32 block over the token tiles, d α_pre and d b_pre are
    partial sums a tile.
``write_back`` (``mhc_write_fwd`` / ``mhc_write_bwd``)
    x, y and the maps' planes in, each stream of x' written into its lanes of
    the one output block; backward d x', x and y in, ``d y = Σ_i H_post[i] ·
    d x'[i]``, the carry's part ``H_resᵀ · d x'`` in the stream's dtype, and
    ``d H_post[i] = ⟨d x'[i], y⟩``, ``d H_res[i, j] = ⟨d x'[i], x[j]⟩`` as
    planes.

Inside a kernel a token's scalars meet its channels as columns: the planes of
a 128-token block are transposed to tokens-major ``[128, 128]`` once (rows
past the planes' are zeros) and a column of that is spread along the lanes;
the products with Φᵀ take the whole tile (the matrix unit wants 128 rows a
weight tile), everything else runs ``_ROWS`` tokens at a time in a loop, so
that a body is a row group's code and not a tile's. Everything float32 in the
plain functions is float32 here — the statistic, the logits' accumulator, the
sigmoid, both mixes' sums, d x's sum — and each written tensor is rounded
once. The kernels take a width C of whole lane tiles (the model file's stated
precondition, which a config is held to) and at most as many streams as put
the maps' planes in one lane tile of columns (n ≤ 10); the plain functions of
the model file are the definition the tests hold them to, not a second path.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as PSpec

from ray_tpu.ops.attention import (
    VMEM_BUDGET_BYTES, VMEM_CEILING_BYTES, batch_head_axes, record_decision,
    resolve_attention, vmem_block_bytes)
from ray_tpu.parallel import mesh as mesh_lib
from ray_tpu.tracing import names as scopes

_LANES = 128
# the tokens a body's loop takes at a time, and the lanes of them its
# float32 values are wide: [_ROWS, _CHANNELS] is 16 vector registers
_ROWS = 32
_CHANNELS = 512
# the token tile the rule reaches for (a multiple of _LANES: the planes'
# tokens are a block's lanes)
_TARGET_TOKENS = 256
KERNELS = ("mix_fwd", "mix_bwd", "write_fwd", "write_bwd")
_NAMES = dict(zip(KERNELS, (
    scopes.MHC_MIX_FWD_KERNEL, scopes.MHC_MIX_BWD_KERNEL,
    scopes.MHC_WRITE_FWD_KERNEL, scopes.MHC_WRITE_BWD_KERNEL)))

_decisions: Dict[tuple, Dict[str, Any]] = {}


class MhcTiling(NamedTuple):
    token_tile: int           # tokens a grid step takes, at the whole width
    vmem_estimate: int        # bytes, _vmem_estimate() of this choice


def mhc_tiling_decisions() -> List[Dict[str, Any]]:
    """Every distinct decision this process has traced a hyper-connection
    with, as the ``ops/mhc_tiling`` events carry them."""
    return list(_decisions.values())


def _plane_rows(n: int) -> int:
    """Rows of a planes block: the n² + 2n logits and 1 / rms, in whole
    sublane tiles of either dtype."""
    return -(-(n * n + 2 * n + 1) // 16) * 16


def _vmem_estimate(kernel: str, T: int, n: int, C: int, a: int) -> int:
    """VMEM bytes one grid step needs: every in/out block twice (Pallas
    double-buffers them), the scratch once. An upper bound, not Mosaic's own
    figure."""
    blk, W, P = vmem_block_bytes, n * C, _plane_rows(n)
    carry, planes, major = blk((T, W), a), blk((P, T), 4), blk((T, _LANES), 4)
    phit = blk((_LANES, W), a)
    if kernel == "mix_fwd":
        return 2 * (carry + blk((T, C), a) + planes + phit) + major
    if kernel == "write_fwd":
        return 2 * (2 * carry + blk((T, C), 4) + planes) + major
    if kernel == "write_bwd":
        return 2 * (3 * carry + 2 * blk((T, C), 4) + 2 * planes) + 2 * major
    return (2 * (3 * carry + blk((T, C), a) + 2 * planes + phit
                 + blk((P, W), 4)) + 3 * major + blk((T, W), 4))


def choose_mhc_tiling(kernel: str, tokens: int, n: int, C: int,
                      dtype_bytes: int) -> MhcTiling:
    """THE rule for how a hyper-connection kernel tiles its work, from the
    shapes. A grid step is ``token_tile`` tokens at the carry's whole width:
    the largest multiple of _LANES that divides the tokens, at most
    _TARGET_TOKENS, whose estimate fits half of what a kernel may be given
    (VMEM_CEILING_BYTES; past Mosaic's default the call raises its limit, as
    the conv pair's does) — _LANES where the tokens are not whole lane tiles
    (the caller pads them: the test shapes alone)."""
    if kernel not in KERNELS:
        raise ValueError(f"unknown hyper-connection kernel {kernel!r}")
    if C % _LANES or _plane_rows(n) > _LANES:
        raise ValueError(
            f"hyper-connection {kernel}: {n} streams {C} wide: a stream is "
            f"whole lane tiles ({_LANES}) and the maps' {n * n + 2 * n + 1} "
            f"planes fit one")
    estimate = functools.partial(_vmem_estimate, kernel, n=n, C=C,
                                 a=dtype_bytes)
    tiles = [t for t in range(_TARGET_TOKENS, 0, -_LANES)
             if tokens % t == 0] or [_LANES]
    fit = [t for t in tiles if estimate(t) <= VMEM_CEILING_BYTES // 2]
    if not fit:
        raise ValueError(
            f"hyper-connection {kernel}: {_LANES} tokens of {n} streams "
            f"{C} wide ({dtype_bytes}-byte) do not fit VMEM: estimated at "
            f"{estimate(_LANES)} bytes of {VMEM_CEILING_BYTES // 2}")
    tiling = MhcTiling(fit[0], estimate(fit[0]))
    record_decision(_decisions, scopes.MHC_TILING, dict(zip(
        scopes.MHC_TILING_ARGS, (kernel, tokens, n, C) + tuple(tiling))))
    return tiling


# ------------------------------------------------------------ kernel bodies
def _sigmoid(z):
    # (written out: what ``lax.logistic`` is, from ops every Mosaic has)
    return 1.0 / (1.0 + jnp.exp(-z))


def _fold(v):
    """v [R, k·128] → the sum of its lane tiles [R, 128]."""
    return sum(v[:, lo:lo + _LANES] for lo in range(0, v.shape[1], _LANES))


def _column(v, k: int, lane):
    """Column k of v [R, 128] as [R, 1] (a masked sum along the lanes)."""
    return jnp.sum(jnp.where(lane == k, v, 0.0), axis=1, keepdims=True)


def _columns(sums, lane):
    """sums, a list of [R, 128] → [R, 128] whose column k is the lane sum of
    sums[k], zeros after the last."""
    return sum(jnp.where(lane == k, jnp.sum(v, axis=1, keepdims=True), 0.0)
               for k, v in enumerate(sums))


def _tokens_major(planes_ref, major):
    """The planes of a tile [P, T] into ``major`` [T, 128] float32: a token a
    row, its planes the first P columns, zeros after — a 128-token block's
    whole [128, 128] transpose at a time."""
    P, T = planes_ref.shape
    rest = jnp.zeros((_LANES - P, _LANES), jnp.float32)
    for lo in range(0, T, _LANES):
        major[lo:lo + _LANES, :] = jnp.concatenate(
            [planes_ref[:, lo:lo + _LANES], rest], axis=0).T


def _planes_of(major, P: int):
    """_tokens_major undone: the first P columns of [T, 128] as [P, T]."""
    return jnp.concatenate(
        [major[lo:lo + _LANES, :].astype(jnp.float32).T[:P]
         for lo in range(0, major.shape[0], _LANES)], axis=1)


def _row_groups(T: int, body, init=0):
    """``body(rows, carry)`` over a tile's tokens, _ROWS at a time."""
    def step(g, carry):
        return body(pl.ds(pl.multiple_of(g * _ROWS, _ROWS), _ROWS), carry)
    return lax.fori_loop(0, T // _ROWS, step, init)


def _chunks(C: int):
    tc = max(c for c in range(_LANES, min(C, _CHANNELS) + 1, _LANES)
             if C % c == 0)
    return [slice(lo, lo + tc) for lo in range(0, C, tc)]


def _stream(i: int, C: int, part: slice) -> slice:
    return slice(i * C + part.start, i * C + part.stop)


def _mix_fwd_kernel(x_ref, phit_ref, ab_ref, u_ref, planes_ref, major, *,
                    n: int, C: int, eps: float):
    """One tile of tokens. x [T, n·C]; Φᵀ [128, n·C] (rows past n² + 2n
    zeros); ab [8, 128] float32 (row 0 α_pre, row 1 b_pre, in columns 0 … n−1)
    → u [T, C] and the planes [P, T] float32: m, then 1 / rms."""
    f = jnp.float32
    T, W, M = x_ref.shape[0], n * C, n * n + 2 * n
    major[...] = lax.dot_general(x_ref[...], phit_ref[...],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=f)
    lane = lax.broadcasted_iota(jnp.int32, (_ROWS, _LANES), 1)

    def group(rows, carry):
        squares = jnp.zeros((_ROWS, _LANES), f)
        for part in _chunks(W):
            xf = x_ref[rows, part].astype(f)
            squares += _fold(xf * xf)
        inv = lax.rsqrt(jnp.sum(squares, axis=1, keepdims=True) / W + eps)
        m = major[rows, :] * inv
        pre = _sigmoid(m * ab_ref[0:1, :] + ab_ref[1:2, :])
        major[rows, :] = jnp.where(lane == M, inv, m)
        h = [_column(pre, i, lane) for i in range(n)]
        for part in _chunks(C):
            u = sum(h[i] * x_ref[rows, _stream(i, C, part)].astype(f)
                    for i in range(n))
            u_ref[rows, part] = u.astype(u_ref.dtype)
        return carry

    _row_groups(T, group)
    planes_ref[...] = _planes_of(major, planes_ref.shape[0])


def _mix_bwd_kernel(x_ref, dxin_ref, du_ref, planes_ref, dm_ref, phit_ref,
                    ab_ref, dx_ref, dphit_ref, dab_ref, major, dmajor, dl,
                    prod, *, n: int, C: int):
    """The same tile's gradients. Besides the forward's operands: the carry's
    incoming cotangent [T, n·C], d u [T, C], the forward's planes and the
    planes' cotangent [P, T] (what the other maps sent back) → d x [T, n·C],
    d Φᵀ [P, n·C] float32 (ONE block, summed over the tiles) and this tile's
    sums for d b_pre (row 0) and d α_pre (row 1) [8, 128]."""
    f = jnp.float32
    T, W, M = x_ref.shape[0], n * C, n * n + 2 * n
    _tokens_major(planes_ref, major)
    _tokens_major(dm_ref, dmajor)
    lane = lax.broadcasted_iota(jnp.int32, (_ROWS, _LANES), 1)
    alpha = ab_ref[0:1, :]

    def first(rows, sums):
        saved = major[rows, :]
        inv = _column(saved, M, lane)
        m = jnp.where(lane < M, saved, 0.0)
        pre = _sigmoid(m * alpha + ab_ref[1:2, :])
        dots = [jnp.zeros((_ROWS, _LANES), f) for _ in range(n)]
        for part in _chunks(C):
            du = du_ref[rows, part].astype(f)
            for i in range(n):
                dots[i] += _fold(
                    du * x_ref[rows, _stream(i, C, part)].astype(f))
        dz = _columns(dots, lane) * pre * (1.0 - pre)   # zero past column n − 1
        dm = dmajor[rows, :] + alpha * dz
        rms_term = inv * inv * jnp.sum(dm * m, axis=1, keepdims=True) / W
        dl[rows, :] = (dm * inv).astype(dl.dtype)
        # what the second loop reads of this one: H_pre, and the RMS's term
        major[rows, :] = jnp.where(lane == M, rms_term,
                                   jnp.where(lane < n, pre, 0.0))
        return (sums[0] + jnp.sum(dz, axis=0, keepdims=True),
                sums[1] + jnp.sum(dz * m, axis=0, keepdims=True))

    zero = jnp.zeros((1, _LANES), f)
    d_bias, d_alpha = _row_groups(T, first, (zero, zero))
    dab_ref[...] = jnp.zeros(dab_ref.shape, f)
    dab_ref[0:1, :] = d_bias
    dab_ref[1:2, :] = d_alpha

    prod[...] = jnp.dot(dl[...], phit_ref[...], preferred_element_type=f)
    dlt = _planes_of(dl, planes_ref.shape[0]).astype(dl.dtype)

    @pl.when(pl.program_id(0) == 0)
    def _():
        dphit_ref[...] = jnp.zeros(dphit_ref.shape, f)

    dphit_ref[...] += jnp.dot(dlt, x_ref[...], preferred_element_type=f)

    def second(rows, carry):
        saved = major[rows, :]
        h = [_column(saved, i, lane) for i in range(n)]
        rms_term = _column(saved, M, lane)
        for part in _chunks(C):
            du = du_ref[rows, part].astype(f)
            for i in range(n):
                at = _stream(i, C, part)
                dx = (dxin_ref[rows, at].astype(f) + h[i] * du
                      + prod[rows, at]
                      - x_ref[rows, at].astype(f) * rms_term)
                dx_ref[rows, at] = dx.astype(dx_ref.dtype)
        return carry

    _row_groups(T, second)


def _write_fwd_kernel(x_ref, y_ref, maps_ref, out_ref, major, *, n: int,
                      C: int):
    """One tile of tokens. x [T, n·C], y [T, C], the maps' planes [P, T]
    float32 (H_res row-major, then H_post) → x' [T, n·C]."""
    f = jnp.float32
    _tokens_major(maps_ref, major)
    lane = lax.broadcasted_iota(jnp.int32, (_ROWS, _LANES), 1)

    def group(rows, carry):
        maps = major[rows, :]
        h = [_column(maps, k, lane) for k in range(n * n + n)]
        for part in _chunks(C):
            xs = [x_ref[rows, _stream(j, C, part)].astype(f) for j in range(n)]
            y = y_ref[rows, part].astype(f)
            for i in range(n):
                out = (sum(h[i * n + j] * xs[j] for j in range(n))
                       + h[n * n + i] * y)
                out_ref[rows, _stream(i, C, part)] = out.astype(out_ref.dtype)
        return carry

    _row_groups(x_ref.shape[0], group)


def _write_bwd_kernel(x_ref, y_ref, maps_ref, dout_ref, dx_ref, dy_ref,
                      dmaps_ref, major, dmajor, *, n: int, C: int):
    """The same tile's gradients: d x' [T, n·C] beside the forward's operands
    → the carry's part H_resᵀ · d x' [T, n·C], d y [T, C], and the maps'
    cotangents as planes [P, T] float32 in the maps' order."""
    f = jnp.float32
    _tokens_major(maps_ref, major)
    lane = lax.broadcasted_iota(jnp.int32, (_ROWS, _LANES), 1)

    def group(rows, carry):
        maps = major[rows, :]
        h = [_column(maps, k, lane) for k in range(n * n + n)]
        dots = [jnp.zeros((_ROWS, _LANES), f) for _ in range(n * n + n)]
        for part in _chunks(C):
            ds = [dout_ref[rows, _stream(i, C, part)].astype(f)
                  for i in range(n)]
            xs = [x_ref[rows, _stream(j, C, part)].astype(f) for j in range(n)]
            y = y_ref[rows, part].astype(f)
            dy_ref[rows, part] = sum(
                h[n * n + i] * ds[i] for i in range(n)).astype(dy_ref.dtype)
            for j in range(n):
                dx_ref[rows, _stream(j, C, part)] = sum(
                    h[i * n + j] * ds[i] for i in range(n)).astype(dx_ref.dtype)
            for i in range(n):
                dots[n * n + i] += _fold(ds[i] * y)
                for j in range(n):
                    dots[i * n + j] += _fold(ds[i] * xs[j])
        dmajor[rows, :] = _columns(dots, lane)
        return carry

    _row_groups(x_ref.shape[0], group)
    dmaps_ref[...] = _planes_of(dmajor, dmaps_ref.shape[0])


# ------------------------------------------------------------------ the calls
@functools.partial(jax.jit, static_argnames=("kernel", "n", "eps", "interpret"))
def _call(kernel: str, *args, n: int, eps: float = 0.0, interpret: bool):
    """The pallas_call of one kernel over grid (token tiles,). The carry-wide
    operands are [tokens, n·C], the d-wide [tokens, C], planes [P, tokens]
    float32, the tokens whole tiles (_whole_tiles). A jit of its own, as the
    conv pair's (short_conv._call): a step traces each kernel a run of
    layers, a sublayer, and the forward once more in the recompute — the
    bodies are then traced once a shape."""
    x = args[0]
    tokens, W = x.shape
    C, P, a = W // n, _plane_rows(n), x.dtype.itemsize
    T, estimate = choose_mhc_tiling(kernel, tokens, n, C, a)
    f = jnp.float32
    carry = pl.BlockSpec((T, W), lambda t: (t, 0))
    wide = pl.BlockSpec((T, C), lambda t: (t, 0))
    planes = pl.BlockSpec((P, T), lambda t: (0, t))
    whole = lambda shape: pl.BlockSpec(shape, lambda t: (0,) * len(shape))
    major = pltpu.VMEM((T, _LANES), f)
    like = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype)
    plane_shape = jax.ShapeDtypeStruct((P, tokens), f)
    order = "parallel"
    if kernel == "mix_fwd":          # x, Φᵀ, ab
        body = functools.partial(_mix_fwd_kernel, eps=eps)
        in_specs = [carry, whole((_LANES, W)), whole((8, _LANES))]
        out_specs = (wide, planes)
        out_shape = (jax.ShapeDtypeStruct((tokens, C), x.dtype), plane_shape)
        scratch = [major]
    elif kernel == "mix_bwd":        # x, d x in, d u, planes, d planes, Φᵀ, ab
        body = _mix_bwd_kernel
        in_specs = [carry, carry, wide, planes, planes, whole((_LANES, W)),
                    whole((8, _LANES))]
        out_specs = (carry, whole((P, W)),
                     pl.BlockSpec((None, 8, _LANES), lambda t: (t, 0, 0)))
        out_shape = (like(x), jax.ShapeDtypeStruct((P, W), f),
                     jax.ShapeDtypeStruct((tokens // T, 8, _LANES), f))
        scratch = [major, major, pltpu.VMEM((T, _LANES), x.dtype),
                   pltpu.VMEM((T, W), f)]
        order = "arbitrary"          # d Φᵀ is one block, summed over the tiles
    elif kernel == "write_fwd":      # x, y, maps
        body = _write_fwd_kernel
        in_specs, out_specs, out_shape = [carry, wide, planes], carry, like(x)
        scratch = [major]
    else:                            # x, y, maps, d x'
        body = _write_bwd_kernel
        in_specs = [carry, wide, planes, carry]
        out_specs = (carry, wide, planes)
        out_shape = (like(x), like(args[1]), plane_shape)
        scratch = [major, major]
    return pl.pallas_call(
        functools.partial(body, n=n, C=C), grid=(tokens // T,),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(order,),
            vmem_limit_bytes=None if estimate <= VMEM_BUDGET_BYTES else min(
                VMEM_CEILING_BYTES, estimate + estimate // 2)),
        interpret=interpret, name=_NAMES[kernel])(*args)


def _whole_tiles(t, axis: int):
    """t with its tokens (``axis``) a whole number of tiles, zeros behind (a
    copy, and only the test shapes take it: a token of zeros mixes to zeros
    and sends every sum nothing)."""
    pad = -t.shape[axis] % _LANES
    if not pad:
        return t
    return jnp.pad(t, [(0, pad if a == axis else 0) for a in range(t.ndim)])


def _flat(t):
    """[B, S, width] → [tokens in whole tiles, width]."""
    return _whole_tiles(t.reshape(-1, t.shape[-1]), 0)


def _planes(t, n: int):
    """[k, B, S] float32 planes → [P, tokens in whole tiles]."""
    t = t.reshape(t.shape[0], -1).astype(jnp.float32)
    return _whole_tiles(jnp.pad(t, ((0, _plane_rows(n) - t.shape[0]), (0, 0))),
                        1)


def _phit(phi, n: int, dtype):
    """Φ [n·C, n² + 2n] as the kernels take it: transposed, in the stream's
    dtype, a lane tile of rows."""
    return jnp.pad(phi.T.astype(dtype), ((0, _LANES - phi.shape[1]), (0, 0)))


def _ab(alpha_pre, bias_pre):
    """α_pre (a scalar) and b_pre [n] as the kernels take them: [8, 128]
    float32, row 0 α_pre and row 1 b_pre in columns 0 … n − 1."""
    n = bias_pre.shape[0]
    rows = jnp.stack([jnp.full((n,), alpha_pre, jnp.float32),
                      bias_pre.astype(jnp.float32)])
    return jnp.pad(rows, ((0, 6), (0, _LANES - n)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _mix(x, phi, alpha_pre, bias_pre, eps, interpret):
    return _mix_fwd(x, phi, alpha_pre, bias_pre, eps, interpret)[0]


def _mix_fwd(x, phi, alpha_pre, bias_pre, eps, interpret):
    n, (B, S, W) = bias_pre.shape[0], x.shape
    M = n * n + 2 * n
    phit, ab = _phit(phi, n, x.dtype), _ab(alpha_pre, bias_pre)
    u, planes = _call("mix_fwd", _flat(x), phit, ab, n=n, eps=eps,
                      interpret=interpret)
    out = (x, u[:B * S].reshape(B, S, W // n),
           planes[:M, :B * S].reshape(M, B, S))
    return out, (x, phi, alpha_pre, bias_pre, phit, ab, planes)


def _mix_bwd(eps, interpret, res, cotangents):
    x, phi, alpha_pre, bias_pre, phit, ab, planes = res
    dxin, du, dm = cotangents
    n, (B, S, W) = bias_pre.shape[0], x.shape
    dx, dphit, dab = _call("mix_bwd", _flat(x), _flat(dxin), _flat(du), planes,
                           _planes(dm, n), phit, ab, n=n, interpret=interpret)
    dab = dab.sum(0)
    return (dx[:B * S].reshape(x.shape),
            dphit[:phi.shape[1]].T.astype(phi.dtype),
            dab[1, :n].sum().astype(alpha_pre.dtype),
            dab[0, :n].astype(bias_pre.dtype))


_mix.defvjp(_mix_fwd, _mix_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _write(x, y, post, res, interpret):
    return _write_fwd(x, y, post, res, interpret)[0]


def _write_fwd(x, y, post, res, interpret):
    n, tokens = post.shape[0], x.shape[0] * x.shape[1]
    maps = _planes(jnp.concatenate([res.reshape((n * n,) + post.shape[1:]),
                                    post]), n)
    out = _call("write_fwd", _flat(x), _flat(y), maps, n=n,
                interpret=interpret)
    return out[:tokens].reshape(x.shape), (x, y, maps)


def _write_bwd(interpret, saved, dout):
    x, y, maps = saved
    n, (B, S, _) = x.shape[-1] // y.shape[-1], x.shape
    dx, dy, dmaps = _call("write_bwd", _flat(x), _flat(y), maps, _flat(dout),
                          n=n, interpret=interpret)
    dmaps = dmaps[:, :B * S]
    return (dx[:B * S].reshape(x.shape), dy[:B * S].reshape(y.shape),
            dmaps[n * n:n * n + n].reshape(n, B, S),
            dmaps[:n * n].reshape(n, n, B, S))


_write.defvjp(_write_fwd, _write_bwd)


def _on_mesh(fn, batch_axis, out_batch_axis, *args):
    """``fn(*args)``, under a mesh of more than one device in a shard_map
    that gives each device its own rows at the whole width. ``batch_axis``
    says where each argument (``out_batch_axis``: each result) has its rows;
    None is the same on every device."""
    mesh = mesh_lib.current_mesh()
    if mesh is None or mesh.devices.size == 1:
        return fn(*args)
    batch_axes, _ = batch_head_axes(mesh, args[0].shape[0], 1)
    rows = lambda axis: (PSpec() if axis is None
                         else PSpec(*[None] * axis, batch_axes))
    out_specs = (rows(out_batch_axis) if isinstance(out_batch_axis, int)
                 else tuple(map(rows, out_batch_axis)))
    return jax.shard_map(fn, mesh=mesh, in_specs=tuple(map(rows, batch_axis)),
                         out_specs=out_specs, check_vma=False)(*args)


def _interpret() -> bool:
    return resolve_attention(mesh=mesh_lib.current_mesh())[1]


def mix(x: jax.Array, phi: jax.Array, alpha_pre: jax.Array,
        bias_pre: jax.Array, eps: float):
    """x [B, S, n·C], Φ [n·C, n² + 2n] in x's dtype, α_pre (a scalar) and
    b_pre [n] → (x — the same array, for the write-back to read: the carry's
    cotangent then comes back through this op's backward, once —, u [B, S, C]
    = Σ_i σ(α_pre · m[i] + b_pre[i]) · x[i] in x's dtype, m [n² + 2n, B, S]
    float32 = (vec(x) · Φ) · rsqrt(mean(vec(x)²) + eps))."""
    interpret = _interpret()
    fn = lambda x, phi, a, b: _mix(x, phi, a, b, eps, interpret)
    return _on_mesh(fn, (0, None, None, None), (0, 0, 1), x, phi, alpha_pre,
                    bias_pre)


def write_back(x: jax.Array, y: jax.Array, post: jax.Array, res: jax.Array
               ) -> jax.Array:
    """x [B, S, n·C], y [B, S, C], H_post [n, B, S] and H_res [n, n, B, S]
    float32 → x' [B, S, n·C], x'[i] = Σ_j H_res[i, j] · x[j] + H_post[i] · y
    in x's dtype."""
    interpret = _interpret()
    fn = lambda x, y, post, res: _write(x, y, post, res, interpret)
    return _on_mesh(fn, (0, 0, 1, 2), 0, x, y, post, res)
