"""The double-gated short convolution of the LFM2 family's ``conv`` layers.

Between the operator's two products — ``[B̃ | C̃ | x̃] = u · W_in`` before it,
``· W_out`` after it (models/lfm2_moe.py) — there is elementwise work alone:

    z = B̃ ⊙ x̃;   c_t = Σ_j w_j ⊙ z_{t−(K−1)+j}  (depthwise, causal, z = 0
    before the row's start, no bias, no activation);   y = C̃ ⊙ c

over ``[B, S, 3·D]`` in and ``[B, S, D]`` out: three reads and one write a
token and channel, ~2·K + 2 operations — bandwidth-bound on any chip. What it
has to move is ``BCx`` once and ``y`` once forward (536.9 MB a layer of the
LFM2 cell: 8 rows of 4,096 tokens, D = 2,048, bf16), and ``BCx`` and ``d y``
in, ``d BCx`` out backward (939.6 MB). Left to XLA it moved five times that:
``z`` went to HBM in float32 between two forward fusions and the backward was
a ``pad``-and-add of three shifted float32 gradients (PERF.md §6, PR 50).

So it is a Pallas kernel pair behind ``jax.custom_vjp``: ``conv_gate_fwd``
reads a run of a row's tokens out of the ONE ``BCx`` array — the block is the
array's whole width, cut at the D-lane boundaries inside the kernel: no split
or copy in front of it — and writes ``y``; ``conv_gate_bwd`` reads the same
block and ``d y``, makes ``z`` and ``c`` again, and writes ``d BCx`` as ONE
``[B, S, 3·D]`` array the in-projection's backward products read whole, with
``d w`` as float32 partial sums a (row, token tile) that XLA adds up. ``z``,
``c``, their shifted copies and every other float32 value live in VMEM. The
K − 1 tokens of ``z`` before a tile (forward, and the backward's ``c``) and
the K − 1 of ``d c`` after it (the backward's ``d z``, anti-causal) are read
as halo blocks, a sublane tile of the neighbouring tile's operands, and are
zero outside the row: a row is one document, no state is reset inside it and
none crosses to the next. Every grid step stands alone. The arithmetic is
float32, the taps in the order the sum above is written, the outputs in
``BCx``'s dtype. (The Mamba-2 mixer's ``mamba2.causal_conv`` is the same conv
under a SiLU, with a bias, at kernel 4, and keeps its own XLA path.)
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as PSpec

from ray_tpu.ops.attention import (
    VMEM_BUDGET_BYTES, VMEM_CEILING_BYTES, batch_head_axes, resolve_attention,
    vmem_block_bytes)
from ray_tpu.parallel import mesh as mesh_lib
from ray_tpu.tracing import names as scopes

# rows of a halo block, and what a token tile is a multiple of: one sublane
# tile of bf16 (two of float32)
_HALO = 16
_LANES = 128
# the token tile the rule reaches for, and the lanes the kernels' bodies take
# at a time (their float32 values are [token tile, channel tile])
_TARGET_TOKENS = 256
_TARGET_CHANNELS = 512


class ConvTiling(NamedTuple):
    token_tile: int           # tokens of one row a grid step takes
    channel_tile: int         # lanes of them the body works on at a time
    vmem_estimate: int        # bytes, _vmem_estimate() of this choice


def _vmem_estimate(kernel: str, ts: int, tc: int, D: int,
                   dtype_bytes: int) -> int:
    """VMEM bytes one grid step needs: every in/out block twice (Pallas
    double-buffers them) and the float32 values the body holds at once for a
    channel tile. An upper bound, not Mosaic's own figure."""
    wide = vmem_block_bytes((ts, D), dtype_bytes)    # [ts, D] of an operand
    halo = vmem_block_bytes((_HALO, D), dtype_bytes)
    taps = vmem_block_bytes((8, D), 4)
    if kernel == "fwd":
        io = 3 * wide + wide + 2 * halo + taps       # BCx, y, B̃ x̃ before
        live = 6
    else:
        io = 3 * wide + wide + 3 * wide + 4 * halo + 2 * taps
        live = 14
    return 2 * io + live * ts * tc * 4


def choose_conv_tiling(kernel: str, S: int, D: int,
                       dtype_bytes: int) -> ConvTiling:
    """THE rule for how a conv-gate kernel (``"fwd"`` / ``"bwd"``) tiles its
    work, from the shapes (S a multiple of _HALO, D of _LANES: _whole_tiles). A
    grid step is ``token_tile`` tokens of one row at the whole width — the
    largest multiple of _HALO that divides S, at most _TARGET_TOKENS — and
    its body takes ``channel_tile`` lanes at a time, the largest multiple of
    _LANES that divides D, at most _TARGET_CHANNELS. While the estimate does
    not fit half of what a kernel may be given (VMEM_CEILING_BYTES; past
    Mosaic's default the call raises its limit, as the scan's does) the token
    tile steps down to the next such divisor. Both kernels of a pair get the
    backward's tile: a pair has one."""
    if kernel not in ("fwd", "bwd"):
        raise ValueError(f"unknown conv-gate kernel {kernel!r}")
    tc = max(c for c in range(_LANES, min(D, _TARGET_CHANNELS) + 1, _LANES)
             if D % c == 0)
    estimate = functools.partial(_vmem_estimate, tc=tc, D=D,
                                 dtype_bytes=dtype_bytes)
    tiles = [t for t in range(min(S, _TARGET_TOKENS), 0, -_HALO) if S % t == 0]
    fit = [t for t in tiles if estimate("bwd", t) <= VMEM_CEILING_BYTES // 2]
    if not fit:
        raise ValueError(
            f"gated_short_conv: {_HALO} tokens at width D={D} "
            f"({dtype_bytes}-byte operands) do not fit VMEM: estimated at "
            f"{estimate('bwd', _HALO)} bytes of {VMEM_CEILING_BYTES // 2}")
    return ConvTiling(fit[0], tc, estimate(kernel, fit[0]))


def _later(v, before, k: int):
    """v [R, C] moved k tokens on: row i holds v[i − k], and the first k rows
    the last k of ``before`` [_HALO, C] (the tile before this one's)."""
    R = v.shape[0]
    rolled = pltpu.roll(v, k, 0)
    row = lax.broadcasted_iota(jnp.int32, before.shape, 0)
    head = jnp.where(row < k, pltpu.roll(before, k, 0), rolled[:_HALO])
    return head if R == _HALO else jnp.concatenate([head, rolled[_HALO:]], 0)


def _earlier(v, after, k: int):
    """v [R, C] moved k tokens back: row i holds v[i + k], and the last k rows
    the first k of ``after`` [_HALO, C] (the tile after this one's)."""
    R = v.shape[0]
    rolled = pltpu.roll(v, R - k, 0)
    row = lax.broadcasted_iota(jnp.int32, after.shape, 0)
    tail = jnp.where(row >= _HALO - k, pltpu.roll(after, _HALO - k, 0),
                     rolled[R - _HALO:])
    return tail if R == _HALO else jnp.concatenate([rolled[:R - _HALO], tail], 0)


def _conv(z, before, w_ref, lanes, K: int):
    """(Σ_j w_j ⊙ z_{t−(K−1)+j}, [z_{t−(K−1)+j} for each j]) on one tile."""
    moved = [_later(z, before, K - 1 - j) for j in range(K - 1)] + [z]
    return sum(m * w_ref[j:j + 1, lanes] for j, m in enumerate(moved)), moved


def _halo(a_ref, b_ref, lanes, outside):
    """The product of two halo blocks' ``lanes`` in float32 — zeros where the
    halo lies ``outside`` the row (the index map gave it some block of the
    row to read all the same)."""
    f = jnp.float32
    return jnp.where(outside, 0.0,
                     a_ref[:, lanes].astype(f) * b_ref[:, lanes].astype(f))


def _fwd_kernel(bcx_ref, b_before_ref, x_before_ref, w_ref, y_ref, *,
                D: int, tc: int, K: int):
    """One token tile of one row. bcx [ts, 3·D]; B̃ and x̃ of the _HALO tokens
    before it [_HALO, D] each; w [K, D] float32 → y [ts, D]."""
    first = pl.program_id(1) == 0
    for lo in range(0, D, tc):
        lanes = slice(lo, lo + tc)
        b, c, x = (bcx_ref[:, i * D + lo:i * D + lo + tc].astype(jnp.float32)
                   for i in range(3))
        before = _halo(b_before_ref, x_before_ref, lanes, first)
        conv, _ = _conv(b * x, before, w_ref, lanes, K)
        y_ref[:, lanes] = (c * conv).astype(y_ref.dtype)


def _bwd_kernel(bcx_ref, b_before_ref, x_before_ref, c_after_ref, dy_ref,
                dy_after_ref, w_ref, dbcx_ref, dw_ref, *, D: int, tc: int,
                K: int):
    """The same tile's gradients. Besides the forward's operands: d y
    [ts, D], and C̃ and d y of the _HALO tokens after the tile → d bcx
    [ts, 3·D] and this tile's part of d w [K, D] float32."""
    f = jnp.float32
    first = pl.program_id(1) == 0
    last = pl.program_id(1) == pl.num_programs(1) - 1
    for lo in range(0, D, tc):
        lanes = slice(lo, lo + tc)
        of = [slice(i * D + lo, i * D + lo + tc) for i in range(3)]
        b, c, x = (bcx_ref[:, part].astype(f) for part in of)
        dy = dy_ref[:, lanes].astype(f)
        before = _halo(b_before_ref, x_before_ref, lanes, first)
        conv, moved = _conv(b * x, before, w_ref, lanes, K)
        dbcx_ref[:, of[1]] = (dy * conv).astype(dbcx_ref.dtype)
        dconv = dy * c
        after = _halo(dy_after_ref, c_after_ref, lanes, last)
        # d z_t = Σ_j w_j ⊙ d c_{t+(K−1)−j}: the taps the other way round
        dz = sum((dconv if j == K - 1 else _earlier(dconv, after, K - 1 - j))
                 * w_ref[j:j + 1, lanes] for j in range(K))
        dbcx_ref[:, of[0]] = (dz * x).astype(dbcx_ref.dtype)
        dbcx_ref[:, of[2]] = (dz * b).astype(dbcx_ref.dtype)
        for j, m in enumerate(moved):
            dw_ref[j:j + 1, lanes] = jnp.sum(dconv * m, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("kernel", "interpret"))
def _call(kernel: str, bcx, w, dy=None, *, interpret: bool):
    """The pallas_call of either kernel over grid (rows, token tiles). bcx
    [B, S, 3·D], w [K, D] float32, S and D whole tiles (_whole_tiles). Forward →
    y [B, S, D]; backward (``dy`` given) → (d bcx, d w's partial sums
    [B, token tiles, K, D] float32). A jit of its own, as the scan's
    (mamba2._chunks_call): a step traces the op a layer run, a direction and
    the recompute, and a set-up several programs — the kernel bodies are then
    traced once a shape and lowered once a program, not once a call (3 s of
    the LFM2 cell's set-up on the chip's host otherwise)."""
    B, S, D3 = bcx.shape
    K, D = w.shape
    ts, tc, estimate = choose_conv_tiling(kernel, S, D, bcx.dtype.itemsize)
    nt, per = S // ts, ts // _HALO
    tile = lambda width: pl.BlockSpec((None, ts, width), lambda b, t: (b, t, 0))
    taps = pl.BlockSpec((K, D), lambda b, t: (0, 0))

    def before(part):
        return pl.BlockSpec((None, _HALO, D), lambda b, t: (
            b, jnp.maximum(t * per - 1, 0), part))

    def after(part):
        return pl.BlockSpec((None, _HALO, D), lambda b, t: (
            b, jnp.minimum((t + 1) * per, S // _HALO - 1), part))

    if kernel == "fwd":
        body, name = _fwd_kernel, scopes.CONV_GATE_FWD_KERNEL
        args = (bcx, bcx, bcx, w)
        in_specs = [tile(D3), before(0), before(2), taps]
        out_shape = jax.ShapeDtypeStruct((B, S, D), bcx.dtype)
        out_specs = tile(D)
    else:
        body, name = _bwd_kernel, scopes.CONV_GATE_BWD_KERNEL
        args = (bcx, bcx, bcx, bcx, dy, dy, w)
        in_specs = [tile(D3), before(0), before(2), after(1), tile(D),
                    after(0), taps]
        out_shape = (jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
                     jax.ShapeDtypeStruct((B, nt, K, D), jnp.float32))
        out_specs = (tile(D3), pl.BlockSpec((None, None, K, D),
                                            lambda b, t: (b, t, 0, 0)))
    return pl.pallas_call(
        functools.partial(body, D=D, tc=tc, K=K), grid=(B, nt),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=None if estimate <= VMEM_BUDGET_BYTES else min(
                VMEM_CEILING_BYTES, estimate + estimate // 2)),
        interpret=interpret, name=name)(*args)


def _whole_tiles(t, parts: int):
    """t [B, S, parts·D] with the tokens a whole number of _HALO and each
    part's channels of _LANES, zeros behind and beside (a copy, and only the
    tiny test shapes take it: zeros gate zeros, and a causal conv's later
    tokens reach no earlier one)."""
    B, S, W = t.shape
    D = W // parts
    ps, pd = -S % _HALO, -D % _LANES
    if not (ps or pd):
        return t
    return jnp.pad(t.reshape(B, S, parts, D), (
        (0, 0), (0, ps), (0, 0), (0, pd))).reshape(B, S + ps, -1)


def _cut(t, S: int, D: int, parts: int):
    """_whole_tiles undone: [B, S, parts·D] of what a kernel made."""
    B, Sp, W = t.shape
    if (Sp, W) == (S, parts * D):
        return t
    return t.reshape(B, Sp, parts, -1)[:, :S, :, :D].reshape(B, S, parts * D)


def _taps(w):
    """w [K, D] as the kernels take it: float32, whole lanes."""
    return jnp.pad(w.astype(jnp.float32), ((0, 0), (0, -w.shape[1] % _LANES)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv_gate(bcx, w, interpret):
    y = _call("fwd", _whole_tiles(bcx, 3), _taps(w), interpret=interpret)
    return _cut(y, bcx.shape[1], w.shape[1], 1)


def _conv_gate_fwd(bcx, w, interpret):
    return _conv_gate(bcx, w, interpret), (bcx, w)


def _conv_gate_bwd(interpret, res, dy):
    bcx, w = res
    S, D = bcx.shape[1], w.shape[1]
    dbcx, dw = _call("bwd", _whole_tiles(bcx, 3), _taps(w),
                     _whole_tiles(dy, 1), interpret=interpret)
    return _cut(dbcx, S, D, 3), dw.sum((0, 1))[:, :D].astype(w.dtype)


_conv_gate.defvjp(_conv_gate_fwd, _conv_gate_bwd)


@jax.named_scope(scopes.CONV_GATE)
def gated_short_conv(bcx: jax.Array, w: jax.Array) -> jax.Array:
    """bcx [B, S, 3·D] (the in-projection's output: B̃, C̃, x̃ in that order),
    w [K, D] (the last tap is the current token) → C̃ ⊙ conv(B̃ ⊙ x̃)
    [B, S, D] in bcx's dtype; the arithmetic in float32. Under a mesh
    (parallel/mesh.current_mesh) each device takes its own rows at the whole
    width; the kernels compile on a TPU and interpret elsewhere
    (attention.resolve_attention's rule)."""
    mesh = mesh_lib.current_mesh()
    _, interpret = resolve_attention(mesh=mesh)
    fn = lambda bcx, w: _conv_gate(bcx, w, interpret)
    if mesh is None:
        return fn(bcx, w)
    batch_axes, _ = batch_head_axes(mesh, bcx.shape[0], 1)
    rows = PSpec(batch_axes, None, None)
    return jax.shard_map(fn, mesh=mesh, in_specs=(rows, PSpec()),
                         out_specs=rows, check_vma=False)(bcx, w)
