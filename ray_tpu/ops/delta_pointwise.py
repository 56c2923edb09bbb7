"""The Gated DeltaNet mixer's elementwise work on either side of its scan.

Between the mixer's in-projections and the delta rule, and between the rule
and the out-projection (models/qwen3_next.delta_mixer), there is elementwise
work alone, per token and per head:

    before:  a = silu(Σ_j w_j ⊙ x_{t−(K−1)+j})   (depthwise, causal, x = 0
             before the row's start, no bias), rounded to the compute dtype;
             q and k then a · rsqrt(Σ_head a² + 1e-6) (· d_k^-½ for q)
    after:   y = o · rsqrt(Σ_head o² / d_v + eps) · gain · silu(z)

over ``[B, S, heads · d]`` tensors — bandwidth-bound on any chip: a read and a
write a token and channel before the scan, two reads and a write after it.
Left to XLA the per-head sums were products with a 0/1 head indicator at the
highest precision (six bf16 passes on 16 or 32 of the MXU's columns), the
conv's backward a ``pad``-and-add of shifted float32 gradients, and float32
``[tokens, width]`` tensors went to HBM between the fusions (PERF.md §6,
PR 63).

So each side is a Pallas kernel pair behind ``jax.custom_vjp`` in which a
head's channels are whole lane tiles: a per-head sum is a lane reduction of a
``[tokens, head]`` value in VMEM, where every float32 value lives and dies.

- ``delta_conv_norm_fwd`` reads a run of a row's tokens of ONE projection's
  output (q, k or v: nothing is concatenated or cut in front of it) with the
  K − 1 tokens before the run as a halo block, and writes the activated (and
  normalised) tensor; ``delta_conv_norm_bwd`` reads the same block, the K − 1
  tokens after it (the conv's gradient is anti-causal: it makes the
  activation's gradient of those tokens again) and the output's gradient,
  and writes the input's gradient, with ``d w`` as float32 partial sums a
  (row, token tile) that XLA adds up — ops/short_conv.py's plan.
- ``delta_gate_norm_fwd`` / ``delta_gate_norm_bwd`` read o, z and the gain
  (and ``d y``) and write y (``d o``, ``d z`` and ``d gain``'s partial sums).

A halo is zero outside the row: a row is one document. Every grid step stands
alone. The arithmetic is float32, the taps in the order the sum above is
written; the outputs — and the one value the XLA form rounds on the way, the
activation, with its cotangent — in the operands' dtype. The plain forms
(models/qwen3_next.py's ``_conv_silu``, ``_l2norm``, ``_gated_rmsnorm``) stay
that model's path off a TPU and the tests' reference.
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
    VMEM_BUDGET_BYTES, batch_head_axes, record_decision, vmem_block_bytes)
from ray_tpu.ops.short_conv import _HALO, _LANES, _cut, _earlier, _later, \
    _whole_tiles
from ray_tpu.parallel import mesh as mesh_lib
from ray_tpu.tracing import names as scopes

# the token tile the rule reaches for, and the lanes a kernel's body takes at
# a time where no norm says a head's (its float32 values are [token tile,
# channel tile])
_TARGET_TOKENS = 256
_TARGET_CHANNELS = 512
# the copies of a kernel's body in one turn of its loop over the channels
_SLABS_A_TURN = 4
# what the published ``l2norm`` adds to a head's Σ x² (qwen3_next._l2norm)
_L2_EPS = 1e-6

CONV_NORM_FWD, CONV_NORM_BWD = "conv_norm_fwd", "conv_norm_bwd"
GATE_NORM_FWD, GATE_NORM_BWD = "gate_norm_fwd", "gate_norm_bwd"
# a kernel → (the [token tile, C] blocks it reads and writes, its halo
# blocks, its [8, C] float32 blocks, the [token tile, channel tile] float32
# values its body holds at once)
_BLOCKS = {CONV_NORM_FWD: (2, 1, 1, 8), CONV_NORM_BWD: (3, 3, 2, 18),
           GATE_NORM_FWD: (3, 0, 1, 8), GATE_NORM_BWD: (5, 0, 2, 14)}
# whose estimate decides a kernel's tile: its pair's backward
_WIDEST = {CONV_NORM_FWD: CONV_NORM_BWD, CONV_NORM_BWD: CONV_NORM_BWD,
           GATE_NORM_FWD: GATE_NORM_BWD, GATE_NORM_BWD: GATE_NORM_BWD}


class PointwiseTiling(NamedTuple):
    token_tile: int           # tokens of one row a grid step takes
    channel_tile: int         # lanes of them the body works on at a time
    vmem_estimate: int        # bytes, _vmem_estimate() of this choice


_decisions: Dict[tuple, Dict[str, Any]] = {}


def pointwise_tiling_decisions() -> List[Dict[str, Any]]:
    """Every distinct tiling this process has traced one of these kernels
    with, as the ``ops/delta_tiling`` events carry them."""
    return list(_decisions.values())


def _vmem_estimate(kernel: str, ts: int, tc: int, C: int,
                   dtype_bytes: int) -> int:
    """VMEM bytes one grid step needs: every in/out block twice (Pallas
    double-buffers them) and the float32 values the body holds at once for a
    channel tile. An upper bound, not Mosaic's own figure."""
    wide, halo, rows, live = _BLOCKS[kernel]
    io = (wide * vmem_block_bytes((ts, C), dtype_bytes)
          + halo * vmem_block_bytes((_HALO, C), dtype_bytes)
          + rows * vmem_block_bytes((8, C), 4))
    return 2 * io + live * ts * tc * 4


def choose_pointwise_tiling(kernel: str, rows: int, S: int, C: int, heads: int,
                            dtype_bytes: int) -> PointwiseTiling:
    """THE rule for how one of the four kernels tiles its work, from the
    shapes (S a multiple of _HALO, a head's channels — or, ``heads`` 0: no
    norm, C — of _LANES: _whole_tiles). A grid step is ``token_tile`` tokens
    of one row at the whole width — a multiple of _HALO that divides S, at
    most _TARGET_TOKENS — and its body takes ``channel_tile`` lanes at a
    time: one head's where a norm sums over it, else a multiple of _LANES
    that divides C, at most _TARGET_CHANNELS. The largest token tile, then
    the largest channel tile, whose estimate is inside Mosaic's default
    limit (VMEM_BUDGET_BYTES); both kernels of a pair get the backward's. A
    width of which not even _HALO tokens fit is refused. Recorded once a
    distinct decision (``ops/delta_tiling``)."""
    if kernel not in _BLOCKS:
        raise ValueError(f"unknown delta-mixer kernel {kernel!r}")
    widest = _WIDEST[kernel]
    estimate = functools.partial(_vmem_estimate, C=C, dtype_bytes=dtype_bytes)
    channels = [C // heads] if heads else [
        c for c in range(min(C, _TARGET_CHANNELS), 0, -_LANES) if C % c == 0]
    fit = [(ts, tc)
           for ts in range(min(S, _TARGET_TOKENS), 0, -_HALO) if S % ts == 0
           for tc in channels if estimate(widest, ts, tc) <= VMEM_BUDGET_BYTES]
    if not fit:
        raise ValueError(
            f"{kernel}: {_HALO} tokens at width C={C} ({dtype_bytes}-byte "
            f"operands) do not fit VMEM: estimated at "
            f"{estimate(widest, _HALO, channels[-1])} bytes of "
            f"{VMEM_BUDGET_BYTES}")
    ts, tc = fit[0]
    tiling = PointwiseTiling(ts, tc, estimate(kernel, ts, tc))
    record_decision(_decisions, scopes.DELTA_TILING, dict(zip(
        scopes.DELTA_POINTWISE_TILING_ARGS,
        (kernel, rows, S, C, heads) + tuple(tiling))))
    return tiling


# --------------------------------------------------------------------------- #
# The kernels
# --------------------------------------------------------------------------- #

def _silu_slope(x, sig):
    """d silu(x) / d x, given sigmoid(x)."""
    return sig * (1.0 + x * (1.0 - sig))


def _activated(x, before, w_ref, lanes, K: int, dtype):
    """One tile's conv c, sigmoid(c), silu(c) rounded to ``dtype`` (float32
    again) and the [x_{t−(K−1)+j} for each j] the conv summed."""
    moved = [_later(x, before, K - 1 - j) for j in range(K - 1)] + [x]
    c = sum(m * w_ref[j:j + 1, lanes] for j, m in enumerate(moved))
    sig = jax.nn.sigmoid(c)
    return c, sig, (c * sig).astype(dtype).astype(jnp.float32), moved


def _each_slab(width: int, tc: int, body, carry=None):
    """``carry = body(lanes, carry)`` for each run of ``tc`` lanes of
    ``width``: a LOOP over lane offsets with _SLABS_A_TURN copies of the body
    a turn. All 16 or 32 copies unrolled cost seconds of tracing and lowering
    in every program of a set-up that holds the kernels; one a turn leaves
    the scheduler nothing to overlap a slab's loads with (PERF.md §6,
    PR 63)."""
    n = width // tc
    per = max(t for t in range(1, min(n, _SLABS_A_TURN) + 1) if n % t == 0)

    def turn(i, c):
        for j in range(per):
            c = body(pl.ds(pl.multiple_of((i * per + j) * tc, tc), tc), c)
        return c

    return lax.fori_loop(0, n // per, turn, carry)


def _conv_norm_fwd_kernel(x_ref, before_ref, w_ref, y_ref, *, tc: int, K: int,
                          norm: bool, scale: float, eps: float):
    """One token tile of one row. x [ts, C]; the _HALO tokens before it
    [_HALO, C]; w [K, C] float32 → y [ts, C]."""
    f = jnp.float32
    first = pl.program_id(1) == 0

    def slab(lanes, _):
        before = jnp.where(first, 0.0, before_ref[:, lanes].astype(f))
        _, _, a, _ = _activated(x_ref[:, lanes].astype(f), before, w_ref,
                                lanes, K, y_ref.dtype)
        if norm:        # (the scale goes into a token's factor, not a tile's)
            a = a * (lax.rsqrt(jnp.sum(a * a, axis=1, keepdims=True) + eps)
                     * scale)
        y_ref[:, lanes] = a.astype(y_ref.dtype)

    _each_slab(x_ref.shape[1], tc, slab)


def _conv_norm_bwd_kernel(x_ref, before_ref, after_ref, dy_ref, dy_after_ref,
                          w_ref, dx_ref, dw_ref, *, tc: int, K: int,
                          norm: bool, scale: float, eps: float):
    """The same tile's gradients. Besides the forward's operands: d y [ts, C],
    and x and d y of the _HALO tokens after the tile → d x [ts, C] and this
    tile's part of d w [K, C] float32."""
    f, dtype = jnp.float32, dx_ref.dtype
    ts = x_ref.shape[0]
    first = pl.program_id(1) == 0
    last = pl.program_id(1) == pl.num_programs(1) - 1

    def dconv(c, sig, a, dy):
        """d c of d y: back through the norm — the activation's cotangent
        rounded where the forward rounds the activation — and the SiLU."""
        if norm:
            inv = lax.rsqrt(jnp.sum(a * a, axis=1, keepdims=True) + eps)
            along = jnp.sum(a * dy, axis=1, keepdims=True)
            dy = ((inv * scale) * (dy - a * (inv * inv * along))
                  ).astype(dtype).astype(f)
        return dy * _silu_slope(c, sig)

    def slab(lanes, _):
        x = x_ref[:, lanes].astype(f)
        before = jnp.where(first, 0.0, before_ref[:, lanes].astype(f))
        c, sig, a, moved = _activated(x, before, w_ref, lanes, K, dtype)
        dc = dconv(c, sig, a, dy_ref[:, lanes].astype(f))
        # d c of the tokens after the tile, made from what came before THEM:
        # this tile's last tokens
        c_after, sig_after, a_after, _ = _activated(
            after_ref[:, lanes].astype(f), x[ts - _HALO:], w_ref, lanes, K,
            dtype)
        after = jnp.where(last, 0.0, dconv(
            c_after, sig_after, a_after, dy_after_ref[:, lanes].astype(f)))
        # d x_t = Σ_j w_j ⊙ d c_{t+(K−1)−j}: the taps the other way round
        dx = sum((dc if j == K - 1 else _earlier(dc, after, K - 1 - j))
                 * w_ref[j:j + 1, lanes] for j in range(K))
        dx_ref[:, lanes] = dx.astype(dtype)
        for j, m in enumerate(moved):
            dw_ref[j:j + 1, lanes] = jnp.sum(dc * m, axis=0, keepdims=True)

    _each_slab(x_ref.shape[1], tc, slab)


def _gate_norm_fwd_kernel(o_ref, z_ref, g_ref, y_ref, *, tc: int, d: int,
                          eps: float):
    """One token tile of one row. o, z [ts, C]; the gain [1, head] float32
    → y [ts, C]."""
    f = jnp.float32

    def slab(lanes, _):
        o, z = o_ref[:, lanes].astype(f), z_ref[:, lanes].astype(f)
        inv = lax.rsqrt(jnp.sum(o * o, axis=1, keepdims=True) / d + eps)
        y_ref[:, lanes] = (o * inv * g_ref[...] * (z * jax.nn.sigmoid(z))
                           ).astype(y_ref.dtype)

    _each_slab(o_ref.shape[1], tc, slab)


def _gate_norm_bwd_kernel(o_ref, z_ref, g_ref, dy_ref, do_ref, dz_ref, dg_ref,
                          *, tc: int, d: int, eps: float):
    """The same tile's gradients: besides the forward's operands d y [ts, C]
    → d o, d z [ts, C] and this tile's part of d gain [1, head] float32."""
    f = jnp.float32
    gain = g_ref[...]

    def slab(lanes, dgain):
        o, z = o_ref[:, lanes].astype(f), z_ref[:, lanes].astype(f)
        dy = dy_ref[:, lanes].astype(f)
        inv = lax.rsqrt(jnp.sum(o * o, axis=1, keepdims=True) / d + eps)
        n = o * inv
        sig = jax.nn.sigmoid(z)
        dz_ref[:, lanes] = (dy * (n * gain) * _silu_slope(z, sig)
                            ).astype(dz_ref.dtype)
        dgated = dy * (z * sig)
        dn = dgated * gain
        along = jnp.sum(dn * n, axis=1, keepdims=True) / d
        do_ref[:, lanes] = (inv * (dn - n * along)).astype(do_ref.dtype)
        return dgain + jnp.sum(dgated * n, axis=0, keepdims=True)

    dg_ref[...] = _each_slab(o_ref.shape[1], tc, slab,
                             jnp.zeros(g_ref.shape, f))


class _Blocks(NamedTuple):
    """A kernel's grid over operands [B, S, C] and the block specs it is put
    together from."""
    grid: tuple               # (rows, token tiles)
    channel_tile: int
    tile: pl.BlockSpec        # a token tile of an operand, its whole width
    before: pl.BlockSpec      # the _HALO tokens before that tile
    after: pl.BlockSpec       # ... and after it
    whole: Any                # array → the spec of one every step reads whole
    sums: Any                 # array → (shape, spec) of float32 partial sums
                              # like it, one a (row, token tile)


def _blocks(kernel: str, x, heads: int) -> _Blocks:
    B, S, C = x.shape
    ts, tc, _ = choose_pointwise_tiling(kernel, B, S, C, heads,
                                        x.dtype.itemsize)
    nt, per = S // ts, ts // _HALO
    return _Blocks(
        (B, nt), tc, pl.BlockSpec((None, ts, C), lambda b, t: (b, t, 0)),
        pl.BlockSpec((None, _HALO, C), lambda b, t: (
            b, jnp.maximum(t * per - 1, 0), 0)),
        pl.BlockSpec((None, _HALO, C), lambda b, t: (
            b, jnp.minimum((t + 1) * per, S // _HALO - 1), 0)),
        lambda a: pl.BlockSpec(a.shape, lambda b, t: (0,) * a.ndim),
        lambda a: (jax.ShapeDtypeStruct((B, nt) + a.shape, jnp.float32),
                   pl.BlockSpec((None, None) + a.shape,
                                lambda b, t: (b, t, 0, 0))))


def _pallas(body, name: str, blocks: _Blocks, in_specs, out_specs, out_shape,
            interpret: bool):
    return pl.pallas_call(
        body, grid=blocks.grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret, name=name)


@functools.partial(jax.jit, static_argnames=("heads", "scale", "interpret"))
def _conv_norm_call(x, w, dy=None, *, heads: int, scale: float,
                    interpret: bool):
    """The pallas_call of either kernel of the first pair over grid (rows,
    token tiles). x [B, S, C] whole tiles (_whole_tiles), w [K, C] float32;
    ``heads`` is how many the norm sums over apart (0: no norm), ``scale``
    what the normalised tensor is multiplied by. Forward → y [B, S, C];
    backward (``dy`` given) → (d x, d w's partial sums [B, token tiles, K, C]
    float32). A jit of its own, as the scan's (gated_delta._chunks_call): a
    step traces each op a layer run, a direction and the recompute — the
    bodies are then traced once a shape."""
    b = _blocks(CONV_NORM_FWD if dy is None else CONV_NORM_BWD, x, heads)
    static = dict(tc=b.channel_tile, K=w.shape[0], norm=bool(heads),
                  scale=scale, eps=_L2_EPS)
    like_x = jax.ShapeDtypeStruct(x.shape, x.dtype)
    if dy is None:
        return _pallas(
            functools.partial(_conv_norm_fwd_kernel, **static),
            scopes.DELTA_CONV_NORM_FWD_KERNEL, b,
            [b.tile, b.before, b.whole(w)], b.tile, like_x, interpret)(x, x, w)
    dw_shape, dw_spec = b.sums(w)
    return _pallas(
        functools.partial(_conv_norm_bwd_kernel, **static),
        scopes.DELTA_CONV_NORM_BWD_KERNEL, b,
        [b.tile, b.before, b.after, b.tile, b.after, b.whole(w)],
        (b.tile, dw_spec), (like_x, dw_shape), interpret)(x, x, x, dy, dy, w)


@functools.partial(jax.jit, static_argnames=("d", "eps", "interpret"))
def _gate_norm_call(o, z, gain, dy=None, *, d: int, eps: float,
                    interpret: bool):
    """The same of the second pair. o, z [B, S, C] whole tiles, gain [1, head]
    float32 (a head's ``d`` channels and zeros beside them). Forward → y
    [B, S, C]; backward (``dy`` given) → (d o, d z, d gain's partial sums
    [B, token tiles, 1, head] float32)."""
    b = _blocks(GATE_NORM_FWD if dy is None else GATE_NORM_BWD, o,
                o.shape[2] // gain.shape[1])
    static = dict(tc=b.channel_tile, d=d, eps=eps)
    like_o = jax.ShapeDtypeStruct(o.shape, o.dtype)
    if dy is None:
        return _pallas(
            functools.partial(_gate_norm_fwd_kernel, **static),
            scopes.DELTA_GATE_NORM_FWD_KERNEL, b,
            [b.tile, b.tile, b.whole(gain)], b.tile, like_o, interpret)(
                o, z, gain)
    dg_shape, dg_spec = b.sums(gain)
    return _pallas(
        functools.partial(_gate_norm_bwd_kernel, **static),
        scopes.DELTA_GATE_NORM_BWD_KERNEL, b,
        [b.tile, b.tile, b.whole(gain), b.tile], (b.tile, b.tile, dg_spec),
        (like_o, like_o, dg_shape), interpret)(o, z, gain, dy)


# --------------------------------------------------------------------------- #
# The two ops
# --------------------------------------------------------------------------- #

def _lanes(w, parts: int):
    """w [K, parts · d] float32 with each part's channels whole lane tiles,
    zeros beside them (as _whole_tiles lays the tokens' channels out)."""
    K, W = w.shape
    d = W // parts
    return jnp.pad(w.astype(jnp.float32).reshape(K, parts, d), (
        (0, 0), (0, 0), (0, -d % _LANES))).reshape(K, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _conv_norm(x, w, heads, scale, interpret):
    parts = max(heads, 1)
    y = _conv_norm_call(_whole_tiles(x, parts), _lanes(w, parts), heads=heads,
                        scale=scale, interpret=interpret)
    return _cut(y, x.shape[1], x.shape[2] // parts, parts)


def _conv_norm_fwd(x, w, heads, scale, interpret):
    return _conv_norm(x, w, heads, scale, interpret), (x, w)


def _conv_norm_bwd(heads, scale, interpret, res, dy):
    x, w = res
    parts = max(heads, 1)
    S, d = x.shape[1], x.shape[2] // parts
    dx, dw = _conv_norm_call(
        _whole_tiles(x, parts), _lanes(w, parts), _whole_tiles(dy, parts),
        heads=heads, scale=scale, interpret=interpret)
    dw = dw.sum((0, 1)).reshape(w.shape[0], parts, -1)[..., :d]
    return _cut(dx, S, d, parts), dw.reshape(w.shape).astype(w.dtype)


_conv_norm.defvjp(_conv_norm_fwd, _conv_norm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gate_norm(o, z, gain, heads, eps, interpret):
    d = gain.shape[0]
    y = _gate_norm_call(_whole_tiles(o, heads), _whole_tiles(z, heads),
                        _lanes(gain[None], 1), d=d, eps=eps,
                        interpret=interpret)
    return _cut(y, o.shape[1], d, heads)


def _gate_norm_fwd(o, z, gain, heads, eps, interpret):
    return _gate_norm(o, z, gain, heads, eps, interpret), (o, z, gain)


def _gate_norm_bwd(heads, eps, interpret, res, dy):
    o, z, gain = res
    S, d = o.shape[1], gain.shape[0]
    do, dz, dg = _gate_norm_call(
        _whole_tiles(o, heads), _whole_tiles(z, heads), _lanes(gain[None], 1),
        _whole_tiles(dy, heads), d=d, eps=eps, interpret=interpret)
    return (_cut(do, S, d, heads), _cut(dz, S, d, heads),
            dg.sum((0, 1, 2))[:d].astype(gain.dtype))


_gate_norm.defvjp(_gate_norm_fwd, _gate_norm_bwd)


def _by_rows(fn, rows: int, *sharded, whole=()):
    """fn(*sharded, *whole) with each device of the mesh in use
    (parallel/mesh.current_mesh) on its own rows of the ``sharded`` [B, S, C]
    operands and of the result."""
    mesh = mesh_lib.current_mesh()
    if mesh is None:
        return fn(*sharded, *whole)
    batch_axes, _ = batch_head_axes(mesh, rows, 1)
    by_row = PSpec(batch_axes, None, None)
    return jax.shard_map(
        fn, mesh=mesh, out_specs=by_row, check_vma=False,
        in_specs=(by_row,) * len(sharded) + (PSpec(),) * len(whole))(
            *sharded, *whole)


def conv_silu_norm(x: jax.Array, w: jax.Array, heads: int = 0,
                   scale: float = 1.0, *, interpret: bool) -> jax.Array:
    """x [B, S, C] (a projection's output), w [K, C] (the last tap is the
    current token) → silu(causal depthwise conv of x by w) rounded to x's
    dtype and — ``heads`` > 0 — L2-normalised over each of the ``heads``
    runs of C / heads channels, times ``scale``; [B, S, C] in x's dtype, the
    arithmetic in float32. Under a mesh each device takes its own rows at
    the whole width."""
    fn = lambda x, w: _conv_norm(x, w, heads, float(scale), interpret)
    return _by_rows(fn, x.shape[0], x, whole=(w,))


def gated_rmsnorm(o: jax.Array, z: jax.Array, gain: jax.Array, eps: float, *,
                  interpret: bool) -> jax.Array:
    """o, z [B, S, heads · d], gain [d] → o / rms_head(o) · gain · silu(z)
    in o's dtype, the arithmetic in float32; the RMS over each head's d
    channels, the one gain vector for every head."""
    heads = o.shape[2] // gain.shape[0]
    fn = lambda o, z, gain: _gate_norm(o, z, gain, heads, eps, interpret)
    return _by_rows(fn, o.shape[0], o, z, whole=(gain,))
