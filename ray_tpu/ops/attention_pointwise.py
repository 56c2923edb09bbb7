"""The gated attention operator's elementwise work on either side of its
flash pair.

Between a q or k projection and the flash kernels, and between the kernels
and the out-projection (models/afmoe.attention_operator), there is
elementwise work alone, per token and per head:

    before:  y = rope(x · rsqrt(mean_head x² + eps) · gain)   (QK-norm, then
             the rotation on a window layer; a full layer has none)
    after:   y = o · sigmoid(gate)

Each is bandwidth-bound on any chip: a read and a write of the tensor before
the kernels, two reads and a write after them. Left to XLA the projections'
outputs were written S-minor and copied to the kernels' hd-minor order, the
norm's statistic was a pass of its own, the rotation a ``[hd, hd]`` product
with the norm as its operand fusion, o went to HBM in float32 and was
transposed there so that the gate's product could take the gating as its
output fusion, and the backward products held every one of those as operand
fusions (PERF.md §6, PR 67).

So each side is a Pallas kernel pair behind ``jax.custom_vjp`` whose BLOCK
INDEX MAPS do the re-ordering: one side of each kernel is ``[B, S, H · hd]``
— where a plain ``[B · S, D] x [D, H · hd]`` product writes its output and
reads its operand — and the other ``[B, H, S, hd]``, the flash pair's own. A
grid step takes a run of a row's tokens of a few heads; a head's channels
are whole lane tiles, the per-head mean a lane reduction of a ``[tokens,
hd]`` value in VMEM and rotate-half a lane roll by hd / 2 against a sine
whose first half is negated. Every float32 value lives and dies in VMEM.

- ``head_norm_rope_fwd`` reads x ``[B, S, H · hd]``, the gain and (a window
  layer) the cos / signed-sin tables and writes y ``[B, H, S, hd]``;
  ``head_norm_rope_bwd`` reads x, the gain, the tables and d y and writes
  d x, with ``d gain`` as float32 partial sums a grid step that XLA adds up.
- ``attn_gate_fwd`` reads o ``[B, H, S, hd]`` and the gate ``[B, S, H · hd]``
  and writes the gated o ``[B, S, H · hd]``; ``attn_gate_bwd`` reads both and
  d y and writes d o ``[B, H, S, hd]`` and d gate ``[B, S, H · hd]``.

The arithmetic is float32; the outputs — and the two values the plain form
rounds on the way, the normalised x and its product with the gain — in the
operands' dtype, so the forward is the plain composition's (parts.
head_rmsnorm, parts.rope) to the bit of that dtype. The plain forms stay the
model's path where a head is not whole lane tiles or no kernel runs, and the
tests' reference.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.delta_pointwise import _by_rows
from ray_tpu.tracing import names as scopes

# the tokens a grid step reaches for (a multiple of _SUBLANES that divides
# the row), and the bytes of ONE of its blocks: the heads a step takes are as
# many as keep a block under it — the backward of the gate holds five blocks,
# each twice (Pallas double-buffers them), inside Mosaic's default 16 MiB
_TARGET_TOKENS = 512
_BLOCK_BYTES = 2 ** 20
_SUBLANES = 16          # a bf16 tile's rows: a row is padded to whole ones
_ROWS = 128             # the tokens of a head a kernel's body takes at a time


def _tiles(S: int, H: int, hd: int, itemsize: int):
    """(token tile, heads a step) for rows of S tokens (whole _SUBLANES) of H
    heads of hd: the largest token tile up to _TARGET_TOKENS that divides S,
    then the most heads that divide H and keep a block under _BLOCK_BYTES."""
    ts = max(t for t in range(_SUBLANES, min(S, _TARGET_TOKENS) + 1, _SUBLANES)
             if S % t == 0)
    hb = max([h for h in range(1, H + 1)
              if H % h == 0 and ts * h * hd * itemsize <= _BLOCK_BYTES] or [1])
    return ts, hb


class _Blocks(NamedTuple):
    """A kernel's grid and the block specs it is put together from."""
    grid: tuple               # (rows, token tiles, head groups)
    heads: int                # the heads a step takes
    flat: pl.BlockSpec        # a step's block of a tensor [B, S, H · hd]
    by_head: pl.BlockSpec     # ... of one [B, H, S, hd]
    table: pl.BlockSpec       # ... of a table [S, hd]
    vector: pl.BlockSpec      # a [1, hd] vector every step reads whole
    sums: Any                 # (shape, spec) of float32 partial sums like
                              # that vector, one a step


def _blocks(B: int, S: int, H: int, hd: int, itemsize: int) -> _Blocks:
    """The heads stand innermost in the grid, so a token tile's tables are
    fetched once."""
    ts, hb = _tiles(S, H, hd, itemsize)
    grid = (B, S // ts, H // hb)
    return _Blocks(
        grid, hb,
        pl.BlockSpec((None, ts, hb * hd), lambda b, t, h: (b, t, h)),
        pl.BlockSpec((None, hb, ts, hd), lambda b, t, h: (b, h, t, 0)),
        pl.BlockSpec((ts, hd), lambda b, t, h: (t, 0)),
        pl.BlockSpec((1, hd), lambda b, t, h: (0, 0)),
        (jax.ShapeDtypeStruct(grid + (1, hd), jnp.float32),
         pl.BlockSpec((None, None, None, 1, hd),
                      lambda b, t, h: (b, t, h, 0, 0))))


def _pallas(body, name: str, grid, in_specs, out_specs, out_shape,
            interpret: bool):
    return pl.pallas_call(
        body, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        interpret=interpret, name=name)


def _each_piece(hb: int, ts: int, hd: int, body, carry=None):
    """``carry = body(h, lanes, rows, carry)`` for each head h of a step's hb
    and each run of _ROWS of its ts tokens (``lanes`` the head's channels of
    a flat block): a LOOP over the heads with a head's runs unrolled inside a
    turn — a [_ROWS, hd] float32 value is 16 registers, so a run's values
    stay in them, and the copies a turn leave the scheduler the next run's
    loads to overlap (ops/delta_pointwise._each_slab, and why)."""
    rows = _ROWS if ts % _ROWS == 0 else ts

    def turn(h, c):
        lanes = pl.ds(pl.multiple_of(h * hd, hd), hd)
        for r in range(0, ts, rows):
            c = body(h, lanes, pl.ds(r, rows), c)
        return c

    return lax.fori_loop(0, hb, turn, carry)


# --------------------------------------------------------------------------- #
# QK-norm + RoPE
# --------------------------------------------------------------------------- #

def _normalised(x, eps: float):
    """x [rows, hd] float32 → (x / rms(x) unrounded, the rows' rsqrt): a
    head's RMS is a lane reduction."""
    inv = lax.rsqrt(jnp.mean(x * x, axis=1, keepdims=True) + eps)
    return x * inv, inv


def _rounded(x, dtype):
    return x.astype(dtype).astype(jnp.float32)


def _half_turned(x):
    """[x₂, x₁] of x = [x₁, x₂] along the lanes: rotate-half is this times a
    sign, which the signed sine carries."""
    return pltpu.roll(x, x.shape[1] // 2, 1)


def _norm_rope_fwd_kernel(x_ref, g_ref, *refs, hb: int, hd: int, eps: float,
                          rope: bool):
    """x [ts, hb · hd]; the gain [1, hd] float32; (``rope``) cos and the
    signed sine [ts, hd] float32 → y [hb, ts, hd]."""
    y_ref = refs[-1]
    dtype = y_ref.dtype

    def piece(h, lanes, rows, _):
        n, _ = _normalised(x_ref[rows, lanes].astype(jnp.float32), eps)
        m = _rounded(_rounded(n, dtype) * g_ref[...], dtype)
        if rope:
            m = m * refs[0][rows, :] + _half_turned(m) * refs[1][rows, :]
        y_ref[h, rows, :] = m.astype(dtype)

    _each_piece(hb, x_ref.shape[0], hd, piece)


def _norm_rope_bwd_kernel(x_ref, g_ref, *refs, hb: int, hd: int, eps: float,
                          rope: bool):
    """The same step's gradients: besides the forward's operands d y
    [hb, ts, hd] → d x [ts, hb · hd] and this step's part of d gain [1, hd]
    float32. The rotation's transpose is the rotation by the other sign; the
    row statistic is made again from x."""
    dy_ref, dx_ref, dg_ref = refs[-3:]
    dtype = dx_ref.dtype

    def piece(h, lanes, rows, dgain):
        dm = dy_ref[h, rows, :].astype(jnp.float32)
        if rope:
            dm = dm * refs[0][rows, :] - _half_turned(dm) * refs[1][rows, :]
        n, inv = _normalised(x_ref[rows, lanes].astype(jnp.float32), eps)
        dn = dm * g_ref[...]
        along = jnp.mean(dn * n, axis=1, keepdims=True)
        dx_ref[rows, lanes] = (inv * (dn - n * along)).astype(dtype)
        return dgain + jnp.sum(dm * _rounded(n, dtype), axis=0, keepdims=True)

    dg_ref[...] = _each_piece(hb, x_ref.shape[0], hd, piece,
                              jnp.zeros(g_ref.shape, jnp.float32))


@functools.partial(jax.jit, static_argnames=("heads", "eps", "interpret"))
def _norm_rope_call(x, gain, tables, dy=None, *, heads: int, eps: float,
                    interpret: bool):
    """The pallas_call of either kernel of the first pair. x [B, S, H · hd]
    (S whole _SUBLANES), gain [1, hd] float32, ``tables`` () or (cos, signed
    sine) [S, hd] float32. Forward → y [B, H, S, hd]; backward (``dy``
    given) → (d x, d gain's partial sums, one a grid step). A jit of its own,
    as ops/delta_pointwise's: a step traces each a layer run, a direction and
    the recompute."""
    B, S, C = x.shape
    hd = C // heads
    b = _blocks(B, S, heads, hd, x.dtype.itemsize)
    static = dict(hb=b.heads, hd=hd, eps=eps, rope=bool(tables))
    ins = [b.flat, b.vector] + [b.table] * len(tables)
    if dy is None:
        return _pallas(
            functools.partial(_norm_rope_fwd_kernel, **static),
            scopes.HEAD_NORM_ROPE_FWD_KERNEL, b.grid, ins, b.by_head,
            jax.ShapeDtypeStruct((B, heads, S, hd), x.dtype), interpret)(
                x, gain, *tables)
    return _pallas(
        functools.partial(_norm_rope_bwd_kernel, **static),
        scopes.HEAD_NORM_ROPE_BWD_KERNEL, b.grid, ins + [b.by_head],
        (b.flat, b.sums[1]),
        (jax.ShapeDtypeStruct(x.shape, x.dtype), b.sums[0]), interpret)(
            x, gain, *tables, dy)


def rope_tables(S: int, hd: int, theta: float):
    """(cos, signed sine) [S, hd] float32 of positions 0 … S − 1, parts.rope's
    angles (rotate-half: the head split [first half, second half]); the
    sine's first half negated — rotate_half(x) · sin = [x₂, x₁] · it."""
    half = hd // 2
    freqs = 1.0 / theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = jnp.arange(S).astype(jnp.float32)[:, None] * freqs[None, :]
    sin = jnp.sin(angles)
    return (jnp.concatenate([jnp.cos(angles)] * 2, axis=-1),
            jnp.concatenate([-sin, sin], axis=-1))


def _padded(x, axis: int):
    """x with its token ``axis`` zero-padded to whole _SUBLANES."""
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, -x.shape[axis] % _SUBLANES)
    return jnp.pad(x, pad) if pad[axis][1] else x


def _norm_rope_operands(x, gain, heads: int, theta):
    """What either kernel of the first pair is called with: x with its
    tokens padded, the gain as the plain form multiplies by it — rounded to
    the tensor's dtype; [1, hd] float32 — and the rotation's tables."""
    xp = _padded(x, 1)
    tables = () if theta is None else rope_tables(
        xp.shape[1], x.shape[2] // heads, theta)
    return xp, gain.astype(x.dtype).astype(jnp.float32)[None], tables


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _norm_rope(x, gain, heads, eps, theta, interpret):
    y = _norm_rope_call(*_norm_rope_operands(x, gain, heads, theta),
                        heads=heads, eps=eps, interpret=interpret)
    return y[:, :, :x.shape[1]]


def _norm_rope_fwd(x, gain, heads, eps, theta, interpret):
    return _norm_rope(x, gain, heads, eps, theta, interpret), (x, gain)


def _norm_rope_bwd(heads, eps, theta, interpret, res, dy):
    x, gain = res
    dx, dg = _norm_rope_call(*_norm_rope_operands(x, gain, heads, theta),
                             _padded(dy, 2), heads=heads, eps=eps,
                             interpret=interpret)
    return dx[:, :x.shape[1]], dg.sum((0, 1, 2, 3)).astype(gain.dtype)


_norm_rope.defvjp(_norm_rope_fwd, _norm_rope_bwd)


def head_norm_rope(x: jax.Array, gain: jax.Array, heads: int, eps: float,
                   theta: Optional[float] = None, *,
                   interpret: bool) -> jax.Array:
    """x [B, S, heads · hd] (a projection's output where a plain product
    writes it), gain [hd] → rope(x / rms_head(x) · gain) as [B, heads, S, hd]
    in x's dtype: the RMS over each head's hd channels, float32 statistics,
    the one gain vector for every head, the rotation (rotate-half, positions
    0 … S − 1, base ``theta``) left out where ``theta`` is None. Under a mesh
    each device takes its own rows."""
    fn = lambda x, gain: _norm_rope(x, gain, heads, eps, theta, interpret)
    return _by_rows(fn, x.shape[0], x, whole=(gain,))


# --------------------------------------------------------------------------- #
# The output gate
# --------------------------------------------------------------------------- #

def _gate_fwd_kernel(o_ref, z_ref, y_ref, *, hb: int, hd: int):
    """o [hb, ts, hd]; the gate's logits z [ts, hb · hd] → y [ts, hb · hd]."""
    def piece(h, lanes, rows, _):
        y_ref[rows, lanes] = (
            o_ref[h, rows, :].astype(jnp.float32)
            * jax.nn.sigmoid(z_ref[rows, lanes].astype(jnp.float32))
        ).astype(y_ref.dtype)

    _each_piece(hb, z_ref.shape[0], hd, piece)


def _gate_bwd_kernel(o_ref, z_ref, dy_ref, do_ref, dz_ref, *, hb: int,
                     hd: int):
    """The same step's gradients: d y [ts, hb · hd] → d o [hb, ts, hd] and
    d z [ts, hb · hd]."""
    def piece(h, lanes, rows, _):
        sig = jax.nn.sigmoid(z_ref[rows, lanes].astype(jnp.float32))
        dy = dy_ref[rows, lanes].astype(jnp.float32)
        do_ref[h, rows, :] = (dy * sig).astype(do_ref.dtype)
        dz_ref[rows, lanes] = (dy * o_ref[h, rows, :].astype(jnp.float32)
                               * (sig * (1.0 - sig))).astype(dz_ref.dtype)

    _each_piece(hb, z_ref.shape[0], hd, piece)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gate_call(o, z, dy=None, *, interpret: bool):
    """The pallas_call of either kernel of the second pair. o [B, H, S, hd],
    z [B, S, H · hd] (S whole _SUBLANES). Forward → y like z; backward
    (``dy`` given) → (d o, d z)."""
    B, H, S, hd = o.shape
    b = _blocks(B, S, H, hd, o.dtype.itemsize)
    static = dict(hb=b.heads, hd=hd)
    like_o, like_z = (jax.ShapeDtypeStruct(t.shape, o.dtype) for t in (o, z))
    if dy is None:
        return _pallas(functools.partial(_gate_fwd_kernel, **static),
                       scopes.ATTN_GATE_FWD_KERNEL, b.grid,
                       [b.by_head, b.flat], b.flat, like_z, interpret)(o, z)
    return _pallas(functools.partial(_gate_bwd_kernel, **static),
                   scopes.ATTN_GATE_BWD_KERNEL, b.grid,
                   [b.by_head, b.flat, b.flat], (b.by_head, b.flat),
                   (like_o, like_z), interpret)(o, z, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _gate(o, z, interpret):
    S = o.shape[2]
    return _gate_call(_padded(o, 2), _padded(z, 1), interpret=interpret)[:, :S]


def _gate_fwd(o, z, interpret):
    return _gate(o, z, interpret), (o, z)


def _gate_bwd(interpret, res, dy):
    o, z = res
    S = o.shape[2]
    do, dz = _gate_call(_padded(o, 2), _padded(z, 1), _padded(dy, 1),
                        interpret=interpret)
    return do[:, :, :S], dz[:, :S]


_gate.defvjp(_gate_fwd, _gate_bwd)


def sigmoid_gated(o: jax.Array, z: jax.Array, *, interpret: bool) -> jax.Array:
    """o [B, H, S, hd] (the flash pair's output where it writes it), z
    [B, S, H · hd] (the gate's projection where a plain product writes it) →
    o · sigmoid(z) as [B, S, H · hd] — where the out-projection's plain
    product reads it — in o's dtype, the arithmetic in float32. Under a mesh
    each device takes its own rows."""
    fn = lambda o, z: _gate(o, z, interpret)
    return _by_rows(fn, o.shape[0], o, z)
