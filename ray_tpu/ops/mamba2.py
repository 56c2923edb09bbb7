"""Mamba-2 mixer: projections, causal depthwise conv, the chunked state-space
(SSD) scan, the gated group norm and the out-projection. The scan is a Pallas
kernel pair; the rest is plain XLA, differentiated by AD.

One row's recurrence, a head h of P channels over a state of N (``B``, ``C``
shared by the H/G heads of a group):

    Δ_t = softplus(dt_t + dt_bias)        a_t = exp(Δ_t · A),  A = −exp(A_log)
    h_t = a_t · h_{t−1} + Δ_t · x_t ⊗ B_t                       h_0 = 0
    y_t = h_t · C_t + D · x_t

``ssd_scan`` computes it in chunks of Q tokens: inside a chunk the quadratic
form ``(C Bᵀ ∘ L) · (Δx)`` with ``L_ij = ∏_{j<k≤i} a_k``, and between chunks
the state. Both are one kernel's (``ssd_chunk_fwd``): a grid step is one
chunk of one row for a tile of one group's heads, the row's chunks the last,
sequential grid axis along which the [P, N] states ride in VMEM — so a
chunk's decay matrix L, C·Bᵀ ∘ L and their casts exist a head at a time, in
VMEM, and never as ``[chunks, heads, Q, Q]`` in HBM. The backward
(``ssd_chunk_bwd``, behind ``jax.custom_vjp``) walks the chunks in reverse
carrying the state's gradient and makes d x, d Δ, d B, d C and the gradient
of the cumulative log-decays from the same tiles; what XLA keeps of the scan
is the log-decays' sums (one product with a triangle of ones, from which AD
takes d Δ's second part and d A) and layout. Δ, a, the cumulative log-decays,
the state and every accumulator are float32; the matmuls take operands in
x's dtype and accumulate in float32. Tiles come from the shapes
(``choose_ssd_tiling``, recorded as ``ops/ssd_tiling``); the chunk states the
forward leaves for the backward, the projections' outputs and the scan's
output carry residual names (tracing/names.py) for the remat rule.

A chip that holds ``heads`` of the model's heads and ``groups`` of its groups
computes exactly their part: the conv is depthwise, ``B`` and ``C`` belong to
a group, the gated norm is over a group's channels, and the out-projection is
linear — the shares' outputs add up to the whole layer's
(tests/test_nemotron_h.py).
"""

from __future__ import annotations

import functools
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


def mamba2_init(rng: jax.Array, n_layers: int, d_model: int, heads: int,
                head_dim: int, groups: int, state: int, conv_kernel: int,
                std: float, out_std: float, param_dtype=jnp.float32
                ) -> Dict[str, Any]:
    """``n_layers`` stacked mixers. ``in_proj`` is kept as its three parts
    (z, xBC, dt): each is one matmul whose output is used whole. ``A_log`` is
    log of uniform [1, 16]; ``dt_bias`` the inverse softplus of a step drawn
    log-uniform in [0.001, 0.1] (floor 1e-4), as the published family's
    initialisation places it."""
    inner = heads * head_dim
    conv_dim = inner + 2 * groups * state
    k = iter(jax.random.split(rng, 7))
    L = n_layers

    def normal(key, shape, s):
        return (jax.random.normal(key, shape) * s).astype(param_dtype)

    dt = jnp.exp(jax.random.uniform(next(k), (L, heads))
                 * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
    dt = jnp.maximum(dt, 1e-4)
    return {
        "w_z": normal(next(k), (L, d_model, inner), std),
        "w_xbc": normal(next(k), (L, d_model, conv_dim), std),
        "w_dt": normal(next(k), (L, d_model, heads), std),
        "conv_w": normal(next(k), (L, conv_kernel, conv_dim),
                         1.0 / conv_kernel),
        "conv_b": jnp.zeros((L, conv_dim), param_dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(param_dtype),
        "A_log": jnp.log(jax.random.uniform(
            next(k), (L, heads), minval=1.0, maxval=16.0)).astype(param_dtype),
        "D": jnp.ones((L, heads), param_dtype),
        "gate_norm": jnp.ones((L, inner), param_dtype),
        "w_out": normal(next(k), (L, inner, d_model), out_std),
    }


def mamba2_logical_axes() -> Dict[str, Any]:
    return {
        "w_z": ("layers", "embed", "mlp"),
        "w_xbc": ("layers", "embed", None),
        "w_dt": ("layers", "embed", None),
        "conv_w": ("layers", None, None),
        "conv_b": ("layers", None),
        "dt_bias": ("layers", None),
        "A_log": ("layers", None),
        "D": ("layers", None),
        "gate_norm": ("layers", "mlp"),
        "w_out": ("layers", "mlp", "embed"),
    }


MATMUL_WEIGHTS = ("w_z", "w_xbc", "w_dt", "w_out")


def causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv over the sequence: xbc [B, S, C], w [K, C] (the
    last tap is the current token), b [C] → float32 [B, S, C]."""
    K, S = w.shape[0], xbc.shape[1]
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    wf = w.astype(jnp.float32)
    out = b.astype(jnp.float32)
    for tap in range(K):
        out = out + padded[:, tap:tap + S].astype(jnp.float32) * wf[tap]
    return out


# --------------------------------------------------------------------------- #
# The chunked scan: tiling, the two kernels, the op
# --------------------------------------------------------------------------- #

class SsdTiling(NamedTuple):
    head_tile: int            # heads of one group a grid step takes
    vmem_estimate: int        # bytes, _vmem_estimate() of this choice


# a grid step's heads are unrolled in the kernel body (each has [Q, Q] tiles
# and products of its own): more than this many is program size for nothing,
# a step's fixed cost is already spread over them
_MAX_HEAD_TILE = 16

_decisions: Dict[tuple, Dict[str, Any]] = {}


def ssd_tiling_decisions() -> List[Dict[str, Any]]:
    """Every distinct tiling this process has traced a scan kernel with, as
    the ``ops/ssd_tiling`` events carry them."""
    return list(_decisions.values())


def _vmem_estimate(kernel: str, Q: int, ht: int, P: int, N: int,
                   dtype_bytes: int) -> int:
    """VMEM bytes one grid step needs: every in/out block twice (Pallas
    double-buffers them), the carried state (its gradient in the backward)
    once, and the float32 values the body holds at once — [Q, ht·P] spreads
    and products, a head's [Q, Q] tiles. An upper bound, not Mosaic's own
    figure."""
    blk, a, W = vmem_block_bytes, dtype_bytes, ht * P
    wide, tile, state = blk((Q, W), 4), blk((Q, Q), 4), blk((N, W), 4)
    spreads = (blk((_TERMS * ht, W), 2) + ht * blk((_TERMS * ht, Q), 2)
               + blk((W, ht), 2))                          # the 0/1 matrices
    io = (blk((Q, W), a) + wide + state                    # x; y or d y; states
          + 5 * blk((Q, _TERMS * ht), 2) + blk((ht, Q), 4)  # packs, cum rows
          + 2 * blk((Q, N), a) + spreads)                  # B, C
    live = 8 * wide + 3 * tile
    if kernel == "bwd":
        io += (blk((Q, W), a) + 2 * blk((Q, ht), 4) + blk((ht, Q), 4)
               + 2 * blk((Q, N), 4) + blk((1, W), 4))      # the gradients, ρ
        live += 10 * wide + 2 * tile
    return 2 * io + state + live


def choose_ssd_tiling(kernel: str, rows: int, S: int, Q: int, hg: int, P: int,
                      N: int, dtype_bytes: int) -> SsdTiling:
    """THE rule for how a scan kernel (``"fwd"`` / ``"bwd"``) tiles its work:
    a grid step is one chunk of one row for ``head_tile`` heads of one group
    (they share the chunk's C·Bᵀ, and the state's products take them all at
    once). The tile is the largest divisor of the group's heads, at most
    _MAX_HEAD_TILE, whose estimate fits half of what a kernel may be given
    (VMEM_CEILING_BYTES; past Mosaic's default the call raises its limit, as
    the EVA kernels do); among those, one whose x block is whole 128-lane
    tiles (``head_tile · P``) before one that is not. A shape of which not
    even one head fits is refused. Recorded once a distinct decision
    (``ops/ssd_tiling``)."""
    if kernel not in ("fwd", "bwd"):
        raise ValueError(f"unknown scan kernel {kernel!r}")
    tiles = [t for t in range(min(hg, _MAX_HEAD_TILE), 0, -1) if hg % t == 0]
    estimate = functools.partial(_vmem_estimate, kernel, Q, P=P, N=N,
                                 dtype_bytes=dtype_bytes)
    fit = [t for t in tiles if estimate(ht=t) <= VMEM_CEILING_BYTES // 2]
    if not fit:
        raise ValueError(
            f"ssd_scan {kernel}: one head of a chunk does not fit VMEM for "
            f"chunk Q={Q} head width P={P} state N={N} ({dtype_bytes}-byte "
            f"operands): estimated at {estimate(ht=1)} bytes of "
            f"{VMEM_CEILING_BYTES // 2}; use a smaller chunk")
    ht = next((t for t in fit if (t * P) % 128 == 0), fit[0])
    tiling = SsdTiling(ht, estimate(ht=ht))
    record_decision(_decisions, scopes.SSD_TILING, dict(zip(
        scopes.SSD_TILING_ARGS, (kernel, rows, S, Q, hg, P, N) + tuple(tiling))))
    return tiling


def _nt(a, b):
    """a [m, k] · b [n, k]ᵀ → float32 [m, n]."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _tn(a, b):
    """a [k, m]ᵀ · b [k, n] → float32 [m, n]."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _nn(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


# A head's per-token scalars (Δ, its log-decays and their exponentials) meet
# the [Q, heads·P] and [Q, Q] tiles as columns spread along the lanes. On the
# chip that spreading is the MXU's: XLA splits each float32 column into three
# bf16 terms (their sum is the float32 to 2⁻²⁴), side by side as [Q, 3·heads],
# and a 0/1 matrix places every head's three terms on that head's lanes —
# exact products, a float32 sum. The same the other way round sums a head's
# lanes (of products of bf16 operands: two terms are as exact as they). No
# lane is broadcast, rotated or reduced by the vector units, whose time the
# [Q, Q] exponentials need.
_TERMS = 3


def _split(v, terms: int = _TERMS):
    """float32 v → `terms` bf16 arrays whose float32 sum is v to 2⁻⁸·ᵗᵉʳᵐˢ.
    A term is what is left, cut (not rounded) to bf16's 16 bits by a mask: a
    float32 → bf16 → float32 round trip is one the compiler may drop as
    excess precision, and every term after the first would be 0."""
    out = []
    for _ in range(terms):
        top = lax.bitcast_convert_type(
            lax.bitcast_convert_type(v, jnp.uint32) & jnp.uint32(0xFFFF0000),
            jnp.float32)
        out.append(top.astype(jnp.bfloat16))            # exact: 16 bits
        v = v - top
    return out


def _spread_matrices(ht: int, P: int, Q: int):
    """(spread [3·ht, ht·P], repeat [ht, 3·ht, Q], gather [ht·P, ht]), bf16
    0/1: a pack's head h onto the P lanes of head h; onto all Q lanes; and the
    P lanes of head h summed into column h."""
    head = jnp.arange(ht)
    of_term = jnp.tile(head, _TERMS)                           # [3·ht]
    of_lane = jnp.repeat(head, P)                              # [ht·P]
    spread = of_term[:, None] == of_lane[None, :]
    repeat = jnp.broadcast_to(
        (head[:, None] == of_term[None, :])[:, :, None], (ht, _TERMS * ht, Q))
    gather = of_lane[:, None] == head[None, :]
    return tuple(m.astype(jnp.bfloat16) for m in (spread, repeat, gather))


def _gather(z, gather_ref, terms: int):
    """Σ over each head's P lanes of z [Q, ht·P] float32 → [Q, ht]."""
    return sum(_nn(t, gather_ref[...]) for t in _split(z, terms))


def _slabs(ht: int, P: int):
    """Heads taken side by side, a slab: as many as fill 128 lanes."""
    hp = max(d for d in range(1, ht + 1) if ht % d == 0 and d * P <= max(P, 128))
    return hp, ht // hp


def _lanes_of(k: int, hp: int, P: int, shape):
    """Which lanes of a slab [·, hp·P] are head k's (None: all of them)."""
    if hp == 1:
        return None
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    return (lane >= k * P) & (lane < (k + 1) * P)


def _ssd_fwd_kernel(x_ref, pk_ref, ct_ref, b_ref, c_ref, spread_ref, repeat_ref,
                    y_ref, *rest, ht: int, P: int, with_states: bool):
    """One chunk of one row, ``ht`` heads of one group. x [Q, ht·P]; the packs
    (_packs) [·, Q, 3·ht]; the log-decays cum again as rows [ht, Q]; B, C
    [Q, N] → y [Q, ht·P] float32 and (``with_states``) the state the heads
    start the chunk from, transposed: [N, ht·P] float32. The state rides
    along the row's chunks (the last grid axis) in ``h_scr``."""
    st_ref, h_scr = rest if with_states else (None,) + rest

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        h_scr[...] = jnp.zeros_like(h_scr)

    Bc, Cc = b_ref[...], c_ref[...]
    dtype, Q = Bc.dtype, Bc.shape[0]
    spread = spread_ref[...]
    dt, e_cum, to_end = (_nn(pk_ref[i], spread) for i in (0, 2, 3))  # [Q, ht·P]
    xf = x_ref[...].astype(jnp.float32)
    dx = (xf * dt).astype(dtype)                        # Δ·x
    h0 = h_scr[...]
    if with_states:
        st_ref[...] = h0
    # the carried state's part: e^{cum_i} C_i·h0
    y_state = _nn(Cc, h0.astype(dtype)) * e_cum
    cb = _nt(Cc, Bc)                                    # all ht heads share it
    causal = (lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
              >= lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    hp, slabs = _slabs(ht, P)
    for s in range(slabs):
        lanes = slice(s * hp * P, (s + 1) * hp * P)
        y = None
        for k in range(hp):
            h = s * hp + k
            # L_ij = e^{cum_i − cum_j}, masked before the exp: above the
            # diagonal the difference is positive and may overflow
            cum_i = _nn(pk_ref[1], repeat_ref[h])       # [Q, Q], rows alike
            L = jnp.exp(jnp.where(causal, cum_i - ct_ref[h:h + 1, :], -jnp.inf))
            yk = _nn((cb * L).astype(dtype), dx[:, lanes])
            mine = _lanes_of(k, hp, P, yk.shape)
            y = yk if y is None else jnp.where(mine, yk, y)
        y_ref[:, lanes] = y + y_state[:, lanes]
    # the state by the chunk's end:
    # e^{total} h0 + Σ_j e^{total − cum_j} Δ_j x_j ⊗ B_j
    h_scr[...] = h0 * e_cum[Q - 1:Q, :] + _tn(Bc, (xf * to_end).astype(dtype))


def _ssd_bwd_kernel(x_ref, pk_ref, ct_ref, b_ref, c_ref, spread_ref, repeat_ref,
                    gather_ref, st_ref, dy_ref,
                    dx_ref, ddt_ref, dcum_ref, dcum_rows_ref, db_ref, dc_ref,
                    rho_ref, ds_scr, *, ht: int, P: int):
    """The same tile's gradients, the row's chunks in reverse: ``ds_scr``
    carries the gradient of the state a chunk starts from (transposed, as the
    state is). d x [Q, ht·P]; d Δ (through Δ·x) [Q, ht]; d cum (through every
    decay) in two parts that add up, [Q, ht] and [ht, Q]; this tile's heads'
    part of d B and d C, [Q, N] float32; and ρ [1, ht·P], whose sum over a
    head's lanes is what reaches the PREVIOUS chunk's total log-decay (the
    state it ends with times that state's gradient).

    Everything [Q, Q] is held transposed, Mᵀ_ji = (C_i·B_j) e^{cum_i − cum_j}:
    d(Δx) = Mᵀ·dY and dMᵀ = Δx·dYᵀ are then plain products, and the row sums
    of W = dM ∘ M (what reaches cum_i) are sums over sublanes. W's column
    sums (what leaves cum_j) are Σ_p Δx_jp d(Δx)_jp, by the same rounded M —
    the two cancel to rounding only if they are made of the same numbers, and
    d A, a sum over every token of sums of them, is all cancellation. So too
    what leaves cum_j through e^{total − cum_j} and what reaches the total:
    both from the rounded Δx e^{total − cum_j} and the rounded dS the state's
    products took."""
    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    Bc, Cc = b_ref[...], c_ref[...]
    dtype, Q = Bc.dtype, Bc.shape[0]
    f32 = jnp.float32
    spread = spread_ref[...]
    dt, e_cum, to_end, e_end = (_nn(pk_ref[i], spread) for i in (0, 2, 3, 4))
    xf = x_ref[...].astype(f32)
    dx = (xf * dt).astype(dtype)
    dy = dy_ref[...]
    dyc = dy.astype(dtype)
    dy_state = (dy * e_cum).astype(dtype)               # e^{cum_i} dY_i
    h0, ds = st_ref[...], ds_scr[...]                   # [N, ht·P] float32
    h0c, dsc = h0.astype(dtype), ds.astype(dtype)
    b_ds = _nn(Bc, dsc)                                 # B_j·dS, a head's per lane
    dx_end = (xf * to_end).astype(dtype)                # e^{total − cum_j} Δ_j x_j
    y_state = _nn(Cc, h0c) * e_cum
    cbT = _nt(Bc, Cc)
    sub, lane = (lax.broadcasted_iota(jnp.int32, (Q, Q), d) for d in (0, 1))
    upper, above = sub <= lane, sub < lane
    # W's diagonal, (C_i·B_i) dY_i·Δx_i, is in its row sum and in its column
    # sum and means nothing to cum_i: it is left out of both, or a head that
    # forgets fast would have the rest drown in its rounding
    m_diag = jnp.sum(jnp.where(sub == lane, cbT, 0.0), axis=1, keepdims=True
                     ).astype(dtype).astype(f32)        # [Q, 1], as M holds it
    dcbT = jnp.zeros_like(cbT)
    hp, slabs = _slabs(ht, P)
    ddx_in, rows = [], []
    for s in range(slabs):
        lanes = slice(s * hp * P, (s + 1) * hp * P)
        dx_s, dy_s = dx[:, lanes], dyc[:, lanes]
        ddx_s = None
        for k in range(hp):
            h = s * hp + k
            cum_j = _nn(pk_ref[1], repeat_ref[h])       # [Q, Q], rows alike
            LT = jnp.exp(jnp.where(upper, ct_ref[h:h + 1, :] - cum_j, -jnp.inf))
            mT = (cbT * LT).astype(dtype)
            mine = _lanes_of(k, hp, P, dx_s.shape)
            dMT = _nt(dx_s if mine is None
                      else jnp.where(mine, dx_s, jnp.zeros_like(dx_s)), dy_s)
            dcbT = dcbT + dMT * LT
            rows.append(jnp.sum(jnp.where(above, dMT * mT.astype(f32), 0.0),
                                axis=0, keepdims=True))
            dk = _nn(mT, dy_s)
            ddx_s = dk if ddx_s is None else jnp.where(mine, dk, ddx_s)
        ddx_in.append(ddx_s)
    ddx_in = jnp.concatenate(ddx_in, axis=1) if slabs > 1 else ddx_in[0]
    ddx = ddx_in + b_ds * e_end                         # d(Δx)
    dx_ref[...] = (ddx * dt).astype(dx_ref.dtype)
    terms = _TERMS if dtype == f32 else 2
    ddt_ref[...] = _gather(xf * ddx, gather_ref, terms)
    dyf = dyc.astype(f32)
    dcum_ref[...] = _gather(
        dyf * y_state - dx.astype(f32) * (ddx_in - m_diag * dyf)
        - dx_end.astype(f32) * b_ds, gather_ref, terms)
    dcum_rows_ref[...] = jnp.concatenate(rows, axis=0) if ht > 1 else rows[0]
    ds_new = ds * e_cum[Q - 1:Q, :] + _tn(Cc, dy_state)
    ds_scr[...] = ds_new
    rho_ref[...] = jnp.sum(h0 * ds_new.astype(dtype).astype(f32), axis=0,
                           keepdims=True)
    dcbT = dcbT.astype(dtype)
    dc_ref[...] = _nt(dy_state, h0c) + _tn(dcbT, Bc)
    db_ref[...] = _nt(dx_end, dsc) + _nn(dcbT, Cc)


def _in_place(tiles: int, w: int) -> bool:
    """Whether [B, S, tiles·w] is blocked where it lies: a tile is whole
    lanes, or the only one. Else the tiles stand in front of the sequence,
    [B, tiles, S, w] (a copy)."""
    return tiles == 1 or w % 128 == 0


def _tiled(t, tiles: int, Q: int, chunk_of, per: int = 1):
    """t [B, S, tiles·w] as a kernel sees it (_in_place), and the BlockSpec
    that hands grid step (row, tile, chunk) its [Q, w]. ``per`` grid tiles
    share one of t's (a group's B and C)."""
    B, S, W = t.shape
    w = W // tiles
    if _in_place(tiles, w):
        return t, pl.BlockSpec(
            (None, Q, w), lambda b, i, c: (b, chunk_of(c), i // per))
    return (jnp.moveaxis(t.reshape(B, S, tiles, w), 2, 1), pl.BlockSpec(
        (None, None, Q, w), lambda b, i, c: (b, i // per, chunk_of(c), 0)))


def _tiled_shape(B: int, S: int, tiles: int, w: int, dtype):
    shape = (B, S, tiles * w) if _in_place(tiles, w) else (B, tiles, S, w)
    return jax.ShapeDtypeStruct(shape, dtype)


def _untiled(t, B: int, S: int):
    """A kernel output [B, S, tiles·w] or [B, tiles, S, w] → the former."""
    return t if t.ndim == 3 else jnp.moveaxis(t, 1, 2).reshape(B, S, -1)


def _packs(dt, cum, Q: int, GT: int, with_e_end: bool):
    """The heads' columns as the kernels take them, [B, GT, packs, S, 3·ht]
    bf16 (_split, a head tile's three terms side by side): Δ, cum, e^{cum},
    Δ·e^{total − cum} and (the backward's) e^{total − cum}."""
    B, S, H = dt.shape
    chunks = cum.reshape(B, S // Q, Q, H)
    e_end = jnp.exp(chunks[:, :, -1:] - chunks).reshape(B, S, H)
    cols = [dt, cum, jnp.exp(cum), dt * e_end] + [e_end] * with_e_end
    terms = jnp.stack([jnp.stack(_split(v), axis=2) for v in cols], axis=1)
    # [B, packs, S, 3, H] → [B, GT, packs, S, 3·ht]
    terms = terms.reshape(B, len(cols), S, _TERMS, GT, H // GT)
    return jnp.moveaxis(terms, 4, 1).reshape(B, GT, len(cols), S, -1)


@functools.partial(jax.jit, static_argnames=("kernel", "G", "Q", "interpret",
                                             "with_states"))
def _chunks_call(kernel: str, x, dt, cum, Bm, Cm, G: int, Q: int,
                 interpret: bool, states=None, dy=None,
                 with_states: bool = True):
    """The pallas_call of either kernel over grid (rows, head tiles, chunks).
    x [B, S, H·P]; dt, cum [B, S, H]; Bm, Cm [B, S, G·N]; S a whole number of
    chunks. Forward → (y [B, S, H·P] float32, states | None); backward
    (``states``, ``dy`` [B, S, H·P] given) → the five gradients, shaped as
    the inputs. A jit of its own: a step traces the scan a layer run, a
    direction and the recompute, and a set-up several programs — the kernel
    bodies (16 heads unrolled) are then traced and lowered once a shape, not
    once a call (4 s of the cell's set-up on the chip's host otherwise).
    Heads and channels, groups and states cross that boundary merged, as the
    mixer holds them: a [.., H, P] value there is a tiled layout of its own
    and a copy on either side (5 ms a step)."""
    B, S, H = dt.shape
    P, N = x.shape[2] // H, Bm.shape[2] // G
    hg, nc = H // G, S // Q
    ht, estimate = choose_ssd_tiling(kernel, B, S, Q, hg, P, N,
                                     x.dtype.itemsize)
    GT, per, W = H // ht, hg // ht, ht * P
    fwd = kernel == "fwd"
    chunk_of = (lambda c: c) if fwd else (lambda c: nc - 1 - c)
    tiled = functools.partial(_tiled, Q=Q, chunk_of=chunk_of)

    def by_chunk(*block):
        """[B, GT, chunks, *block] arrays: one block a grid step."""
        return pl.BlockSpec((None, None, None) + block, lambda b, i, c: (
            b, i, chunk_of(c)) + (0,) * len(block))

    def whole(t):
        return pl.BlockSpec(t.shape, lambda b, i, c: (0,) * t.ndim)

    packs = _packs(dt, cum, Q, GT, with_e_end=not fwd)
    n_packs = packs.shape[2]
    spread, repeat, gather = _spread_matrices(ht, P, Q)
    args, specs = zip(
        tiled(x, GT),
        (packs, pl.BlockSpec((None, None, n_packs, Q, _TERMS * ht),
                             lambda b, i, c: (b, i, 0, chunk_of(c), 0))),
        (cum.reshape(B, nc, Q, GT, ht).transpose(0, 3, 1, 4, 2),
         by_chunk(ht, Q)),
        tiled(Bm, G, per=per), tiled(Cm, G, per=per),
        (spread, whole(spread)), (repeat, whole(repeat)))
    x_spec = specs[0]
    state_shape = jax.ShapeDtypeStruct((B, GT, nc, N, W), jnp.float32)
    shape = functools.partial(_tiled_shape, B, S, GT)
    if fwd:
        body = functools.partial(_ssd_fwd_kernel, ht=ht, P=P,
                                 with_states=with_states)
        out_shape = [shape(W, jnp.float32)] + [state_shape] * with_states
        out_specs = [x_spec] + [by_chunk(N, W)] * with_states
    else:
        body = functools.partial(_ssd_bwd_kernel, ht=ht, P=P)
        dy, dy_spec = tiled(dy, GT)
        args += (gather, states, dy)
        specs += (whole(gather), by_chunk(N, W), dy_spec)
        col = jax.ShapeDtypeStruct((B, GT, nc, Q, ht), jnp.float32)
        bc = jax.ShapeDtypeStruct((B, GT, nc, Q, N), jnp.float32)
        out_shape = [shape(W, x.dtype), col, col,
                     jax.ShapeDtypeStruct((B, GT, nc, ht, Q), jnp.float32),
                     bc, bc,
                     jax.ShapeDtypeStruct((B, GT, nc, 1, W), jnp.float32)]
        out_specs = [x_spec, by_chunk(Q, ht), by_chunk(Q, ht), by_chunk(ht, Q),
                     by_chunk(Q, N), by_chunk(Q, N), by_chunk(1, W)]
    out = pl.pallas_call(
        body, grid=(B, GT, nc), in_specs=list(specs), out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((N, W), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=None if estimate <= VMEM_BUDGET_BYTES else min(
                VMEM_CEILING_BYTES, estimate + estimate // 2)),
        interpret=interpret,
        name=scopes.SSD_CHUNK_FWD_KERNEL if fwd else scopes.SSD_CHUNK_BWD_KERNEL,
    )(*args)
    if fwd:
        return _untiled(out[0], B, S), (out[1] if with_states else None)
    dx, ddt, dcum, dcum_rows, db, dc, rho = out
    # [B, GT, nc, Q, ·] → [B, S, GT · ·]
    seq = lambda t: _untiled(t.reshape(B, GT, S, -1), B, S)
    # what reaches a chunk's total log-decay — its last row — is the next
    # chunk's ρ, a head's lanes summed
    d_total = jnp.pad(rho.reshape(B, GT, nc, ht, P).sum(-1)[:, :, 1:],
                      ((0, 0), (0, 0), (0, 1), (0, 0)))        # [B, GT, nc, ht]
    dcum = (dcum + jnp.swapaxes(dcum_rows, 3, 4)).at[:, :, :, -1].add(d_total)
    # a group's heads in `per` tiles: their parts of d B and d C add up
    db, dc = (seq(t).reshape(B, S, G, per, N).sum(3).reshape(Bm.shape)
              .astype(Bm.dtype) for t in (db, dc))
    return _untiled(dx, B, S), seq(ddt), seq(dcum), db, dc


def _merged(x, Bm, Cm):
    """x [B, S, H, P], Bm, Cm [B, S, G, N] with their last two dims merged."""
    return tuple(t.reshape(t.shape[:2] + (-1,)) for t in (x, Bm, Cm))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd_chunks(x, dt, cum, Bm, Cm, Q, interpret):
    xm, bm, cm = _merged(x, Bm, Cm)
    return _chunks_call("fwd", xm, dt, cum, bm, cm, Bm.shape[2], Q, interpret,
                        with_states=False)[0].reshape(x.shape)


def _ssd_chunks_fwd(x, dt, cum, Bm, Cm, Q, interpret):
    xm, bm, cm = _merged(x, Bm, Cm)
    y, states = _chunks_call("fwd", xm, dt, cum, bm, cm, Bm.shape[2], Q,
                             interpret)
    # by name, so that a checkpoint policy that keeps it spares the backward
    # a second forward call
    states = checkpoint_name(states, scopes.RES_SSD_STATES)
    return y.reshape(x.shape), (x, dt, cum, Bm, Cm, states)


def _ssd_chunks_bwd(Q, interpret, res, dy):
    x, dt, cum, Bm, Cm, states = res
    xm, bm, cm = _merged(x, Bm, Cm)
    dx, ddt, dcum, db, dc = _chunks_call(
        "bwd", xm, dt, cum, bm, cm, Bm.shape[2], Q, interpret, states=states,
        dy=dy.reshape(xm.shape))
    return (dx.reshape(x.shape), ddt, dcum, db.reshape(Bm.shape),
            dc.reshape(Cm.shape))


_ssd_chunks.defvjp(_ssd_chunks_fwd, _ssd_chunks_bwd)


def _ssd_scan(x, dt, A, Bm, Cm, *, chunk: int, interpret: bool):
    Bsz, S, H = dt.shape
    Q = min(chunk, S)
    pad = -S % Q
    if pad:
        x, dt, Bm, Cm = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                         for t in (x, dt, Bm, Cm))
    # cumulative log-decay inside a chunk, inclusive: cum_i = Σ_{k≤i} Δ_k·A —
    # as a product with the lower triangle of ones (a windowed sum is slow on
    # the chip), every term in float32
    cum = jnp.einsum("ij,bcjh->bcih", jnp.tril(jnp.ones((Q, Q), jnp.float32)),
                     (dt * A).reshape(Bsz, -1, Q, H),
                     precision=lax.Precision.HIGHEST).reshape(dt.shape)
    return _ssd_chunks(x, dt, cum, Bm, Cm, Q, interpret)[:, :S]


@jax.named_scope(scopes.SSD_SCAN)
def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
             Cm: jax.Array, chunk: int) -> jax.Array:
    """The recurrence above without its ``D·x`` term. x [B, S, H, P] in the
    compute dtype, dt [B, S, H] float32 (after softplus), A [H] float32
    (negative), Bm / Cm [B, S, G, N] → y [B, S, H, P] float32. A row shorter
    than a chunk, or not a whole number of them, is padded with Δ = 0 steps
    (a = 1, nothing added to the state) that are cut off again. Under a mesh
    (parallel/mesh.current_mesh) each device scans its own rows, and its own
    groups' heads where tp divides the groups; the kernels compile on a TPU
    and interpret elsewhere (attention.resolve_attention's rule)."""
    mesh = mesh_lib.current_mesh()
    _, interpret = resolve_attention(mesh=mesh)
    fn = functools.partial(_ssd_scan, chunk=chunk, interpret=interpret)
    if mesh is None:
        return fn(x, dt, A, Bm, Cm)
    batch_axes, head_ax = batch_head_axes(mesh, x.shape[0], Bm.shape[2])
    tok, vec = PSpec(batch_axes, None, head_ax, None), PSpec(head_ax)
    return jax.shard_map(
        fn, mesh=mesh, out_specs=tok, check_vma=False,
        in_specs=(tok, PSpec(batch_axes, None, head_ax), vec, tok, tok),
    )(x, dt, A, Bm, Cm)


def _gated_group_norm(y, z, g, groups: int, eps: float):
    """RMSNorm over each group's channels of y ⊙ silu(z), scaled by g:
    y, z [B, S, inner] → [B, S, inner] in z's dtype."""
    yf = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    shape = yf.shape
    yg = yf.reshape(shape[:-1] + (groups, shape[-1] // groups))
    yg = yg * lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + eps)
    return (yg.reshape(shape) * g.astype(jnp.float32)).astype(z.dtype)


@jax.named_scope(scopes.MAMBA)
def mamba2_mixer(u: jax.Array, p: Dict[str, Any], *, heads: int, head_dim: int,
                 groups: int, state: int, chunk: int, eps: float) -> jax.Array:
    """u [B, S, D] (normed, compute dtype) → the mixer's output [B, S, D] in
    float32 (the out-projection's accumulator; the caller adds the residual).
    ``p`` holds one layer's tensors, the matmul weights in the compute dtype."""
    Bsz, S, _ = u.shape
    inner, gn = heads * head_dim, groups * state
    z = checkpoint_name(jnp.einsum("bsd,de->bse", u, p["w_z"]),
                        scopes.RES_MAMBA_Z)
    xbc = checkpoint_name(jnp.einsum("bsd,de->bse", u, p["w_xbc"]),
                          scopes.RES_MAMBA_XBC)
    dt = checkpoint_name(jnp.einsum("bsd,dh->bsh", u, p["w_dt"],
                                    preferred_element_type=jnp.float32),
                         scopes.RES_MAMBA_DT)
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"])).astype(u.dtype)
    x = xbc[..., :inner].reshape(Bsz, S, heads, head_dim)
    Bm = xbc[..., inner:inner + gn].reshape(Bsz, S, groups, state)
    Cm = xbc[..., inner + gn:].reshape(Bsz, S, groups, state)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y = ssd_scan(x, dt, A, Bm, Cm, chunk)
    y = y + x.astype(jnp.float32) * p["D"].astype(jnp.float32)[:, None]
    y = checkpoint_name(y.astype(u.dtype).reshape(Bsz, S, inner),
                        scopes.RES_SSD_Y)
    y = _gated_group_norm(y, z, p["gate_norm"], groups, eps)
    return jnp.einsum("bse,ed->bsd", y, p["w_out"],
                      preferred_element_type=jnp.float32)
