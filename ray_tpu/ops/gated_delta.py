"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464): a linear-attention
recurrence whose state is CORRECTED, not only added to. One row, one value
head, S ∈ R^{d_k × d_v} in float32:

    S ← e^{g_t} · S          S ← S + k_t ⊗ β_t (v_t − Sᵀ k_t)          o_t = Sᵀ q_t

g_t ≤ 0 a scalar a head and token (the gate), β_t ∈ (0, 1) the write
strength; ``H_k`` key heads serve ``H_v = r · H_k`` value heads, each key head
the r value heads that follow each other.

``gated_delta_scan`` computes it in chunks of C tokens (section 3.3 of the
paper). With ``c_i = Σ_{j≤i} g_j`` from the chunk's start, ``γ = e^c``, ``Γ_ij
= e^{c_i − c_j}`` (the exponential of a masked difference, never a quotient),
K, V, Q the chunk's rows and S the state at its start:

    A = strict_lower(diag(β) · (K Kᵀ ⊙ Γ))           X = (I + A)⁻¹
    W = X · (β γ ⊙ K)     U = X · (β ⊙ V)            D = U − W S
    O = (γ ⊙ Q) S + lower(Q Kᵀ ⊙ Γ) D               S ← γ_C S + ((γ_C / γ) ⊙ K)ᵀ D

``ops/mamba2.ssd_scan`` cannot express it: its chunk is a masked product,
this one a masked product AFTER a unit-lower-triangular solve inside every
chunk, and the backward differentiates through that solve (``d A = −Xᵀ·d[W|U]
· [W|U]ᵀ``). Three Pallas kernels behind ``jax.custom_vjp``:

- ``gated_delta_fwd_solve`` writes X of every chunk. X depends on k, c and β
  alone, not on the carried state, so its grid has NO sequential axis: every
  chunk of every key head is its own problem. Stored are the value heads'
  [C, C] diagonal blocks alone, in the compute dtype — the one form anything
  reads X in — a tile's heads side by side along the lanes: ``[B, H_v / ht,
  chunks, C, ht·C]``, a tenth of the float32 states' bytes.
- ``gated_delta_fwd`` and ``gated_delta_bwd`` read X's block and make the
  rest of the chunk form on ssd_scan's grid: a grid step is one chunk of one
  row for a TILE of the value heads of one key head, the row's chunks the
  last, sequential axis along which the [d_k, d_v] states (backward: their
  gradients) ride in VMEM. X is a residual, not a differentiated value:
  ``d A`` leaves the backward kernel, which needs K Kᵀ ⊙ Γ for it still.

The residuals are (q, k, v, c, β, the state each chunk starts from, X), the
last two by name (``delta_states``, ``delta_x``): with no ``remat`` the solve
runs once a step by construction; under a checkpoint policy that keeps
``delta_x`` the recompute's dead-code elimination drops the solve's call and
runs the solve-free forward alone — the step solves once a layer, where the
two kernels that each solved inside themselves did it three times.

The tile's heads are stacked along the ROWS of every [C, C] matrix — ``ht``
heads are one [ht·C, ht·C] problem whose off-diagonal blocks the mask
empties, so at the published C = 64 the two value heads of a key head fill
the MXU's 128 rows and columns, and the key head's K and Q are read once for
both: no value head's copy of k or q exists in HBM. A grid step takes several
such key heads (``key_tile``), each its own stack: the solve is a chain of
dependent products, a chain a key head, and independent chains in one basic
block are what lets the scheduler fill one's latency with another's passes.

The solve is blocked forward substitution by doubling: with the inverse of the
b-blocks on the diagonal in hand, ``X ← X − X · L · X`` (L the blocks left of
and below them inside each 2b-block) gives the 2b-blocks'; log₂ C levels,
exact in exact arithmetic. Its products, the decays, the masks' exponentials
and the state are float32 — a float32 product on the MXU as three bf16 passes
of two-term splits (``_mm32``: 2⁻¹⁶) —; every other product takes its operands
in the compute dtype and accumulates in float32.

What XLA keeps: the cumulative gates ``c`` (a product with a triangle of ones,
whose transpose AD makes d g from d c with), padding and layout. Beside the
kernels stands ``gated_delta_chunked``, the same chunk form in plain XLA under
AD (``jax.scipy``'s triangular solve): what runs where the platform is not a
TPU (attention.resolve_attention's rule: one switch, the platform) and the
tests' middle term between the kernels and the token-by-token recurrence.
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
# (float32-accumulating products a · bᵀ, aᵀ · b, a · b: the scan kernels')
from ray_tpu.ops.mamba2 import _nn, _nt, _tn
from ray_tpu.parallel import mesh as mesh_lib
from ray_tpu.tracing import names as scopes

CHUNK = 64          # the published chunk (modeling_qwen3_next's fallback)
_MXU = 128          # rows and columns of one MXU pass


# --------------------------------------------------------------------------- #
# The chunk form in plain XLA
# --------------------------------------------------------------------------- #

def _padded(q, k, v, g, beta, C: int):
    """The row padded to whole chunks with steps that change nothing: g = 0
    (no decay), β = 0 (nothing written)."""
    pad = -q.shape[1] % C
    if not pad:
        return q, k, v, g, beta
    return tuple(jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                 for t in (q, k, v, g, beta))


def _cumulative(g, C: int):
    """c_i = Σ_{j≤i} g_j inside each chunk, inclusive: g [B, S, H] float32, S
    whole chunks. A product with the lower triangle of ones (a windowed sum
    is slow on the chip), every term in float32."""
    B, S, H = g.shape
    return jnp.einsum("ij,bcjh->bcih", jnp.tril(jnp.ones((C, C), jnp.float32)),
                      g.reshape(B, S // C, C, H),
                      precision=lax.Precision.HIGHEST).reshape(g.shape)


def gated_delta_chunked(q, k, v, g, beta, chunk: int = CHUNK):
    """gated_delta_scan's result by the module docstring's chunk form in
    plain XLA, differentiated by AD: a ``lax.scan`` over the row's chunks,
    the solve ``jax.scipy.linalg.solve_triangular``. The same operand
    precisions as the kernels (products in v's dtype, float32 solve, decays
    and state)."""
    from jax.scipy.linalg import solve_triangular

    B, S, Hk, dk = q.shape
    Hv, dv = v.shape[2:]
    r, C, dt = Hv // Hk, min(chunk, S), v.dtype
    q, k, v, g, beta = _padded(q, k, v, g, beta, C)
    cum = _cumulative(g, C)
    nc = q.shape[1] // C

    def chunks(t):      # [B, S, H, ·] → [nc, B, H, C, ·]
        return jnp.moveaxis(t.reshape((B, nc, C) + t.shape[2:]), (1, 3), (0, 2))

    def by_value_head(t):           # a key head's rows for each of its r
        return jnp.repeat(t, r, axis=2)

    qs, ks, vs = by_value_head(chunks(q)), by_value_head(chunks(k)), chunks(v)
    cs, bs = chunks(cum[..., None]), chunks(beta[..., None])  # [nc,B,Hv,C,1]
    tri = jnp.tril(jnp.ones((C, C), bool))
    f32 = jnp.float32

    def mm(a, b, spec):
        return jnp.einsum(spec, a.astype(dt), b.astype(dt),
                          preferred_element_type=f32)

    def step(S0, xs):
        Q, K, V, c, b = xs
        diff = c - jnp.swapaxes(c, -1, -2)
        G = jnp.where(tri, jnp.exp(jnp.where(tri, diff, 0.0)), 0.0)
        gam, end = jnp.exp(c), c[..., -1:, :]
        Kf = K.astype(f32)
        Ms = jnp.where(tri & ~jnp.eye(C, dtype=bool),
                       mm(K, K, "bhik,bhjk->bhij") * G, 0.0)
        X = solve_triangular(jnp.eye(C, dtype=f32) + b * Ms,
                             jnp.broadcast_to(jnp.eye(C, dtype=f32), Ms.shape),
                             lower=True, unit_diagonal=True)
        W = mm(X, b * gam * Kf, "bhij,bhjk->bhik")
        U = mm(X, b * V.astype(f32), "bhij,bhjv->bhiv")
        D = U - mm(W, S0, "bhik,bhkv->bhiv")
        P = mm(Q, K, "bhik,bhjk->bhij") * G
        O = (mm(gam * Q.astype(f32), S0, "bhik,bhkv->bhiv")
             + mm(P, D, "bhij,bhjv->bhiv"))
        S1 = jnp.exp(end) * S0 + mm(jnp.exp(end - c) * Kf, D,
                                    "bhik,bhiv->bhkv")
        return S1, O.astype(dt)

    _, out = lax.scan(step, jnp.zeros((B, Hv, dk, dv), f32),
                      (qs, ks, vs, cs, bs))
    return jnp.moveaxis(out, (0, 2), (1, 3)).reshape(B, nc * C, Hv, dv)[:, :S]


# --------------------------------------------------------------------------- #
# Tiling
# --------------------------------------------------------------------------- #

class DeltaTiling(NamedTuple):
    head_tile: int            # value heads of one key head a grid step stacks
    key_tile: int             # key heads (each its own stack) a grid step takes
    vmem_estimate: int        # bytes, _vmem_estimate() of this choice


_KERNELS = ("solve", "fwd", "bwd")
# key heads a grid step takes at most: each is an unrolled copy of the body
# (program size), and past a few chains the MXU has no latency left to fill
# (the solve kernel alone at 4 × 8,192 tokens, 16 key heads: 16.9 / 16.1 /
# 15.7 / 15.5 ms at 2 / 4 / 8 / 16; PERF.md §6, PR 62)
_MAX_KEY_TILE = 4

_decisions: Dict[tuple, Dict[str, Any]] = {}


def delta_tiling_decisions() -> List[Dict[str, Any]]:
    """Every distinct tiling this process has traced a delta-rule kernel
    with, as the ``ops/delta_tiling`` events carry them."""
    return list(_decisions.values())


def _stackable(r: int, C: int) -> List[int]:
    """The divisors of r whose stack of heads is at most one MXU pass tall
    (a taller one multiplies the emptied off-diagonal blocks for nothing),
    the largest first; one head always."""
    return [t for t in range(r, 0, -1)
            if r % t == 0 and (t == 1 or t * C <= _MXU)]


def _vmem_estimate(kernel: str, C: int, ht: int, kt: int, dk: int, dv: int,
                   dtype_bytes: int) -> int:
    """VMEM bytes one grid step needs: every in/out block twice (Pallas
    double-buffers them), the carried states once, and the float32 values
    the body holds at once — a key head's stacked [N, N] matrices (N = ht·C)
    and [N, d] operands and products. The solve kernel has k, the rows and
    X's blocks, no state and no [N, d_v] value: its live set is the levels'
    squares beside the stacked K. The other two read X's block and hold
    none of the solve's. An upper bound, not Mosaic's own figure."""
    blk, a, N = vmem_block_bytes, dtype_bytes, ht * C
    square, wide = blk((N, N), 4), blk((N, max(dk, dv)), 4)
    x_block = kt * blk((C, N), a)
    if kernel == "solve":
        io = blk((C, kt * dk), a) + kt * blk((2, N), 4) + x_block
        return 2 * io + kt * (10 * square + blk((N, dk), 4))
    state = kt * ht * blk((dk, dv), 4)
    io = (2 * blk((C, kt * dk), a) + 2 * blk((C, kt * ht * dv), a)  # q k v o
          + kt * blk((2, N), 4) + x_block + state)              # rows, X, states
    live = kt * (6 * square + 10 * wide)
    if kernel == "bwd":
        io += (2 * blk((C, kt * ht * dv), a)                    # d o, d v
               + 2 * blk((C, kt * dk), 4) + kt * blk((2, N), 4))
        live += kt * (8 * square + 10 * wide)
    return 2 * io + state + live


def choose_delta_tiling(kernel: str, rows: int, S: int, C: int, Hk: int,
                        r: int, dk: int, dv: int, dtype_bytes: int
                        ) -> DeltaTiling:
    """THE rule for how a delta-rule kernel (``"solve"`` / ``"fwd"`` /
    ``"bwd"``) tiles its work: a grid step is one chunk of one row for
    ``key_tile`` key heads, each with ``head_tile`` of its r value heads
    stacked along the rows of the chunk's matrices. ``head_tile`` is the
    largest divisor of r whose stack is at most one MXU pass tall
    (``head_tile · C ≤ 128``, _stackable) — ONE for the three kernels of a
    scan, whose X is laid out by it: the largest that the backward, the
    widest of them, has room for —; ``key_tile`` — where a step holds ALL of
    a key head's value heads — the largest divisor of H_k up to _MAX_KEY_TILE
    that this kernel has room for. Room is half of what a
    kernel may be given (VMEM_CEILING_BYTES; past Mosaic's default the call
    raises its limit, as the scan kernels do). A shape of which not even one
    head fits is refused. Recorded once a distinct decision
    (``ops/delta_tiling``)."""
    if kernel not in _KERNELS:
        raise ValueError(f"unknown delta-rule kernel {kernel!r}")
    estimate = functools.partial(_vmem_estimate, C=C, dk=dk, dv=dv,
                                 dtype_bytes=dtype_bytes)
    room = VMEM_CEILING_BYTES // 2
    tiles = [t for t in _stackable(r, C)
             if estimate("bwd", ht=t, kt=1) <= room]
    if not tiles:
        raise ValueError(
            f"gated_delta_scan {kernel}: one head of a chunk does not fit "
            f"VMEM for chunk C={C}, widths d_k={dk} d_v={dv} ({dtype_bytes}-"
            f"byte operands): the backward's estimated at "
            f"{estimate('bwd', ht=1, kt=1)} bytes of {room}; use a smaller "
            f"chunk")
    ht = tiles[0]
    kt = 1 if ht < r else next(
        t for t in range(min(Hk, _MAX_KEY_TILE), 0, -1)
        if Hk % t == 0 and estimate(kernel, ht=ht, kt=t) <= room)
    tiling = DeltaTiling(ht, kt, estimate(kernel, ht=ht, kt=kt))
    record_decision(_decisions, scopes.DELTA_TILING, dict(zip(
        scopes.DELTA_TILING_ARGS,
        (kernel, rows, S, C, Hk, r, dk, dv) + tuple(tiling))))
    return tiling


def solve_flops(C: int, r: int, dk: int) -> int:
    """FLOPs the solve kernel SPENDS on one chunk of one key head: a stack's
    K Kᵀ and, a doubling level, two products of three bf16 passes at the
    stack's N = head_tile · C — what making X again costs the step (the
    remat rule's price), not what the recurrence requires."""
    ht = _stackable(r, C)[0]
    N, levels = ht * C, max(0, C.bit_length() - 2)
    return (r // ht) * 2 * (N * N * dk + levels * 2 * 3 * N ** 3)


# --------------------------------------------------------------------------- #
# The kernels
# --------------------------------------------------------------------------- #

def _cut(x):
    """float32 x → (hi, lo) bf16 with hi + lo = x to 2⁻¹⁷: hi is x CUT to
    bf16's 16 bits by a mask (exact; a float32 → bf16 → float32 round trip is
    one a compiler may drop as excess precision), lo what is left, rounded."""
    hi = lax.bitcast_convert_type(
        lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(0xFFFF0000),
        jnp.float32)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def _mm32(a, b):
    """a · b of float32 operands GIVEN AS THEIR CUTS (hi, lo) to 2⁻¹⁶ on a
    bf16 MXU: three passes (the lo · lo term is below the rest's error)."""
    (ah, al), (bh, bl) = a, b
    return _nn(ah, bh) + (_nn(ah, bl) + _nn(al, bh))


def _solve(A, i, j, C: int):
    """(I + A)⁻¹ of A [N, N] float32, strictly lower triangular inside each
    C-block of the diagonal and empty outside them (``i``, ``j`` the row and
    column iotas): blocked forward substitution by doubling — the inverse of
    the b-blocks on the diagonal gives the 2b-blocks' by ``X − X · L · X``, L
    the part of A left of and below them inside a 2b-block: where i and j
    first differ in bit b (A is empty above the diagonal already). A is cut
    once and its cuts masked a level; X is cut once a level for both of the
    level's products. The first level (b = 1: X = I) needs no product."""
    differ = i ^ j
    zero = jnp.zeros((), jnp.bfloat16)
    cuts = _cut(A)

    def level(b):
        return (differ & -b) == b

    X = (i == j).astype(jnp.float32) - jnp.where(level(1), A, 0.0)
    b = 2
    while b < C:
        at = level(b)
        L = tuple(jnp.where(at, t, zero) for t in cuts)
        Xc = _cut(X)
        X = X - _mm32(Xc, _cut(_mm32(L, Xc)))
        b *= 2
    return X


class _Gates(NamedTuple):
    """A stack's masks and decays (``_gates``), N = ht·C rows."""
    c_row: Any      # [1, N] c along the lanes
    c: Any          # [N, 1] c, β down the sublanes
    b: Any
    G: Any          # [N, N] Γ, the diagonal's 1 included, 0 outside the mask
    same: Any       # the masks: same head; and i ≥ j; and i > j; i = j
    incl: Any
    strict: Any
    eye: Any
    i: Any          # the row and column iotas
    j: Any


class _Chunk(NamedTuple):
    """What the forward and the backward kernel make of a grid step's blocks
    (``_chunk``): the ht heads stacked along the N = ht·C rows."""
    K: Any          # [N, dk] the key head's rows, once a stacked head
    Q: Any
    V: Any          # [N, dv]
    b: Any          # [N, 1] β
    gam: Any        # [N, 1] γ = e^c
    gam_end: Any    # [N, 1] γ at its head's last token
    e: Any          # [N, 1] γ_C / γ
    G: Any          # [N, N] Γ
    Xb: Any         # [N, N] (I + A)⁻¹ in the compute dtype
    Kg: Any         # [N, dk] float32 γ ⊙ K
    Qg: Any
    Kd: Any         # (γ_C / γ) ⊙ K
    W: Any          # [N, dk] float32
    U: Any          # [N, dv]
    D: Any          # [N, dv]
    P: Any          # [N, N] lower(Q Kᵀ ⊙ Γ)
    incl: Any       # the masks: same head and i ≥ j; and i > j
    strict: Any
    eye: Any


def _heads(x, ht: int, C: int):
    """A stacked [N, ·] value as its ht heads' [C, ·]."""
    return [x[h * C:(h + 1) * C] for h in range(ht)]


def _stack(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _to_col(row, eye):
    """[1, N] → [N, 1]: the diagonal of the row spread down the sublanes."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _to_row(col, eye):
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _gates(rows, ht: int, C: int) -> _Gates:
    """rows [2, N] (c, β) → the stack's masks, c and β as columns, and Γ."""
    N = ht * C
    c_row, b_row = rows[0:1, :], rows[1:2, :]
    i = lax.broadcasted_iota(jnp.int32, (N, N), 0)
    j = lax.broadcasted_iota(jnp.int32, (N, N), 1)
    eye = i == j
    same = (i & -C) == (j & -C)
    incl, strict = same & (i >= j), same & (i > j)
    c, b = _to_col(c_row, eye), _to_col(b_row, eye)
    G = jnp.where(incl, jnp.exp(jnp.where(incl, c - c_row, 0.0)), 0.0)
    return _Gates(c_row, c, b, G, same, incl, strict, eye, i, j)


def _chunk(K, Q, V, rows, X, states, ht: int, C: int) -> _Chunk:
    """The module docstring's chunk form of one key head's stack, up to D
    and P, from the solve kernel's X: K, Q [C, dk] (the key head's), V [N,
    dv] (its ht value heads' rows stacked), rows [2, N] (c, β), X [C, N]
    (the heads' blocks side by side), states ht × [dk, dv]."""
    f32 = jnp.float32
    dt = V.dtype
    K, Q = _stack([K] * ht), _stack([Q] * ht)
    t = _gates(rows, ht, C)
    c_end = jnp.sum(jnp.where(t.same & ((t.j & (C - 1)) == C - 1), t.c_row,
                              0.0), axis=1, keepdims=True)
    gam, gam_end, e = jnp.exp(t.c), jnp.exp(c_end), jnp.exp(c_end - t.c)
    # every head's rows get the tile's blocks; the mask leaves each its own
    Xb = X if ht == 1 else jnp.where(t.same, _stack([X] * ht),
                                     jnp.zeros((), X.dtype))
    Kf = K.astype(f32)
    Kg, Qg, Kd = gam * Kf, gam * Q.astype(f32), e * Kf
    W = _nn(Xb, (t.b * Kg).astype(dt))
    U = _nn(Xb, (t.b * V.astype(f32)).astype(dt))
    D = U - _stack([_nn(w.astype(dt), s.astype(dt))
                    for w, s in zip(_heads(W, ht, C), states)])
    P = jnp.where(t.incl, _nt(Q, K) * t.G, 0.0)
    return _Chunk(K, Q, V, t.b, gam, gam_end, e, t.G, Xb, Kg, Qg, Kd,
                  W, U, D, P, t.incl, t.strict, t.eye)


def _solve_kernel(k_ref, rows_ref, x_ref, *, kt: int, ht: int, C: int):
    """(I + A)⁻¹ of one chunk of one row for kt key heads and the ht value
    heads of each: k [C, kt·dk]; rows [kt, 2, N] float32 (c, β) → x [kt, C,
    N], head h's [C, C] block at lanes h·C: (the stacked X is empty outside
    its diagonal blocks: its heads' rows summed lay them side by side).
    Every grid step is its own problem: no state, no sequential axis."""
    dk = k_ref.shape[-1] // kt
    for a in range(kt):
        K = _stack([k_ref[:, a * dk:(a + 1) * dk]] * ht)
        t = _gates(rows_ref[a], ht, C)
        Ms = jnp.where(t.strict, _nt(K, K) * t.G, 0.0)
        X = _solve(t.b * Ms, t.i, t.j, C)
        x_ref[a] = sum(_heads(X, ht, C)).astype(x_ref.dtype)


def _key_heads(q_ref, k_ref, v_ref, rows_ref, x_ref, kt: int, ht: int):
    """A grid step's blocks as its kt key heads' (K, Q, V stacked, rows, X):
    q, k [C, kt·dk]; v [C, kt·ht·dv]; rows [kt, 2, N]; x [kt, C, N]."""
    dk, dv = k_ref.shape[-1] // kt, v_ref.shape[-1] // (kt * ht)
    for a in range(kt):
        lanes = slice(a * dk, (a + 1) * dk)
        V = _stack([v_ref[:, (a * ht + h) * dv:(a * ht + h + 1) * dv]
                    for h in range(ht)])
        yield k_ref[:, lanes], q_ref[:, lanes], V, rows_ref[a], x_ref[a]


def _fwd_kernel(q_ref, k_ref, v_ref, rows_ref, x_ref, o_ref, *rest, kt: int,
                ht: int, C: int, with_states: bool):
    """One chunk of one row for kt key heads and the ht value heads of each.
    Blocks: q, k [C, kt·dk]; v, o [C, kt·ht·dv]; rows [kt, 2, N] float32 (c,
    β); x [kt, C, N] (_solve_kernel's); with_states the state each head's
    chunk STARTS from, [kt·ht, dk, dv] float32. The states ride in ``s_ref``
    along the chunks."""
    states_ref, s_ref = rest if with_states else (None,) + rest
    dt = v_ref.dtype
    dv = v_ref.shape[-1] // (kt * ht)

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    if with_states:
        states_ref[...] = s_ref[...]
    for a, (K, Q, V, rows, X) in enumerate(
            _key_heads(q_ref, k_ref, v_ref, rows_ref, x_ref, kt, ht)):
        states = [s_ref[a * ht + h] for h in range(ht)]
        m = _chunk(K, Q, V, rows, X, states, ht, C)
        Db = m.D.astype(dt)
        inner = _nn(m.P.astype(dt), Db)
        for h, (qg, kd, d, s0, o) in enumerate(zip(
                _heads(m.Qg, ht, C), _heads(m.Kd, ht, C), _heads(Db, ht, C),
                states, _heads(inner, ht, C))):
            at = a * ht + h
            o_ref[:, at * dv:(at + 1) * dv] = (
                o + _nn(qg.astype(dt), s0.astype(dt))).astype(o_ref.dtype)
            s_ref[at] = m.gam_end[h * C:h * C + 1] * s0 + _tn(kd.astype(dt), d)


def _bwd_kernel(q_ref, k_ref, v_ref, rows_ref, x_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, drows_ref, ds_ref, *, kt: int, ht: int,
                C: int):
    """The same tile's gradients, the chunks reversed: d q, d k [C, kt·dk]
    float32 (a key head's stacked heads summed), d v [C, kt·ht·dv], d rows
    [kt, 2, N] (d c, d β). X is read, not differentiated: d A leaves here.
    ``ds_ref`` carries the gradient of the state a chunk ENDS with."""
    f32 = jnp.float32
    dt = v_ref.dtype
    dk, dv = k_ref.shape[-1] // kt, v_ref.shape[-1] // (kt * ht)
    heads = functools.partial(_heads, ht=ht, C=C)
    lane = lax.broadcasted_iota(jnp.int32, (1, ht * C), 1)

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    for a, (K, Q, V, rows, X) in enumerate(
            _key_heads(q_ref, k_ref, v_ref, rows_ref, x_ref, kt, ht)):
        states = [states_ref[a * ht + h] for h in range(ht)]
        m = _chunk(K, Q, V, rows, X, states, ht, C)
        Ms = jnp.where(m.strict, _nt(m.K, m.K) * m.G, 0.0)
        dO = _stack([do_ref[:, (a * ht + h) * dv:(a * ht + h + 1) * dv]
                     for h in range(ht)])
        dS1 = [ds_ref[a * ht + h] for h in range(ht)]
        S0b = [s.astype(dt) for s in states]
        dS1b = [s.astype(dt) for s in dS1]
        Pb, Db, Wb, Ub = (x.astype(dt) for x in (m.P, m.D, m.W, m.U))
        Kdb = heads(m.Kd.astype(dt))

        # O = Qg·S0 + P·D and S1 = γ_C·S0 + Kdᵀ·D
        dD = _tn(Pb, dO) + _stack([_nn(kd, s) for kd, s in zip(Kdb, dS1b)])
        dP = jnp.where(m.incl, _nt(dO, Db), 0.0)
        dQg = _stack([_nt(o, s) for o, s in zip(heads(dO), S0b)])
        dKd = _stack([_nt(d, s) for d, s in zip(heads(Db), dS1b)])
        # D = U − W·S0, [W | U] = X·[βγK | βV]
        dDb = dD.astype(dt)
        dW = -_stack([_nt(d, s) for d, s in zip(heads(dDb), S0b)])
        dRk, dRv = _tn(m.Xb, dW.astype(dt)), _tn(m.Xb, dDb)
        # X = (I + A)⁻¹: d A = −Xᵀ·d[W|U]·[W|U]ᵀ; A = β ⊙ Ms
        dA = -jnp.where(m.strict, _nt(dRk.astype(dt), Wb)
                        + _nt(dRv.astype(dt), Ub), 0.0)
        dMs = m.b * dA
        # Ms = K Kᵀ ⊙ Γ, P = Q Kᵀ ⊙ Γ (the masks are in d Ms, d P and Γ)
        GK, GQ = (dMs * m.G).astype(dt), (dP * m.G).astype(dt)
        E = dMs * Ms + dP * m.P                     # Γ ⊙ d Γ
        dKg = m.b * dRk
        kd_dot = jnp.sum(dKd * m.Kd, axis=1, keepdims=True)
        dbeta = (jnp.sum(dRk * m.Kg, axis=1, keepdims=True)
                 + jnp.sum(dRv * m.V.astype(f32), axis=1, keepdims=True)
                 + jnp.sum(dA * Ms, axis=1, keepdims=True))
        dc = (jnp.sum(dKg * m.Kg + dQg * m.Qg, axis=1, keepdims=True) - kd_dot
              + jnp.sum(E, axis=1, keepdims=True))
        dK = (_nn(GK, m.K) + _tn(GK, m.K) + _tn(GQ, m.Q)
              + m.gam * dKg + m.e * dKd)
        dQ = _nn(GQ, m.K) + m.gam * dQg
        lanes = slice(a * dk, (a + 1) * dk)
        dq_ref[:, lanes] = sum(heads(dQ)).astype(dq_ref.dtype)
        dk_ref[:, lanes] = sum(heads(dK)).astype(dk_ref.dtype)
        dV = (m.b * dRv).astype(dv_ref.dtype)
        # what reaches a head's last c besides: γ_C in S1's two terms
        dc_row = _to_row(dc, m.eye) - jnp.sum(E, axis=0, keepdims=True)
        for h, (qg, w, o, d, s0, s1, kd) in enumerate(zip(
                heads(m.Qg.astype(dt)), heads(Wb), heads(dO), heads(dDb),
                states, dS1, heads(kd_dot))):
            at = a * ht + h
            dv_ref[:, at * dv:(at + 1) * dv] = dV[h * C:(h + 1) * C]
            g_end = m.gam_end[h * C:h * C + 1]
            ds_ref[at] = _tn(qg, o) + g_end * s1 - _tn(w, d)
            end = jnp.sum(kd, axis=0, keepdims=True) + g_end * jnp.sum(
                jnp.sum(s1 * s0, axis=1, keepdims=True), axis=0, keepdims=True)
            dc_row = dc_row + jnp.where(lane == h * C + C - 1, end, 0.0)
        drows_ref[a, 0:1, :] = dc_row
        drows_ref[a, 1:2, :] = _to_row(dbeta, m.eye)


def _rows(cum, beta, C: int, ht: int):
    """c and β as the kernels take them: [B, S, Hv] each → [B, Hv/ht, nc, 2,
    ht·C] float32, a tile's heads side by side along the lanes."""
    B, S, Hv = cum.shape
    both = jnp.stack([cum, beta], axis=1).reshape(B, 2, S // C, C, Hv // ht, ht)
    return both.transpose(0, 4, 2, 1, 5, 3).reshape(B, Hv // ht, S // C, 2,
                                                    ht * C)


def _unrows(t, S: int):
    """_rows' way back for one of the two: [B, T, nc, ht·C] → [B, S, Hv]."""
    B, T, nc, N = t.shape
    C = S // nc
    return t.reshape(B, T, nc, N // C, C).transpose(0, 2, 4, 1, 3).reshape(
        B, S, T * (N // C))


@functools.partial(jax.jit, static_argnames=("kernel", "Hk", "C", "interpret",
                                             "with_states"))
def _chunks_call(kernel: str, q, k, v, cum, beta, Hk: int, C: int,
                 interpret: bool, X=None, states=None, do=None,
                 with_states: bool = True):
    """The pallas_call of one kernel over grid (rows, head tiles, chunks).
    q, k [B, S, Hk·dk]; v [B, S, Hv·dv]; cum, beta [B, S, Hv] float32; S whole
    chunks. ``"solve"`` (q unread) → X [B, T, nc, C, ht·C] in v's dtype, a
    tile of ht value heads' blocks side by side; ``"fwd"`` (X given) → (o [B,
    S, Hv·dv] in v's dtype, states | None); ``"bwd"`` (X, ``states``, ``do``
    given) → the five gradients, shaped as the inputs. A jit of its own, as
    mamba2._chunks_call and for its reason; heads and channels cross it
    merged."""
    B, S, Hv = cum.shape
    r = Hv // Hk
    dk, dv = k.shape[2] // Hk, v.shape[2] // Hv
    nc = S // C
    if not interpret and (dk % 128 or dv % 128 or C % 16):
        raise NotImplementedError(
            f"gated_delta_scan on a TPU takes head widths of whole lane tiles "
            f"and chunks of whole sublane tiles (d_k={dk}, d_v={dv}, C={C})")
    ht, kt, estimate = choose_delta_tiling(kernel, B, S, C, Hk, r, dk, dv,
                                           v.dtype.itemsize)
    # T tiles of ht value heads; a key head's `per` tiles; kt key heads (each
    # ONE tile: the rule takes several only where per == 1) a grid step
    T, per, N = Hv // ht, r // ht, ht * C
    chunk_of = (lambda c: nc - 1 - c) if kernel == "bwd" else (lambda c: c)

    def by_chunk(*block):
        """[B, T, chunks, *block] arrays: kt tiles' blocks a grid step."""
        return pl.BlockSpec((None, kt, None) + block, lambda b, i, c: (
            b, i, chunk_of(c)) + (0,) * len(block))

    key_spec = pl.BlockSpec((None, C, kt * dk),
                            lambda b, i, c: (b, chunk_of(c), i // per))
    val_spec = pl.BlockSpec((None, C, kt * ht * dv),
                            lambda b, i, c: (b, chunk_of(c), i))
    state_shape = jax.ShapeDtypeStruct((B, T * ht, nc, dk, dv), jnp.float32)
    state_spec = pl.BlockSpec((None, kt * ht, None, dk, dv),
                              lambda b, i, c: (b, i, chunk_of(c), 0, 0))
    rows = _rows(cum, beta, C, ht)
    rows_spec, x_spec = by_chunk(2, N), by_chunk(C, N)
    args = [q, k, v, rows, X]
    specs = [key_spec, key_spec, val_spec, rows_spec, x_spec]
    if kernel == "solve":
        body, name = _solve_kernel, scopes.GATED_DELTA_SOLVE_KERNEL
        args, specs = [k, rows], [key_spec, rows_spec]
        out_shape = jax.ShapeDtypeStruct((B, T, nc, C, N), v.dtype)
        out_specs = x_spec
    elif kernel == "fwd":
        body = functools.partial(_fwd_kernel, with_states=with_states)
        name = scopes.GATED_DELTA_FWD_KERNEL
        out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype)] \
            + [state_shape] * with_states
        out_specs = [val_spec] + [state_spec] * with_states
    else:
        body, name = _bwd_kernel, scopes.GATED_DELTA_BWD_KERNEL
        args += [states, do]
        specs += [state_spec, val_spec]
        # d q, d k: a key head's tiles each write their own, summed below —
        # float32 partial sums; one tile a key head writes the operands'
        # dtype at once (a cast outside the call is a pass over HBM of its
        # own wherever a kernel reads the gradient: PERF.md §6, PR 63)
        tile_spec = pl.BlockSpec((None, C, kt * dk),
                                 lambda b, i, c: (b, chunk_of(c), i))
        key_grad = jax.ShapeDtypeStruct(
            (B, S, T * dk), jnp.float32 if per > 1 else q.dtype)
        out_shape = [key_grad, key_grad, jax.ShapeDtypeStruct(v.shape, v.dtype),
                     jax.ShapeDtypeStruct((B, T, nc, 2, N), jnp.float32)]
        out_specs = [tile_spec, tile_spec, val_spec, rows_spec]
    out = pl.pallas_call(
        functools.partial(body, kt=kt, ht=ht, C=C), grid=(B, T // kt, nc),
        in_specs=specs, out_specs=out_specs, out_shape=out_shape,
        # the states ride along the chunks; the solve carries nothing
        scratch_shapes=[] if kernel == "solve" else [
            pltpu.VMEM((kt * ht, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"
                                 if kernel == "solve" else "arbitrary"),
            vmem_limit_bytes=None if estimate <= VMEM_BUDGET_BYTES else min(
                VMEM_CEILING_BYTES, estimate + estimate // 2)),
        interpret=interpret, name=name,
    )(*args)
    if kernel == "solve":
        return out
    if kernel == "fwd":
        return out[0], (out[1] if with_states else None)
    dq, dk_, dv_, drows = out

    def summed(t):      # a key head's `per` tiles
        return t.reshape(B, S, Hk, per, dk).sum(3).reshape(q.shape).astype(
            q.dtype)

    return (summed(dq), summed(dk_), dv_, _unrows(drows[:, :, :, 0], S),
            _unrows(drows[:, :, :, 1], S))


def _merged(*ts):
    """[B, S, H, d] values with their last two dims merged."""
    return tuple(t.reshape(t.shape[:2] + (-1,)) for t in ts)


def _solved(q, k, v, cum, beta, C, interpret):
    """X of every chunk, by the name a checkpoint policy keeps it under: the
    recompute of a layer that kept it runs no solve, and the backward reads
    it whether the layer did or not."""
    return checkpoint_name(
        _chunks_call("solve", *_merged(q, k, v), cum, beta, q.shape[2], C,
                     interpret), scopes.RES_DELTA_X)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _delta_chunks(q, k, v, cum, beta, C, interpret):
    X = _solved(q, k, v, cum, beta, C, interpret)
    return _chunks_call("fwd", *_merged(q, k, v), cum, beta, q.shape[2], C,
                        interpret, X=X, with_states=False)[0].reshape(v.shape)


def _delta_chunks_fwd(q, k, v, cum, beta, C, interpret):
    X = _solved(q, k, v, cum, beta, C, interpret)
    o, states = _chunks_call("fwd", *_merged(q, k, v), cum, beta, q.shape[2],
                             C, interpret, X=X)
    # by name, so that a checkpoint policy that keeps it (and o) spares the
    # backward a second forward call
    states = checkpoint_name(states, scopes.RES_DELTA_STATES)
    return o.reshape(v.shape), (q, k, v, cum, beta, states, X)


def _delta_chunks_bwd(C, interpret, res, do):
    q, k, v, cum, beta, states, X = res
    dq, dk, dv, dcum, dbeta = _chunks_call(
        "bwd", *_merged(q, k, v), cum, beta, q.shape[2], C, interpret, X=X,
        states=states, do=do.reshape(do.shape[:2] + (-1,)))
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dcum, dbeta)


_delta_chunks.defvjp(_delta_chunks_fwd, _delta_chunks_bwd)


def _kernel_scan(q, k, v, g, beta, *, chunk: int, interpret: bool):
    S = q.shape[1]
    C = min(chunk, S)
    if C & (C - 1):
        raise ValueError(f"gated_delta_scan: the chunk (or a shorter row) "
                         f"must be a power of two; got {C}")
    q, k, v, g, beta = _padded(q, k, v, g, beta, C)
    return _delta_chunks(q, k, v, _cumulative(g, C), beta, C, interpret)[:, :S]


@jax.named_scope(scopes.GATED_DELTA)
def gated_delta_scan(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array, chunk: int = CHUNK,
                     impl: str = "auto") -> jax.Array:
    """The recurrence of the module docstring. q, k [B, S, H_k, d_k] (as the
    caller normalised and scaled them) and v [B, S, H_v, d_v] in the compute
    dtype, g (≤ 0) and beta [B, S, H_v] float32 → o [B, S, H_v, d_v] in v's
    dtype; value head h reads key head h // (H_v / H_k). A row is one
    document: no state is reset inside it. A row that is not whole chunks is
    padded with steps that change nothing and cut again. Which
    implementation runs is attention.resolve_attention's rule on ``impl``:
    the kernels on a TPU (and interpreted where a caller says "pallas"
    elsewhere), ``gated_delta_chunked`` off it. Under a mesh each device
    scans its own rows."""
    mesh = mesh_lib.current_mesh()
    impl, interpret = resolve_attention(impl, mesh)
    if impl == "ring":
        raise NotImplementedError("the gated delta rule reads a row whole: "
                                  "use a mesh without a cp axis")
    if impl == "pallas":
        fn = functools.partial(_kernel_scan, chunk=chunk, interpret=interpret)
    else:
        fn = functools.partial(gated_delta_chunked, chunk=chunk)
    if mesh is None:
        return fn(q, k, v, g, beta)
    batch_axes, _ = batch_head_axes(mesh, q.shape[0], q.shape[2])
    tok, col = PSpec(batch_axes, None, None, None), PSpec(batch_axes, None, None)
    return jax.shard_map(fn, mesh=mesh, out_specs=tok, check_vma=False,
                         in_specs=(tok, tok, tok, col, col))(q, k, v, g, beta)
