"""Flash attention for TPU as Pallas kernels (fwd + bwd, causal, custom VJP).

This is the perf-critical op the XLA fallback can't match: XLA materializes the
[S, S] probability matrix as a backward residual per layer, forcing full remat
at GPT-2 batch sizes. The kernels below keep the online-softmax
running state (m, l, acc) in VMEM and never write probabilities to HBM; the
backward pass recomputes logits blockwise from (q, k, lse) the flash-attention
way.

Design notes (TPU-first):
- TWO kernel pairs, chosen by the head's widths alone (kernel_layout): where
  they fill whole 128-lane tiles the operands are hd-minor, [B·H, S, hd];
  where one does not (GPT-2's 64; latent attention's 192 beside 128) they
  are S-minor, [B·H, hd, S] — dense at any width and the way XLA stores such
  a head anyway, so the projections' outputs and the layer scan's saved
  stacks go in and the gradients come out with no transposing copy. The
  pairs share the tile rule, the VMEM arithmetic, the transposed logits tile
  and the f32 statistics; their tiles' axes differ, so each has its own
  BlockSpecs and loop body.
- q and k have one width, ``hd``, and v and o another, ``hd_v`` (equal
  anywhere but in latent attention: 192 and 128): every operand is read and
  written at its own width — no v padded to q's, no q·k padded to whole
  tiles in HBM — and the softmax scale is the caller's where it gives one
  (PR 55).
- The public API takes [B, S, H, hd] and transposes at the boundary (XLA
  fuses the transpose into the surrounding projection matmuls) or, with a
  head-major ``layout``, takes a pair's own order as it is. Inside, batch
  and head are merged (a free reshape) into one dim of independent rows:
  every block's minor dims are the tile Mosaic requires, and the grid walks
  the rows one at a time whatever the head count.
- The q/kv tile is choose_tiling's decision, from the shapes and an estimate
  of the VMEM the blocks need; callers pass no tile.
- K/V live whole per row in VMEM (S·hd·2B ≈ 128 KiB at S=1024), so the kv
  loop is VMEM-resident with no DMA choreography. Where the whole rows pass
  Mosaic's default scoped-VMEM limit (the backward from 8,192 tokens at
  hd 128 or 192 / 128) the call asks for its own estimate and half again,
  up to VMEM_CEILING_BYTES of the core's 128 MiB (choose_tiling,
  _compiler_params; PR 55): what bounds the sequence length is that ceiling
  — a 32,768-token backward at those widths is refused. The index maps put
  the row first and the tile's position last: making kv (forward) or q
  (backward) a third grid axis with scratch accumulators is a change of
  those maps, not of the layout (ROADMAP D16: the route not taken, for rows
  past the ceiling).
- The logits tile is computed transposed, s^T = k·q^T: softmax statistics are
  lane-dense [1, block_q] rows and their reductions run down the sublanes.
  Logits/softmax accumulate in f32 (MXU native via preferred_element_type);
  p·v and the backward matmuls run bf16→f32.
- The causal mask is computed from GLOBAL positions `q_offset`/`kv_offset`
  (scalar-prefetch args), so the same kernel serves single-device attention
  (offsets 0) and ring attention (per-step rotated offsets, ops/ring_attention;
  the ring's chunk functions keep the hd-minor pair at every width).
- Backward = ONE fused kernel (grid over kv blocks, loop q): dk/dv written
  per kv block, dq accumulated in a VMEM-resident whole-row f32 scratch —
  s/p/dp computed once per block pair instead of twice (the split dq + dkv
  formulation costs 7 matmuls and double the exp/mask work; fused is 5).
- lse/delta ride as [B·H, 1, S] so their (1, block) tiles satisfy the minor-
  dim rules and the kernels read them as the rows they are; lse is [B, H, S]
  at the API edge (the rows' two dims, in the caller's order, and S). The
  S-minor backward makes delta itself, from o's row.

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

# Which way a kernel pair's operand tiles lie (kernel_layout's two answers,
# the `layout` of an ops/flash_tiling event).
HD_MINOR = "hd_minor"     # [rows, S, hd]: dense where hd fills the 128 lanes
S_MINOR = "s_minor"       # [rows, hd, S]: dense at any hd


def kernel_layout(hd: int, hd_v: Optional[int] = None) -> str:
    """THE rule for which kernel pair a head takes, from its two widths
    alone: ``hd`` of q and k, ``hd_v`` of v and o (``hd`` where the caller
    gives none). A width that is not whole lane tiles (GPT-2's 64) stored
    hd-minor fills part of every tile, in HBM and in VMEM, and XLA does not
    store it so: it writes the projections' outputs and the layer scan's
    saved stacks S-minor, and a kernel that reads hd-minor costs a
    transposing copy a tensor each way (PERF.md §6, PR 48). Such a head takes
    the S-minor pair; widths of whole tiles (% 128 == 0) are dense hd-minor,
    which is what XLA picks there, and keep the hd-minor pair. One pair
    serves all four operands, so ONE width that is not whole tiles decides:
    latent attention's q and k at 192 would stand hd-minor as 256 lanes, a
    third of them padding in HBM and in VMEM, beside a v that fills its 128 —
    S-minor both are dense as they are (192 and 128 rows of sublanes), so
    192 / 128 takes the S-minor pair."""
    widths = (hd, hd if hd_v is None else hd_v)
    return HD_MINOR if all(w % 128 == 0 for w in widths) else S_MINOR


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
                  Sq: int, Skv: int, hd: int, dtype_bytes: int,
                  layout: str = HD_MINOR, hd_v: Optional[int] = None) -> int:
    """VMEM bytes one grid step needs, as the rule counts them: every in/out
    block twice (Pallas double-buffers them), the backward's f32 dq
    accumulator once, and one [block_k, block_q] f32 logits tile plus the
    loop's f32 accumulators. An upper bound, not Mosaic's own figure.
    ``layout`` says which way a block lies: S-minor blocks are [width, tile]
    (no lane is padded at any width), hd-minor blocks [tile, width]. The
    width is ``hd`` for q, k and their gradients and ``hd_v`` (``hd`` where
    none is given) for v, o and theirs."""
    blk = vmem_block_bytes
    hd_v = hd if hd_v is None else hd_v

    def op(rows, width, itemsize=dtype_bytes):  # an operand block of `rows`
        return blk((width, rows) if layout == S_MINOR else (rows, width),
                   itemsize)

    tile = blk((block_k, block_q), 4)
    if kernel == "fwd":
        io = (op(block_q, hd) + op(block_q, hd_v)         # q, o
              + op(Skv, hd) + op(Skv, hd_v)               # k, v: whole rows
              + blk((1, block_q), 4))                     # lse
        live = tile + blk((hd_v, block_q), 4)             # s^T; acc^T
    else:
        # q, dq and do: whole rows, and lse, delta; the S-minor kernel makes
        # delta itself, from o's row
        o_rows, stats = (2, 1) if layout == S_MINOR else (1, 2)
        io = (2 * op(Sq, hd) + o_rows * op(Sq, hd_v)
              + 2 * op(block_k, hd) + 2 * op(block_k, hd_v)   # k, dk; v, dv
              + stats * blk((1, Sq), 4))
        live = (blk((hd, Sq), 4)                          # dq^T accumulator
                + tile + op(block_k, hd, 4) + op(block_k, hd_v, 4))  # s^T; dk, dv
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
            tiling: Tiling, layout: str, hd_v: int,
            window: Optional[int] = None) -> None:
    """The tiling a flash kernel is traced with, with the shapes it was
    given (``hd`` of q and k, ``hd_v`` of v and o), the pair it belongs to
    and, under a causal window (0: none), how many (q, kv) tile pairs the
    call visits beside how many the causal walk alone would
    (``ops/flash_tiling``; visited_tiles)."""
    record_decision(_decisions, names.FLASH_TILING, dict(zip(
        names.FLASH_TILING_ARGS,
        (kernel, rows, Sq, Skv, hd) + tuple(tiling) + (layout, hd_v)
        + (window or 0,) + visited_tiles(kernel, Sq, Skv, tiling.block_q,
                                         tiling.block_k, window))))


def _compiler_params(tiling: Tiling):
    """What a flash call tells Mosaic beside its grid: nothing where the
    tiling's estimate is inside the default scoped-VMEM limit — the call is
    then what it always was —, else that estimate and half again as the
    call's own limit (choose_tiling lets none through that would pass
    VMEM_CEILING_BYTES), as the EVA, scan and sparse kernels ask."""
    if tiling.vmem_estimate <= VMEM_BUDGET_BYTES:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=min(
        VMEM_CEILING_BYTES, tiling.vmem_estimate + tiling.vmem_estimate // 2))


def choose_tiling(
    kernel: str, Sq: int, Skv: int, hd: int, dtype_bytes: int, *,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
    layout: str = HD_MINOR, hd_v: Optional[int] = None,
) -> Tiling:
    """THE rule for how a flash kernel tiles its work. ``kernel`` is ``"fwd"``
    or ``"bwd"``. The keywords are a caller's explicit choices: each is kept
    (clamped to a divisor of its sequence) and the rule fills in the other.
    Raises ``ValueError`` when no tiling of its own fits the ceiling.
    ``layout`` is the pair's (kernel_layout): the tile is along S either way,
    the sublanes of an hd-minor block and the lanes of an S-minor one, and
    only the estimate differs. ``hd`` is q's and k's width, ``hd_v`` v's and
    o's (``hd`` where none is given).

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
    - When a tiling does not fit VMEM_BUDGET_BYTES (Mosaic's default limit)
      the rule halves the kv tile, then the q tile, in turn, down to 128.
      What does not shrink that way are the whole-row blocks (k/v in the
      forward; q, do, dq — S-minor o too — and the f32 dq accumulator in the
      backward).
    - Where the whole rows alone pass that budget (PR 55: the backward of an
      8,192-token row at 192 / 128 is 26 MiB of them) the tiles go back to
      their target and the CALL asks Mosaic for its estimate and half again
      (_compiler_params), as the EVA, scan and sparse kernels do — a v5e
      core has 128 MiB of VMEM, and the default limit is a default. What
      bounds the sequence length is then VMEM_CEILING_BYTES: an estimate
      whose half again passes it is refused (the backward of a 32,768-token
      row at those widths, 107 MiB, or at hd 128, 68 MiB). A shape that fits
      the default budget gets the tiling it always got and no limit of its
      own.
    """
    if kernel not in ("fwd", "bwd"):
        raise ValueError(f"unknown flash kernel {kernel!r}")
    # a caller who fixed both gets them: Mosaic is the judge
    explicit = block_q is not None and block_k is not None

    def walk(limit: int) -> Tuple[Optional[Tiling], Tiling]:
        """(the first tiling of the halving walk estimated within ``limit``,
        the last one tried)."""
        q_ = _pick_block(Sq, block_q or _TARGET_TILE)
        k_ = _pick_block(Skv, block_k or _TARGET_TILE)
        while True:
            t = Tiling(q_, k_, vmem_estimate(kernel, q_, k_, Sq, Skv, hd,
                                             dtype_bytes, layout, hd_v))
            if explicit or t.vmem_estimate <= limit:
                return t, t
            can_k = block_k is None and k_ > _MIN_TILE
            can_q = block_q is None and q_ > _MIN_TILE
            if can_k and (k_ >= q_ or not can_q):
                k_ = _pick_block(Skv, k_ // 2)
            elif can_q:
                q_ = _pick_block(Sq, q_ // 2)
            else:
                return None, t

    # (the call's own limit is its estimate and half again: the largest
    # estimate whose half again is still under the ceiling)
    for limit in (VMEM_BUDGET_BYTES, VMEM_CEILING_BYTES * 2 // 3):
        fits, t = walk(limit)
        if fits is not None:
            return fits
    raise ValueError(
        f"flash attention {kernel}: no tiling fits the VMEM ceiling of "
        f"{VMEM_CEILING_BYTES} bytes for Sq={Sq} Skv={Skv} hd={hd} "
        f"hd_v={hd if hd_v is None else hd_v} "
        f"({dtype_bytes}-byte operands, {layout}): the smallest tried, block_q="
        f"{t.block_q} block_k={t.block_k}, is estimated at "
        f"{t.vmem_estimate} bytes, and a call asks for half again"
    )


# --------------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------------- #

def _kv_block_range(q_global, kv_off_ref, block_q: int, block_k: int,
                    nk: int, causal: bool):
    """(kv blocks a q tile starting at ``q_global`` attends to, how many of
    them need no mask) of ``nk``: under the causal mask only blocks whose
    global start can be <= the last query row count, and those whose last
    column is <= the FIRST query row need no mask — only the diagonal-
    straddling tail pays the iota/select work."""
    if not causal:
        return nk, nk
    last_q = q_global + block_q - 1
    num_blocks = jnp.clip((last_q - kv_off_ref[0]) // block_k + 1, 0, nk)
    num_full = jnp.clip((q_global - kv_off_ref[0] + 1) // block_k, 0, nk)
    return num_blocks, num_full


def _q_block_range(kv_global, q_off_ref, block_q: int, block_k: int,
                   nq: int, causal: bool):
    """(first q tile that sees the kv block starting at ``kv_global``, first
    that sees all of it) of ``nq``: the backward's walk, masked between the
    two and unmasked from the second on."""
    if not causal:
        return 0, 0
    first = jnp.clip((kv_global - q_off_ref[0]) // block_q, 0, nq)
    first_full = jnp.clip(
        -((q_off_ref[0] - kv_global - block_k + 1) // block_q), 0, nq
    )
    return first, first_full


def _kv_band(q_global, kv_off_ref, block_q: int, block_k: int,
             causal_range, window: int):
    """_kv_block_range under a causal WINDOW (query i sees keys j <= i with
    i - j < ``window``): the four edges ``(start, lo, hi, end)`` of a q
    tile's walk over the kv blocks, from the causal walk's ``causal_range``
    (_kv_block_range's pair). It starts at the first block that
    holds a key inside the window of the tile's FIRST row and ends where the
    causal walk ends; blocks ``[lo, hi)`` lie wholly inside the band — their
    first key inside the window of the tile's LAST row, their last key at or
    before its first — and take no mask, ``[start, lo)`` straddle the
    window's edge and ``[hi, end)`` the diagonal and take one (a tile whose
    window is narrower than its blocks has lo == hi: one masked walk)."""
    off = kv_off_ref[0]
    last_q = q_global + block_q - 1
    end, causal_full = causal_range
    start = jnp.clip((q_global - window + 1 - off) // block_k, 0, end)
    lo = jnp.clip(-((off - (last_q - window + 1)) // block_k), start, end)
    hi = jnp.clip(causal_full, lo, end)
    return start, lo, hi, end


def _q_band(kv_global, q_off_ref, block_q: int, block_k: int, nq: int,
            causal_range, window: int):
    """_q_block_range under a causal window: the four edges ``(first, lo,
    hi, end)`` of a kv block's walk over the ``nq`` q tiles, from the causal
    walk's ``causal_range`` (_q_block_range's pair). It starts where
    the causal walk starts and ends after the last tile that holds a row
    whose window still reaches the block's LAST key; tiles ``[lo, hi)`` see
    the whole block — their first row at or past its last key, their last
    row's window reaching its first — and take no mask, ``[first, lo)``
    straddle the diagonal and ``[hi, end)`` the window's edge."""
    off = q_off_ref[0]
    first, causal_full = causal_range
    last_k = kv_global + block_k - 1
    end = jnp.clip((last_k + window - 1 - off) // block_q + 1, first, nq)
    lo = jnp.clip(causal_full, first, end)
    hi = jnp.clip((kv_global + window - off) // block_q, lo, end)
    return first, lo, hi, end


def _kv_row_of(groups: int, batch: int, heads_lead: bool):
    """The index map's row of k and v for a q row ``g`` of the grid where
    ``groups`` query heads read one key-value head (1: g itself — the map a
    call without grouped heads always had). Rows are batch · heads merged:
    with the batch leading a kv head's row is g // groups; with the heads
    leading ([H, B, ..]) it is (g // (B · groups)) · B + g % B."""
    if groups == 1:
        return lambda g: g
    if heads_lead:
        return lambda g: (g // (batch * groups)) * batch + g % batch
    return lambda g: g // groups


def _group_sum(d, q_shape, groups: int, heads_lead: bool):
    """dk or dv as the backward kernel writes it, one a QUERY head ([rows of
    q, ...]), summed over each key-value head's ``groups`` query heads →
    [leading two dims of k, ...]."""
    a, b = q_shape[:2]
    if groups == 1:
        return d.reshape((a, b) + d.shape[1:])
    if heads_lead:      # [H, B, ..] → [KH, G, B, ..]
        return d.reshape((a // groups, groups, b) + d.shape[1:]).sum(
            axis=1, dtype=jnp.float32).astype(d.dtype)
    return d.reshape((a, b // groups, groups) + d.shape[1:]).sum(
        axis=2, dtype=jnp.float32).astype(d.dtype)


def _in_window(keep, q_pos, k_pos, window: Optional[int]):
    """A masked tile's ``keep`` (the causal half, from the tile's global
    positions) with the window's other edge: one ``where`` takes both."""
    if window is None:
        return keep
    return keep & (q_pos - k_pos < window)


def _band_loops(edges, make_body, carry):
    """The three walks of a windowed call over ``edges`` = (start, lo, hi,
    end): masked, unmasked, masked."""
    start, lo, hi, end = edges
    carry = lax.fori_loop(start, lo, make_body(True), carry)
    carry = lax.fori_loop(lo, hi, make_body(False), carry)
    return lax.fori_loop(hi, end, make_body(True), carry)


def visited_tiles(kernel: str, Sq: int, Skv: int, block_q: int, block_k: int,
                  window: Optional[int]) -> Tuple[int, int]:
    """(tiles a causal call of ``kernel`` visits at offsets 0 under
    ``window``, tiles the causal walk alone would): _kv_band / _q_band's
    walks counted in plain integers — what an ``ops/flash_tiling`` event
    says of how much of the triangle a window skipped."""
    nq, nk = Sq // block_q, Skv // block_k
    visited = causal = 0
    if kernel == "fwd":
        for i in range(nq):
            end = min(max((i * block_q + block_q - 1) // block_k + 1, 0), nk)
            start = 0 if window is None else min(
                max((i * block_q - window + 1) // block_k, 0), end)
            visited, causal = visited + end - start, causal + end
    else:
        for j in range(nk):
            first = min(max(j * block_k // block_q, 0), nq)
            end = nq if window is None else min(max(
                (j * block_k + block_k + window - 2) // block_q + 1, first), nq)
            visited, causal = visited + end - first, causal + nq - first
    return visited, causal


def _fwd_kernel(
    q_off_ref, kv_off_ref,            # scalar prefetch: global offsets [1]
    q_ref, k_ref, v_ref,              # [bq, hd], [Skv, hd], [Skv, hd_v]
    o_ref, lse_ref,                   # [bq, hd_v], [1, bq]
    *, scale: float, causal: bool, block_q: int, block_k: int, kv_len: int,
    window: Optional[int] = None,
):
    qi = pl.program_id(1)
    q_global = q_off_ref[0] + qi * block_q

    num_blocks, num_full = _kv_block_range(
        q_global, kv_off_ref, block_q, block_k, kv_len // block_k, causal)

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
    hd_v = v_ref.shape[-1]

    def make_body(masked):
        def body(ki, carry):
            m, l, acc = carry               # [1, bq], [1, bq], [hd_v, bq]
            kv = pl.ds(ki * block_k, block_k)
            s = lax.dot_general(
                k_ref[kv, :], qs, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                               # [bk, bq]
            if masked:
                q_pos = q_global + lax.broadcasted_iota(
                    jnp.int32, (block_k, block_q), 1)
                k_pos = kv_off_ref[0] + ki * block_k + lax.broadcasted_iota(
                    jnp.int32, (block_k, block_q), 0)
                keep = _in_window(q_pos >= k_pos, q_pos, k_pos, window)
                s = jnp.where(keep, s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = l * alpha + jnp.sum(p, axis=0, keepdims=True)
            v = v_ref[kv, :]
            acc = acc * alpha + lax.dot_general(
                v, p.astype(v.dtype), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                               # v^T·p^T = (p·v)^T, [hd_v, bq]
            return m_new, l, acc
        return body

    carry = (jnp.full((1, block_q), _NEG_INF, jnp.float32),
             jnp.zeros((1, block_q), jnp.float32),
             jnp.zeros((hd_v, block_q), jnp.float32))
    if window is None:
        carry = lax.fori_loop(0, num_full, make_body(False), carry)
        m, l, acc = lax.fori_loop(num_full, num_blocks, make_body(causal),
                                  carry)
    else:
        m, l, acc = _band_loops(
            _kv_band(q_global, kv_off_ref, block_q, block_k,
                     (num_blocks, num_full), window), make_body, carry)
    # rows with no valid kv (ring attention future chunks): l == 0 →
    # output 0, lse = -inf-ish so the ring merge gives them zero weight.
    l_safe = jnp.where(l > 0, l, 1.0)
    o_ref[...] = (acc / l_safe).T.astype(o_ref.dtype)
    lse_ref[...] = jnp.where(l > 0, m + jnp.log(l_safe), _NEG_INF)


def _mha_forward_bhsd(
    q, k, v, q_offset, kv_offset, *,
    causal: bool, scale: float, interpret: bool,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
    window: Optional[int] = None, heads_lead: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """q,k: [B, H, S, hd], v: [B, H, S, hd_v] → (o [B,H,S,hd_v], lse
    [B,H,S]). Batch and head are merged (a free reshape) into the one dim of
    independent rows the grid walks. Tiles the caller leaves None are
    choose_tiling's."""
    B, H, Sq, hd = q.shape
    Skv, hd_v = k.shape[2], v.shape[3]
    R, KR = B * H, k.shape[0] * k.shape[1]
    kv_of = _kv_row_of(R // KR, H if heads_lead else B, heads_lead)
    t = choose_tiling("fwd", Sq, Skv, hd, q.dtype.itemsize,
                      block_q=block_q, block_k=block_k, hd_v=hd_v)
    _record("fwd", R, Sq, Skv, hd, t, HD_MINOR, hd_v, window)
    bq, bk = t.block_q, t.block_k

    def kv_row(width):
        return pl.BlockSpec((None, Skv, width),
                            lambda g, i, *_: (kv_of(g), 0, 0))

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=bq, block_k=bk, kv_len=Skv, window=window,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R, Sq // bq),
            in_specs=[
                pl.BlockSpec((None, bq, hd), lambda g, i, *_: (g, i, 0)),
                kv_row(hd), kv_row(hd_v),
            ],
            out_specs=[
                pl.BlockSpec((None, bq, hd_v), lambda g, i, *_: (g, i, 0)),
                pl.BlockSpec((None, 1, bq), lambda g, i, *_: (g, 0, i)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((R, Sq, hd_v), q.dtype),
            jax.ShapeDtypeStruct((R, 1, Sq), jnp.float32),
        ],
        compiler_params=_compiler_params(t),
        interpret=interpret,
        name=names.FLASH_FWD_KERNEL,
    )(q_offset, kv_offset, q.reshape(R, Sq, hd), k.reshape(KR, Skv, hd),
      v.reshape(KR, Skv, hd_v))
    return o.reshape(B, H, Sq, hd_v), lse.reshape(B, H, Sq)


def _fwd_kernel_s_minor(
    q_off_ref, kv_off_ref,            # scalar prefetch: global offsets [1]
    q_ref, k_ref, v_ref,              # [hd, bq], [hd, Skv], [hd_v, Skv]
    o_ref, lse_ref,                   # [hd_v, bq], [1, bq]
    *, scale: float, causal: bool, block_q: int, block_k: int, kv_len: int,
    window: Optional[int] = None,
):
    """_fwd_kernel on S-minor tiles: every operand tile is [width, tile],
    dense at any width (the sequence fills the lanes) — hd for q and k, hd_v
    for v and o, equal or not. The logits tile and the
    statistics are the same s^T [block_k, block_q] and [1, block_q] rows; the
    accumulator is o^T [hd_v, block_q] and is stored as it is, and
    v · p^T is a plain product. What the layout costs is k's tile transposed
    for s^T = k^T · q (a [hd, block_k] tile a pair)."""
    qi = pl.program_id(1)
    q_global = q_off_ref[0] + qi * block_q

    num_blocks, num_full = _kv_block_range(
        q_global, kv_off_ref, block_q, block_k, kv_len // block_k, causal)

    qs = q_ref[...] * jnp.asarray(scale, q_ref.dtype)        # [hd, bq]
    hd_v = v_ref.shape[0]

    def make_body(masked):
        def body(ki, carry):
            m, l, acc = carry               # [1, bq], [1, bq], [hd_v, bq]
            kv = pl.ds(pl.multiple_of(ki * block_k, block_k), block_k)
            s = lax.dot_general(
                k_ref[:, kv], qs, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                               # k^T·q, [bk, bq]
            if masked:
                q_pos = q_global + lax.broadcasted_iota(
                    jnp.int32, (block_k, block_q), 1)
                k_pos = kv_off_ref[0] + ki * block_k + lax.broadcasted_iota(
                    jnp.int32, (block_k, block_q), 0)
                keep = _in_window(q_pos >= k_pos, q_pos, k_pos, window)
                s = jnp.where(keep, s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = l * alpha + jnp.sum(p, axis=0, keepdims=True)
            v = v_ref[:, kv]
            acc = acc * alpha + jnp.dot(
                v, p.astype(v.dtype), preferred_element_type=jnp.float32,
            )                               # v·p^T = (p·v)^T, [hd_v, bq]
            return m_new, l, acc
        return body

    carry = (jnp.full((1, block_q), _NEG_INF, jnp.float32),
             jnp.zeros((1, block_q), jnp.float32),
             jnp.zeros((hd_v, block_q), jnp.float32))
    if window is None:
        carry = lax.fori_loop(0, num_full, make_body(False), carry)
        m, l, acc = lax.fori_loop(num_full, num_blocks, make_body(causal),
                                  carry)
    else:
        m, l, acc = _band_loops(
            _kv_band(q_global, kv_off_ref, block_q, block_k,
                     (num_blocks, num_full), window), make_body, carry)
    l_safe = jnp.where(l > 0, l, 1.0)
    o_ref[...] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[...] = jnp.where(l > 0, m + jnp.log(l_safe), _NEG_INF)


def _mha_forward_s_minor(
    q, k, v, q_offset, kv_offset, *,
    causal: bool, scale: float, interpret: bool,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
    window: Optional[int] = None, heads_lead: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """q,k: [B, H, hd, S], v: [B, H, hd_v, S] → (o [B,H,hd_v,S], lse
    [B,H,S]): _mha_forward_bhsd with the sequence minor ([H, B, ..] as well:
    the two leading dims are the rows). Rows, grid and tile rule are the
    same; a block is [width, tile] and its index moves along the last dim."""
    B, H, hd, Sq = q.shape
    Skv, hd_v = k.shape[3], v.shape[2]
    R, KR = B * H, k.shape[0] * k.shape[1]
    kv_of = _kv_row_of(R // KR, H if heads_lead else B, heads_lead)
    t = choose_tiling("fwd", Sq, Skv, hd, q.dtype.itemsize, block_q=block_q,
                      block_k=block_k, layout=S_MINOR, hd_v=hd_v)
    _record("fwd", R, Sq, Skv, hd, t, S_MINOR, hd_v, window)
    bq, bk = t.block_q, t.block_k

    def q_tile(width):
        return pl.BlockSpec((None, width, bq), lambda g, i, *_: (g, 0, i))

    def kv_row(width):
        return pl.BlockSpec((None, width, Skv),
                            lambda g, i, *_: (kv_of(g), 0, 0))

    kernel = functools.partial(
        _fwd_kernel_s_minor, scale=scale, causal=causal,
        block_q=bq, block_k=bk, kv_len=Skv, window=window,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R, Sq // bq),
            in_specs=[q_tile(hd), kv_row(hd), kv_row(hd_v)],
            out_specs=[
                q_tile(hd_v),
                pl.BlockSpec((None, 1, bq), lambda g, i, *_: (g, 0, i)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((R, hd_v, Sq), q.dtype),
            jax.ShapeDtypeStruct((R, 1, Sq), jnp.float32),
        ],
        compiler_params=_compiler_params(t),
        interpret=interpret,
        name=names.FLASH_FWD_KERNEL,
    )(q_offset, kv_offset, q.reshape(R, hd, Sq), k.reshape(KR, hd, Skv),
      v.reshape(KR, hd_v, Skv))
    return o.reshape(B, H, hd_v, Sq), lse.reshape(B, H, Sq)


# --------------------------------------------------------------------------- #
# Backward
# --------------------------------------------------------------------------- #

def _fused_bwd_kernel(
    q_off_ref, kv_off_ref,
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dk_ref, dv_ref, dq_acc,
    *, scale: float, causal: bool, block_q: int, block_k: int, q_len: int,
    window: Optional[int] = None,
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
    first, first_full = _q_block_range(
        kv_global, q_off_ref, block_q, block_k, nq, causal)

    scale_c = jnp.asarray(scale, q_ref.dtype)

    # the logits tile is held transposed (see _fwd_kernel): lse and delta are
    # read as the [1, block_q] rows they are stored as, p^T and ds^T feed dv
    # and dk as plain matmuls with no transpose, and dq accumulates
    # transposed, [hd, Sq] — lane-dense, half the VMEM of a lane-padded
    # [Sq, hd] block.
    k = k_ref[...]
    v = v_ref[...]
    hd, hd_v = k.shape[-1], v.shape[-1]
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
                q_pos = q_off_ref[0] + qi * block_q + lax.broadcasted_iota(
                    jnp.int32, (block_k, block_q), 1)
                k_pos = kv_global + lax.broadcasted_iota(
                    jnp.int32, (block_k, block_q), 0)
                keep = _in_window(q_pos >= k_pos, q_pos, k_pos, window)
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
             jnp.zeros((block_k, hd_v), jnp.float32))
    if window is None:
        carry = lax.fori_loop(first, first_full, make_body(causal), carry)
        dk, dv = lax.fori_loop(first_full, nq, make_body(False), carry)
    else:
        dk, dv = _band_loops(
            _q_band(kv_global, q_off_ref, block_q, block_k, nq,
                    (first, first_full), window), make_body, carry)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _write_dq():
        dq_ref[...] = dq_acc[...].T.astype(dq_ref.dtype)


def _mha_backward_bhsd(
    q, k, v, o, lse, do, q_offset, kv_offset, *,
    causal: bool, scale: float, interpret: bool,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
    window: Optional[int] = None, heads_lead: bool = False,
):
    """q, k [B, H, S, hd]; v, o, do [B, H, S, hd_v]; lse [B, H, S]. Returns
    dq, dk, dv. Rows and tiles as in _mha_forward_bhsd, chosen for this
    kernel separately."""
    B, H, Sq, hd = q.shape
    Skv, hd_v = k.shape[2], v.shape[3]
    R, KR = B * H, k.shape[0] * k.shape[1]
    groups = R // KR
    kv_of = _kv_row_of(groups, H if heads_lead else B, heads_lead)
    t = choose_tiling("bwd", Sq, Skv, hd, q.dtype.itemsize,
                      block_q=block_q, block_k=block_k, hd_v=hd_v)
    _record("bwd", R, Sq, Skv, hd, t, HD_MINOR, hd_v, window)
    bq, bk = t.block_q, t.block_k

    # delta_i = rowsum(dO_i * O_i): cheap elementwise+reduce, XLA fuses it.
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    ).reshape(R, 1, Sq)

    def row(width):
        return pl.BlockSpec((None, Sq, width), lambda g, i, *_: (g, 0, 0))

    def kv_block(width, of=lambda g: g):
        return pl.BlockSpec((None, bk, width),
                            lambda g, i, *_: (of(g), i, 0))

    stat = pl.BlockSpec((None, 1, Sq), lambda g, i, *_: (g, 0, 0))

    fused_kernel = functools.partial(
        _fused_bwd_kernel, scale=scale, causal=causal,
        block_q=bq, block_k=bk, q_len=Sq, window=window,
    )
    dq, dk, dv = pl.pallas_call(
        fused_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R, Skv // bk),
            in_specs=[row(hd), kv_block(hd, kv_of), kv_block(hd_v, kv_of),
                      row(hd_v), stat, stat],
            out_specs=[row(hd), kv_block(hd), kv_block(hd_v)],
            scratch_shapes=[pltpu.VMEM((hd, Sq), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((R, Sq, hd), q.dtype),
            jax.ShapeDtypeStruct((R, Skv, hd), k.dtype),
            jax.ShapeDtypeStruct((R, Skv, hd_v), v.dtype),
        ],
        compiler_params=_compiler_params(t),
        interpret=interpret,
        name=names.FLASH_BWD_KERNEL,
    )(q_offset, kv_offset, q.reshape(R, Sq, hd), k.reshape(KR, Skv, hd),
      v.reshape(KR, Skv, hd_v), do.reshape(R, Sq, hd_v), lse.reshape(R, 1, Sq),
      delta)
    # dk and dv leave the kernel one a QUERY head; a key-value head's is the
    # sum over its group (all of them where none is grouped)
    return (dq.reshape(q.shape),
            _group_sum(dk, q.shape, groups, heads_lead),
            _group_sum(dv, q.shape, groups, heads_lead))


def _fused_bwd_kernel_s_minor(
    q_off_ref, kv_off_ref,
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,   # [hd | hd_v, Sq | bk]
    dq_ref, dk_ref, dv_ref, dq_acc,
    *, scale: float, causal: bool, block_q: int, block_k: int, q_len: int,
    window: Optional[int] = None,
):
    """_fused_bwd_kernel on S-minor tiles. s^T = k^T·q and dp^T = v^T·do
    contract hd, dimension 0 of both tiles (Mosaic transposes the [hd, bk]
    one); dv^T = do·p and dk^T = q·ds contract the q tile of both operands
    (the form of q·k^T), dq^T = k·ds^T is a plain product into the [hd, Sq]
    accumulator, which is written as it is.
    delta = rowsum(do · o) is made here, a q tile at a time, from the o row
    (a sum down hd's sublanes): made by XLA beside the out-projection's
    backward, it drew o and do into that product's layout, batch-major, and
    each cost a transposing copy on its way here."""
    ki = pl.program_id(1)
    kv_global = kv_off_ref[0] + ki * block_k

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    nq = q_len // block_q
    first, first_full = _q_block_range(
        kv_global, q_off_ref, block_q, block_k, nq, causal)

    scale_c = jnp.asarray(scale, q_ref.dtype)
    k = k_ref[...]                                       # [hd, bk]
    v = v_ref[...]                                       # [hd_v, bk]
    hd, hd_v = k.shape[0], v.shape[0]
    k_scaled = k * scale_c

    def make_body(masked):
        def body(qi, carry):
            dk, dv = carry                       # [hd, bk], [hd_v, bk] f32
            sl = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
            qs = q_ref[:, sl] * scale_c                  # [hd, bq]
            do = do_ref[:, sl]
            lse = lse_ref[:, sl]                         # [1, bq]
            delta = jnp.sum(
                do.astype(jnp.float32) * o_ref[:, sl].astype(jnp.float32),
                axis=0, keepdims=True)
            s = lax.dot_general(
                k, qs, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                            # k^T·q, [bk, bq]
            if masked:
                q_pos = q_off_ref[0] + qi * block_q + lax.broadcasted_iota(
                    jnp.int32, (block_k, block_q), 1)
                k_pos = kv_global + lax.broadcasted_iota(
                    jnp.int32, (block_k, block_q), 0)
                keep = _in_window(q_pos >= k_pos, q_pos, k_pos, window)
                s = jnp.where(keep, s, _NEG_INF)
            p = jnp.exp(s - lse)                         # [bk, bq]
            dv = dv + lax.dot_general(
                do, p.astype(do.dtype), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                            # do·p, [hd_v, bk]
            dp = lax.dot_general(
                v, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                            # v^T·do, [bk, bq]
            ds = (p * (dp - delta)).astype(qs.dtype)     # [bk, bq]
            dk = dk + lax.dot_general(
                qs, ds, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                            # q·ds, [hd, bk]
            dq_acc[:, sl] += jnp.dot(
                k_scaled, ds, preferred_element_type=jnp.float32)
            return dk, dv
        return body

    carry = (jnp.zeros((hd, block_k), jnp.float32),
             jnp.zeros((hd_v, block_k), jnp.float32))
    if window is None:
        carry = lax.fori_loop(first, first_full, make_body(causal), carry)
        dk, dv = lax.fori_loop(first_full, nq, make_body(False), carry)
    else:
        dk, dv = _band_loops(
            _q_band(kv_global, q_off_ref, block_q, block_k, nq,
                    (first, first_full), window), make_body, carry)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _write_dq():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _mha_backward_s_minor(
    q, k, v, o, lse, do, q_offset, kv_offset, *,
    causal: bool, scale: float, interpret: bool,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
    window: Optional[int] = None, heads_lead: bool = False,
):
    """q, k [B, H, hd, S]; v, o, do [B, H, hd_v, S]; lse [B, H, S]. Returns
    dq, dk, dv: _mha_backward_bhsd with the sequence minor."""
    B, H, hd, Sq = q.shape
    Skv, hd_v = k.shape[3], v.shape[2]
    R, KR = B * H, k.shape[0] * k.shape[1]
    groups = R // KR
    kv_of = _kv_row_of(groups, H if heads_lead else B, heads_lead)
    t = choose_tiling("bwd", Sq, Skv, hd, q.dtype.itemsize, block_q=block_q,
                      block_k=block_k, layout=S_MINOR, hd_v=hd_v)
    _record("bwd", R, Sq, Skv, hd, t, S_MINOR, hd_v, window)
    bq, bk = t.block_q, t.block_k

    def row(width):
        return pl.BlockSpec((None, width, Sq), lambda g, i, *_: (g, 0, 0))

    def kv_block(width, of=lambda g: g):
        return pl.BlockSpec((None, width, bk),
                            lambda g, i, *_: (of(g), 0, i))

    stat = pl.BlockSpec((None, 1, Sq), lambda g, i, *_: (g, 0, 0))

    fused_kernel = functools.partial(
        _fused_bwd_kernel_s_minor, scale=scale, causal=causal,
        block_q=bq, block_k=bk, q_len=Sq, window=window,
    )
    dq, dk, dv = pl.pallas_call(
        fused_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R, Skv // bk),
            in_specs=[row(hd), kv_block(hd, kv_of), kv_block(hd_v, kv_of),
                      row(hd_v), row(hd_v), stat],
            out_specs=[row(hd), kv_block(hd), kv_block(hd_v)],
            scratch_shapes=[pltpu.VMEM((hd, Sq), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((R, hd, Sq), q.dtype),
            jax.ShapeDtypeStruct((R, hd, Skv), k.dtype),
            jax.ShapeDtypeStruct((R, hd_v, Skv), v.dtype),
        ],
        compiler_params=_compiler_params(t),
        interpret=interpret,
        name=names.FLASH_BWD_KERNEL,
    )(q_offset, kv_offset, q.reshape(R, hd, Sq), k.reshape(KR, hd, Skv),
      v.reshape(KR, hd_v, Skv), o.reshape(R, hd_v, Sq),
      do.reshape(R, hd_v, Sq), lse.reshape(R, 1, Sq))
    return (dq.reshape(q.shape),
            _group_sum(dk, q.shape, groups, heads_lead),
            _group_sum(dv, q.shape, groups, heads_lead))


# --------------------------------------------------------------------------- #
# Public API ([B, S, H, hd] boundary layout)
# --------------------------------------------------------------------------- #

def _to_bhsd(x):
    return jnp.swapaxes(x, 1, 2)


def _relayout(x, src: str, dst: str):
    """x from axis order ``src`` to ``dst`` (strings over b, h, s, d)."""
    if src == dst:
        return x
    return jnp.transpose(x, tuple(src.index(c) for c in dst))


# The axis orders a kernel pair takes as they are. Batch and head are merged
# into rows, so either may lead: the S-minor pair's callers put the heads
# first (parts.head_layout).
_KERNEL_AXES = {HD_MINOR: ("bhsd",), S_MINOR: ("bhds", "hbds")}
HEAD_MAJOR_LAYOUTS = _KERNEL_AXES[HD_MINOR] + _KERNEL_AXES[S_MINOR]
LAYOUTS = ("bshd",) + HEAD_MAJOR_LAYOUTS


def _kernel_axes(layout: str, hd: int, hd_v: int) -> Tuple[str, str]:
    """(pair, the axis order its kernels are handed) for a caller's layout:
    the caller's own where the pair takes it, else the pair's first."""
    pair = kernel_layout(hd, hd_v)
    own = _KERNEL_AXES[pair]
    return pair, layout if layout in own else own[0]


def _zero_off():
    return jnp.zeros((1,), jnp.int32)


def _checked_window(window: Optional[int], causal: bool) -> Optional[int]:
    """A caller's ``window`` as the kernels take it: None, or a whole number
    of keys >= 1 under the causal mask."""
    if window is None:
        return None
    if not causal:
        raise ValueError("a window is the causal mask's other edge: "
                         f"window={window} needs causal=True")
    if int(window) != window or window < 1:
        raise ValueError(f"window must be a whole number >= 1; got {window!r}")
    return int(window)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, causal, scale, block_q, block_k, bwd_block_q,
           bwd_block_k, interpret, layout, window):
    o, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k, bwd_block_q,
                      bwd_block_k, interpret, layout, window)
    return o


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, bwd_block_q,
               bwd_block_k, interpret, layout, window):
    d = layout.index("d")
    pair, axes = _kernel_axes(layout, q.shape[d], v.shape[d])
    qt, kt, vt = (_relayout(x, layout, axes) for x in (q, k, v))
    forward = _mha_forward_s_minor if pair == S_MINOR else _mha_forward_bhsd
    o, lse = forward(
        qt, kt, vt, _zero_off(), _zero_off(),
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret, window=window, heads_lead=axes[0] == "h",
    )
    # the kernel's two residuals by name: a checkpoint policy that keeps both
    # does not run the forward kernel a second time in the backward
    o = checkpoint_name(o, names.RES_FLASH_O)
    lse = checkpoint_name(lse, names.RES_FLASH_LSE)
    return _relayout(o, axes, layout), (qt, kt, vt, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, bwd_block_q, bwd_block_k,
               interpret, layout, window, res, do):
    qt, kt, vt, o, lse = res
    # (do has v's width; q's is what q's size leaves beside the rows and
    # the sequence, which q and do share)
    hd_v = do.shape[layout.index("d")]
    pair, axes = _kernel_axes(layout, qt.size // (do.size // hd_v), hd_v)
    backward = _mha_backward_s_minor if pair == S_MINOR else _mha_backward_bhsd
    grads = backward(
        qt, kt, vt, o, lse, _relayout(do, layout, axes),
        _zero_off(), _zero_off(),
        causal=causal, scale=scale, block_q=bwd_block_q, block_k=bwd_block_k,
        interpret=interpret, window=window, heads_lead=axes[0] == "h",
    )
    return tuple(_relayout(g, axes, layout) for g in grads)


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
    window: Optional[int] = None,
) -> jax.Array:
    """Multi-head flash attention. q,k,v in and o out are in the axis order
    ``layout`` spells (one of LAYOUTS): [B, S, H, hd] ("bshd", the default),
    head-major hd-minor ("bhsd") or head-major S-minor ("bhds", or "hbds"
    with the heads leading: [.., hd, S]). v — and with it o — may be of
    another width than q and k (latent attention's 128 beside 192): each is
    read at its own width, nothing is padded. ``scale`` is 1/√hd of q's
    width unless given.

    Which kernel pair runs is kernel_layout's answer for the two widths, not
    the caller's: "bhsd" is the hd-minor pair's own order and "bhds" / "hbds"
    the S-minor pair's (batch and head are merged into rows, so either may
    lead), and a caller that hands a pair its own order (models/gpt2.py
    does) has no transpose at the boundary; any other is transposed here.

    The q/kv tile, forward and backward separately, is choose_tiling's, from
    the shapes. The block_* keywords are explicit overrides of it: block_q /
    block_k the forward's tiles; a bwd_* left None follows its forward twin.

    k and v may hold FEWER heads than q (grouped-query attention: H / KH
    query heads read one key-value head, head n the key-value head n // (H /
    KH)): the kernels then read each key-value head's rows where they stand
    — the index map of k's and v's blocks divides the row —, nothing is
    repeated in HBM, and dk and dv are summed over each group after the
    backward kernel, which writes them a query head.

    ``window`` (static; causal calls only): query i sees the keys j <= i with
    i - j < window. Both kernels then walk the BAND alone — a q tile's kv
    blocks from where its first row's window starts, a kv block's q tiles up
    to where the last row that still reaches it lies (_kv_band, _q_band) —,
    unmasked inside it and masked on its two edges. A window that hides
    nothing (>= the keys' length) and None are the same program.

    Differentiable (custom VJP, flash backward). On non-TPU backends the
    kernels run in Pallas interpreter mode so tests validate the same code.
    """
    if layout not in LAYOUTS:
        raise ValueError(f"unknown layout {layout!r}")
    if interpret is None:
        _, interpret = resolve_attention()
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[layout.index("d")])
    window = _checked_window(window, causal)
    if window is not None and window >= k.shape[layout.index("s")]:
        window = None
    return _flash(
        q, k, v, causal, scale, block_q, block_k,
        bwd_block_q or block_q, bwd_block_k or block_k,
        interpret, layout, window,
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
def flash_attention_sharded(q, k, v, mesh, *, layout: str = "bhsd",
                            **kwargs) -> jax.Array:
    """flash_attention for callers under jit/GSPMD (the model forward).
    q, k, v: GLOBAL head-major arrays in and out — [B, H, S, hd], or any
    other of flash_attention's head-major ``layout``s; kwargs as
    flash_attention's.

    GSPMD cannot partition a Mosaic custom call: under a jit over more than
    one device the bare pallas_call does not lower at all ("Mosaic kernels
    cannot be automatically partitioned. Please wrap the call in a
    shard_map"). The shard_map hands each device its own shard, the batch
    over dp·fsdp and the heads over tp, wherever ``layout`` has them. The
    sequence stays whole per device (a cp axis belongs to
    ring_attention_sharded), so a ``window`` is refused on a mesh with one."""
    if layout not in HEAD_MAJOR_LAYOUTS:
        raise ValueError(f"unknown head-major layout {layout!r}")
    if (kwargs.get("window") is not None and mesh is not None
            and mesh.shape.get("cp", 1) > 1):
        raise NotImplementedError(
            f"window={kwargs['window']} under cp > 1: a device holds a chunk "
            "of the sequence and the ring (ops/ring_attention.py) carries "
            "whole rows of kv; use a cp=1 mesh for windowed attention")
    if mesh is None:
        return flash_attention(q, k, v, layout=layout, **kwargs)
    if kwargs.get("interpret") is None:
        _, kwargs["interpret"] = resolve_attention(mesh=mesh)
    b, h = layout.index("b"), layout.index("h")
    batch_axes, head_ax = batch_head_axes(mesh, q.shape[b], q.shape[h])
    if (k.shape[h] != q.shape[h] and head_ax is not None
            and mesh.shape.get(head_ax, 1) > 1):
        raise NotImplementedError(
            f"grouped heads ({q.shape[h]} of q on {k.shape[h]} of k and v) "
            "under tp > 1: the kernels' rows are not cut by the group here; "
            "repeat k and v to q's heads, or use a tp=1 mesh")
    spec = [None] * 4
    spec[b], spec[h] = batch_axes, head_ax
    spec = P(*spec)
    fn = jax.shard_map(
        functools.partial(flash_attention, layout=layout, **kwargs),
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
    window: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Forward-only flash attention returning (out [B,S,H,hd], lse [B,H,S])
    with GLOBAL position offsets — the building block for ring attention's
    per-step chunk computation (ops/ring_attention.py merges partials by lse).
    ``window`` as flash_attention's, over the global positions (the ring
    itself passes none: ops/ring_attention.py refuses a window).
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
        interpret=interpret, window=_checked_window(window, causal),
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
    window: Optional[int] = None,
):
    """Backward for one (q-chunk, kv-chunk) pair with global offsets; returns
    (dq, dk, dv) contributions (all [B,S,H,hd]). `lse` is the GLOBAL logsumexp
    over all chunks. Used by ring attention's backward ring pass. ``window``
    as flash_attention_with_lse's."""
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
        interpret=interpret, window=_checked_window(window, causal),
    )
    return _to_bhsd(dq), _to_bhsd(dk), _to_bhsd(dv)
