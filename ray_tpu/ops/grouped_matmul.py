"""The held experts' grouped products, as kernels of the program's own.

``ops/moe._pass_rows`` multiplies a buffer of rows, sorted by held expert, by
each expert's own matrix: ``x[rows, K] · W[e][K, N]`` for the rows of group e
(``group_sizes[e]`` of them, one group after another; rows past the last
group belong to no expert). ``lax.ragged_dot`` is that product, and on a TPU
the compiler's own grouped kernel, which takes no tiling from its caller: at
the DeepSeek cell's N = 1,408 = 11 × 128 it ran at a quarter of the matrix
unit's peak in all three of its forms (PERF.md §6, PR 60). So the three forms
are Pallas kernels here, tiled from the shapes the call is given:

``gmm``    ``x[rows, K] · W[e][K, N] → [rows, N]``, the forward product;
``gmm_t``  ``d[rows, N] · W[e][K, N]ᵀ → [rows, K]``, the gradient to the
           input — W is read as it lies (row-major) and contracted over its
           minor dimension: no transposed copy of the weights is made;
``tgmm``   ``x[rows, K]ᵀ · d[rows, N] → [held, K, N]`` by group, the
           gradient to the weights.

A grid step is one VISIT: a row tile's rows that belong to one group (a tile
that straddles two groups is visited once for each, a group that lies in
three tiles three times; an empty group once, so that its weight gradient is
written — as zeros). Every visit takes its operands WHOLE in the other two
dimensions — the expert's matrix stays in VMEM while the visits stay in its
group, each row tile is read once a visit, and a width that no wide tile
divides (1,408; 2,688 = 21 × 128) is simply the block's size. Accumulation
is float32 on the matrix unit, the output is rounded once to the operands'
dtype (what ``preferred_element_type=x.dtype`` gave), and each call states
the VMEM it needs. The row products leave rows that belong to no group as
they find them (as the compiler's kernel does: ``_pass_rows`` masks them).

``grouped_tiling`` is THE rule for which implementation a form takes at a
shape and how tall its row tile is; it reads only what the call is given and
can be asked what it chose (``grouped_tiling_decisions``, the
``ops/grouped_tiling`` events). ``grouped_dot`` is the seam ``ops/moe`` calls.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import (
    VMEM_BUDGET_BYTES, VMEM_CEILING_BYTES, record_decision, resolve_attention,
    vmem_block_bytes)
from ray_tpu.parallel import mesh as mesh_lib
from ray_tpu.tracing import names as scopes

_LANES = 128
FORMS = ("gmm", "gmm_t", "tgmm")
# which implementation a form takes: the kernels below, or the compiler's own
# (`lax.ragged_dot` and what AD makes of it)
PALLAS, COMPILER = "pallas", "compiler"
# the row tiles the rule may give a visit, tallest first. A tile that
# straddles two groups is multiplied once for each, so a short tile wastes
# less: 256 rows measured best or within 2 % of it in every form at every
# expert cell's shapes on the v5e — groups of 640 to 3,800 rows —, 512 was 3
# to 12 % slower and 1,024 half the rate (PERF.md §6, PR 60 has the table)
_ROW_TILES = (256, 128)

_decisions: Dict[tuple, Dict[str, Any]] = {}


class GroupedTiling(NamedTuple):
    impl: str                 # PALLAS or COMPILER
    row_tile: int             # rows a visit takes (0: the compiler's choice)
    vmem_estimate: int        # bytes, _vmem_estimate() of this choice (or 0)


def grouped_tiling_decisions() -> List[Dict[str, Any]]:
    """Every distinct decision this process has traced a grouped product
    with, as the ``ops/grouped_tiling`` events carry them."""
    return list(_decisions.values())


def _vmem_estimate(form: str, tm: int, K: int, N: int, a: int) -> int:
    """VMEM bytes one visit needs: every in/out block twice (Pallas
    double-buffers them), the float32 product once and — for ``tgmm`` — the
    float32 sum it is added to (counted twice: the add may be made beside
    it). An upper bound, not Mosaic's own figure."""
    blk = vmem_block_bytes
    weights = blk((K, N), a)
    if form == "tgmm":
        return (2 * (blk((tm, K), a) + blk((tm, N), a) + weights)
                + 2 * blk((K, N), 4))
    wide, out = (K, N) if form == "gmm" else (N, K)
    return (2 * (blk((tm, wide), a) + weights + blk((tm, out), a))
            + 2 * blk((tm, out), 4))


def grouped_tiling(form: str, rows: int, held: int, K: int, N: int,
                   dtype_bytes: int, devices: int = 1) -> GroupedTiling:
    """THE rule for how one grouped product runs, from its shapes alone:
    ``rows`` of the buffer over ``held`` groups, each expert's matrix [K, N]
    (in every form: ``gmm_t`` contracts over N, ``tgmm`` writes [K, N]), the
    bytes of an operand's element (0: the operands' dtypes differ) and the
    ``devices`` the step is laid out on.

    The program's kernels wherever they can take the call — a product and
    its two gradients together, all three on one row tile: K and N whole
    lane tiles (the blocks are the operands' whole widths, so no other
    divisor is asked of them), the rows a whole number of row tiles, and a
    visit's estimate within what a kernel may be given (two thirds of
    VMEM_CEILING_BYTES) in every form. The row tile is the tallest of
    _ROW_TILES that divides the rows and fits. Anything else — a toy's
    widths, rows that are no whole tile, operands of two dtypes — is the
    compiler's kernel, as every call was before PR 60; and so is every call
    on more devices than one: the compiler partitions its own kernel, and a
    Pallas call has no rule for that. (``held`` decides nothing yet: it is
    in the record, and is what a rule for groups far shorter than a row tile
    would read.)"""
    if form not in FORMS:
        raise ValueError(f"unknown grouped product {form!r}")
    tiling = GroupedTiling(COMPILER, 0, 0)
    if (devices == 1 and K % _LANES == 0 and N % _LANES == 0
            and dtype_bytes in (2, 4)):
        fit = [t for t in _ROW_TILES if rows % t == 0 and all(
            _vmem_estimate(f, t, K, N, dtype_bytes)
            <= VMEM_CEILING_BYTES * 2 // 3 for f in FORMS)]
        if fit:
            tiling = GroupedTiling(PALLAS, fit[0], _vmem_estimate(
                form, fit[0], K, N, dtype_bytes))
    record_decision(_decisions, scopes.GROUPED_TILING, dict(zip(
        scopes.GROUPED_TILING_ARGS,
        (form, rows, held, K, N, dtype_bytes, devices) + tuple(tiling))))
    return tiling


# ------------------------------------------------------------------- visits
@functools.partial(jax.jit, static_argnums=(1, 2))
@jax.named_scope(scopes.MOE_ROUTED)
def _visits(group_sizes: jax.Array, rows: int, tm: int):
    """The grid's visits for ``group_sizes`` [held] over ``rows`` in tiles of
    ``tm``: (offsets [held + 1]: where each group starts, then the total;
    groups, tiles [V]: visit v is the rows of ``tiles[v]`` that belong to
    ``groups[v]``; count [1]: how many of the V are visits — the rest repeat
    the last one, so a step past the count moves no block and does nothing).
    Visits are in group order and, within a group, tile order: a block of
    either is only ever revisited at once. Every (group, tile) that share a
    row is a visit, and every empty group has one (of no rows, at the tile
    where it would start). V = tiles + held bounds their number. (A jitted
    function of its own: a step calls it a dozen times a pass with the same
    shapes, and traces and lowers it once.)"""
    held, tiles = group_sizes.shape[0], rows // tm
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    # a tile whose first row is a pair's: that row's group is the first that
    # ends past it
    first_row = jnp.arange(tiles, dtype=jnp.int32) * tm
    tile_group = jnp.sum(ends[None, :] <= first_row[:, None], axis=1,
                         dtype=jnp.int32)
    # a group whose first row is inside a tile, or that has no row
    inside = (sizes == 0) | (starts % tm != 0)
    stride = tiles + 1
    none = (held + 1) * stride
    key = jnp.sort(jnp.concatenate([
        jnp.where(first_row < ends[-1],
                  tile_group * stride + jnp.arange(tiles, dtype=jnp.int32),
                  none),
        jnp.where(inside, jnp.arange(held, dtype=jnp.int32) * stride
                  + jnp.minimum(starts // tm, tiles - 1), none)]))
    count = jnp.sum(key < none, dtype=jnp.int32)
    key = jnp.where(key < none, key, key[count - 1])
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return offsets, key // stride, key % stride, count[None]


def _visit(offsets, groups, tiles, count, tm: int):
    """(this step is a visit, its group, the first and one past the last of
    the group's rows, the tile's first row, the tile lies wholly inside the
    group) of grid step ``program_id(0)``."""
    v = pl.program_id(0)
    g = groups[v]
    lo, hi, row0 = offsets[g], offsets[g + 1], tiles[v] * tm
    whole = jnp.logical_and(lo <= row0, row0 + tm <= hi)
    return v < count[0], g, lo, hi, row0, whole


def _of_group(shape, lo, hi, row0):
    """Which elements of a tile [tm, width] lie in the group's rows."""
    row = row0 + lax.broadcasted_iota(jnp.int32, shape, 0)
    return jnp.logical_and(lo <= row, row < hi)


# ------------------------------------------------------------ kernel bodies
def _gmm_kernel(offsets, groups, tiles, count, x_ref, w_ref, o_ref, *,
                tm: int, transposed: bool):
    """One visit of a row product: x [tm, wide] · W_g ([wide, out], or [out,
    wide] read ``transposed``) → the group's rows of o [tm, out]."""
    visit, _, lo, hi, row0, whole = _visit(offsets, groups, tiles, count, tm)
    dims = (((1,), (1 if transposed else 0,)), ((), ()))

    def product():
        return lax.dot_general(x_ref[...], w_ref[...], dims,
                               preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(visit, whole))
    def _():
        o_ref[...] = product().astype(o_ref.dtype)

    @pl.when(jnp.logical_and(visit, jnp.logical_and(~whole, lo < hi)))
    def _():
        # the tile's other rows are another visit's: the one before this has
        # written its own, the one after will write its own over these (the
        # select in float32: a v5e's vector unit has no bfloat16)
        o_ref[...] = jnp.where(
            _of_group(o_ref.shape, lo, hi, row0), product(),
            o_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _tgmm_kernel(offsets, groups, tiles, count, x_ref, d_ref, o_ref, acc, *,
                 tm: int, steps: int):
    """One visit of the weights' gradient: the group's rows of x [tm, K] and
    d [tm, N] → xᵀ · d added to the float32 sum [K, N], which the group's
    last visit rounds into o [K, N]."""
    visit, g, lo, hi, row0, whole = _visit(offsets, groups, tiles, count, tm)
    v = pl.program_id(0)
    first = jnp.logical_or(v == 0, groups[jnp.maximum(v - 1, 0)] != g)
    last = jnp.logical_or(v == count[0] - 1,
                          groups[jnp.minimum(v + 1, steps - 1)] != g)
    dims = (((0,), (0,)), ((), ()))

    @pl.when(jnp.logical_and(visit, first))
    def _():
        acc[...] = jnp.zeros_like(acc)

    # (the sum is the product's own accumulator: no product is written out
    # to be added)
    @pl.when(jnp.logical_and(visit, whole))
    def _():
        acc[...] += lax.dot_general(x_ref[...], d_ref[...], dims,
                                    preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(visit, jnp.logical_and(~whole, lo < hi)))
    def _():
        # (one operand's rows masked: the other's meet zeros)
        d = jnp.where(_of_group(d_ref.shape, lo, hi, row0),
                      d_ref[...].astype(jnp.float32), 0.0)
        acc[...] += lax.dot_general(x_ref[...], d.astype(d_ref.dtype), dims,
                                    preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(visit, last))
    def _():
        # (an empty group's one visit is its first and its last: zeros)
        o_ref[...] = acc[...].astype(o_ref.dtype)


# -------------------------------------------------------------------- calls
def _compiler_params(tiling: GroupedTiling):
    """The visits run in order (a block is revisited at once, the sum is
    carried); past Mosaic's default scoped VMEM the call states its own
    estimate and half again, never more than VMEM_CEILING_BYTES."""
    limit = None
    if tiling.vmem_estimate > VMEM_BUDGET_BYTES:
        limit = min(VMEM_CEILING_BYTES,
                    tiling.vmem_estimate + tiling.vmem_estimate // 2)
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=limit)


@functools.lru_cache(maxsize=None)
def _pallas_call(form: str, a_shape, b_shape, dtype, held: int,
                 tiling: GroupedTiling, interpret: bool):
    """One form's kernel as a call of (*visits, a, b): (a, b) = (x, W) for
    ``gmm``, (d, W) for ``gmm_t``, (x, d) for ``tgmm``. One object a distinct
    call, so that a step's many calls of it trace its body once."""
    rows, tm = a_shape[0], tiling.row_tile
    steps = rows // tm + held

    def by_tile(width):
        return pl.BlockSpec((tm, width), lambda v, off, g, t, n: (t[v], 0))

    def by_group(shape):
        return pl.BlockSpec((None,) + shape,
                            lambda v, off, g, t, n: (g[v], 0, 0))

    if form == "tgmm":
        K, N = a_shape[1], b_shape[1]
        kernel = functools.partial(_tgmm_kernel, tm=tm, steps=steps)
        in_specs, out_specs = [by_tile(K), by_tile(N)], by_group((K, N))
        out_shape = jax.ShapeDtypeStruct((held, K, N), dtype)
        scratch = [pltpu.VMEM((K, N), jnp.float32)]
    else:
        out = b_shape[2] if form == "gmm" else b_shape[1]
        kernel = functools.partial(_gmm_kernel, tm=tm,
                                   transposed=form == "gmm_t")
        in_specs = [by_tile(a_shape[1]), by_group(b_shape[1:])]
        out_specs = by_tile(out)
        out_shape = jax.ShapeDtypeStruct((rows, out), dtype)
        scratch = []
    return pl.pallas_call(
        kernel, out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(steps,), in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        compiler_params=_compiler_params(tiling), interpret=interpret,
        name=f"grouped_{form}")


def _pallas_product(form: str, a: jax.Array, b: jax.Array,
                    group_sizes: jax.Array, tiling: GroupedTiling,
                    interpret: bool) -> jax.Array:
    """One form through its kernel (_pallas_call's operands)."""
    call = _pallas_call(form, a.shape, b.shape, a.dtype,
                        group_sizes.shape[0], tiling, interpret)
    visits = _visits(group_sizes, a.shape[0], tiling.row_tile)
    # a trace's reader finds the grouped products by this name, whoever
    # makes them (tracing/names.RAGGED_DOT_KERNEL)
    with jax.named_scope(scopes.RAGGED_DOT_KERNEL):
        return call(*visits, a, b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped_dot(x, w, group_sizes, tilings, interpret):
    return _pallas_product("gmm", x, w, group_sizes, tilings[0], interpret)


def _grouped_dot_fwd(x, w, group_sizes, tilings, interpret):
    return (_grouped_dot(x, w, group_sizes, tilings, interpret),
            (x, w, group_sizes))


def _grouped_dot_bwd(tilings, interpret, res, d):
    x, w, group_sizes = res
    return (_pallas_product("gmm_t", d, w, group_sizes, tilings[1], interpret),
            _pallas_product("tgmm", x, d, group_sizes, tilings[2], interpret),
            None)


_grouped_dot.defvjp(_grouped_dot_fwd, _grouped_dot_bwd)


def grouped_dot(x: jax.Array, w: jax.Array,
                group_sizes: jax.Array) -> jax.Array:
    """x [rows, K] · w [held, K, N] by group (``group_sizes`` [held] int32:
    that many of x's rows, one group after another) → [rows, N] in x's
    dtype, accumulated in float32; a row past the last group is whatever the
    kernel left there. Each of the product and its two gradients runs as
    ``grouped_tiling`` says for its shape, its dtype and the mesh the step
    traces under (parallel/mesh.current_mesh)."""
    mesh = mesh_lib.current_mesh()
    (rows, K), (held, _, N) = x.shape, w.shape
    tilings = tuple(grouped_tiling(
        form, rows, held, K, N,
        x.dtype.itemsize if w.dtype == x.dtype else 0,
        1 if mesh is None else mesh.size) for form in FORMS)
    if tilings[0].impl == COMPILER:         # the three are decided together
        return lax.ragged_dot(x, w, group_sizes,
                              preferred_element_type=x.dtype)
    _, interpret = resolve_attention(mesh=mesh)
    return _grouped_dot(x, w, group_sizes, tilings, interpret)
