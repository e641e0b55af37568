"""A share's row movement as Pallas kernels of the repo's own, beside the
grouped matmuls.

    take_rows(x (T, D), index (N,), live ()) -> (N, D)
    add_rows(y (N, D), index (N,), live (), T, weight (N,) or None) -> (T, D) f32

`take_rows`: row i is `x[index[i]]` for i < `live`; a row at or past `live`
is left as found (`grouped_matmul`'s own contract: `_gmm` never visits it,
`_tgmm` masks it). `add_rows`: out[t] is the sum over i < `live` with
index[i] == t of weight[i] * y[i], in float32, repeated indices allowed (a
token's top_k choices). Each is the other's transpose, under one
`custom_vjp` each: `take_rows`' is `add_rows` of the cotangent, `add_rows`'
is the cotangent's rows taken and weighed, and the weight's a row-wise dot
(`_take` with `y` and `weight`: one kernel makes both).

Two kernels over **a column strip that stays in VMEM while the live row
tiles pass**: the grid is (strips of D, row tiles of N), the tiles innermost
and their extent `ceil(live / tm)`, traced, as `grouped_matmul._visits`'
count is: **work ends at the last live row tile**, and a chunk a quarter
full moves a quarter of its rows. XLA's gather and scatter-add move the
whole chunk whatever came (`ops/moe._chunk_part`, PERF.md, PR 74).

- `_take`: the strip is x's, (T, w), fetched once a strip by its
  `BlockSpec` and, where x is bfloat16, widened to float32 in a scratch of
  the strip's shape (a bfloat16 row is half a sublane word: 32-bit rows can
  be addressed one at a time). A tile's rows are read out of it one after
  another by the scalar-prefetched index, eight to a turn of the loop, into a
  float32 tile, which is written whole (cast, or with `weight` times the
  weight, beside its row-wise dot with `y`).
- `_add`: the strip is the output's, (T, w) float32, zeroed at a strip's
  first tile and written back when the strip changes. A tile of y is widened
  and weighed whole, its rows at or past `live` made zeros, and its rows
  are added where they belong one after another: repeated indices meet in
  order, and no row is added twice.

The path is chosen **from the shape alone** (`tiling`): the kernels where N
is a multiple of the row tile, D of 128 and T of 16; the plain `jax.numpy`
forms otherwise. On the kernels' path `lax.platform_dependent` takes Mosaic
where the program is lowered for the TPU and the plain forms anywhere else
(not the interpreted kernel: the models' tests run XLA's gather and
scatter-add on the CPU as they did, and `tests/test_row_moves.py` runs the
kernels interpreted by `_take` and `_add`'s own argument). The builders and
the platform's choice are jitted, as `grouped_matmul`'s are.

`rows_visited` is the pure count (numpy or jax) that
`models/transformer.routing_stats` reads for `kungfu_moe_rows_moved_share`.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kungfu_tpu.ops.grouped_matmul import VMEM_LIMIT, _f32, _widest
from kungfu_tpu.ops.kernel_call import kernel_call

GROUP = 8  # rows a turn of the rows' loop: a float32 sublane tile
# what a grid step's blocks may take of `VMEM_LIMIT` by `tiling`'s account:
# the strip twice (the pipeline's two buffers), its float32 copy, the tiles
STRIP_ROOM = 80 << 20


class Tiles(NamedTuple):
    """Rows a tile (`tm`) and the columns a strip (`w`, of D)."""
    tm: int
    w: int


def tiling(N: int, D: int, T: int) -> Optional[Tiles]:
    """The kernels' tiles for N rows of width D moved out of or into T rows,
    or None where the shape takes the plain forms: N not a multiple of 128,
    D not of 128 or T not of 16. The row tile is the largest of 512, 256 and
    128 that divides N; the strip is as wide as `STRIP_ROOM` allows with the
    strip counted at 8 bytes an element (a float32 strip in the pipeline's
    two buffers, or a bfloat16 one twice and its float32 copy) and the
    tiles at 16."""
    if N % 128 or D % 128 or T % 16:
        return None
    tm = next(t for t in (512, 256, 128) if N % t == 0)
    w = _widest(D, lambda w: 8 * T * w + 16 * tm * w <= STRIP_ROOM)
    return Tiles(tm, w)


def rows_visited(live, tm: int):
    """Rows of the tiles of `tm` that the kernels visit for `live` rows:
    `live` rounded up to a tile, one tile where nothing came (`add_rows`
    writes its zeros there). numpy's or jax's."""
    xp = jnp if isinstance(live, jax.Array) else np
    return xp.maximum(-(-live // tm), 1) * tm


def _tile_rows(live_ref, tm: int):
    """The live rows of this grid step's tile, 0 .. tm, and its first row."""
    base = lax.mul(pl.program_id(1), tm)
    return lax.clamp(0, lax.sub(live_ref[0], base), tm), base


def _each_block(T: int, run):
    """`run(rows)` for each block of a strip's T rows, in a loop: a strip is
    thousands of vector registers, and a kernel's text is its compile time."""
    block = math.gcd(T, 256)

    def one(b, carry):
        run(pl.ds(pl.multiple_of(lax.mul(b, block), block), block))
        return carry

    lax.fori_loop(0, T // block, one, None)


def _each_live_row(index_ref, base, n, T: int, run):
    """`run(i, t)` for the tile's rows i < n, t = index[base + i] held to
    0 .. T - 1, `GROUP` rows a turn of the loop; the last turn runs past n
    to the group's end."""
    def group(g, carry):
        first = pl.multiple_of(lax.mul(g, GROUP), GROUP)
        for s in range(GROUP):
            i = lax.add(first, s)
            run(i, lax.clamp(0, index_ref[lax.add(base, i)], T - 1))
        return carry

    lax.fori_loop(0, lax.div(lax.add(n, GROUP - 1), GROUP), group, None)


def _weighed(rows, w_ref):
    """A tile's rows (tm, w) float32, each times its weight of `w_ref`
    (tm, 1)."""
    return lax.mul(rows, lax.broadcast_in_dim(w_ref[...], rows.shape, (0, 1)))


def _take_kernel(index_ref, live_ref, x_ref, *refs, tm: int, weighted: bool,
                 widen: bool):
    if weighted:
        y_ref, w_ref, out_ref, dot_ref, *scratch = refs
    else:
        out_ref, *scratch = refs
    rows_ref = scratch[-1]
    src_ref = scratch[0] if widen else x_ref
    T = x_ref.shape[0]
    n, base = _tile_rows(live_ref, tm)

    if widen:
        @pl.when(lax.eq(pl.program_id(1), 0))
        def _():
            def widened(rows):
                src_ref[rows, :] = _f32(x_ref[rows, :])

            _each_block(T, widened)

    def take(i, t):
        rows_ref[pl.ds(i, 1), :] = src_ref[pl.ds(t, 1), :]

    _each_live_row(index_ref, base, n, T, take)
    rows = rows_ref[...]
    if weighted:
        out_ref[...] = lax.convert_element_type(_weighed(rows, w_ref),
                                                out_ref.dtype)
        # the rows' dots with y's along the lanes, (8, tm) of eight equal
        # rows, by the MXU (ones times the products, transposed): a column
        # (tm, 1) would be a sum across lanes a row and, in HBM, 128 lanes
        # wide for one
        dot_ref[...] = lax.dot_general(
            lax.full((GROUP, rows.shape[1]), 1, jnp.float32),
            lax.mul(rows, _f32(y_ref[...])), (((1,), (1,)), ((), ())),
            precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    else:
        out_ref[...] = lax.convert_element_type(rows, out_ref.dtype)


def _add_kernel(index_ref, live_ref, y_ref, w_ref, out_ref, tile_ref, *,
                tm: int):
    T = out_ref.shape[0]
    n, base = _tile_rows(live_ref, tm)

    @pl.when(lax.eq(pl.program_id(1), 0))
    def _():
        def zeroed(rows):
            out_ref[rows, :] = lax.full((rows.size, out_ref.shape[1]), 0,
                                        jnp.float32)

        _each_block(T, zeroed)

    y = _f32(y_ref[...])
    row = lax.broadcasted_iota(jnp.int32, y.shape, 0)
    tile_ref[...] = lax.select(
        lax.lt(row, lax.full_like(row, n)), _weighed(y, w_ref),
        lax.full_like(y, 0))

    def add(i, t):
        out_ref[pl.ds(t, 1), :] = lax.add(out_ref[pl.ds(t, 1), :],
                                          tile_ref[pl.ds(i, 1), :])

    _each_live_row(index_ref, base, n, T, add)


def _scalars(index, live, tm: int):
    """The kernels' two scalar-prefetch arrays and the grid's extent over row
    tiles: the tiles `live` rows reach, one where nothing came."""
    live = lax.convert_element_type(live, jnp.int32)
    count = lax.max(lax.div(live + (tm - 1), tm), 1)
    return (lax.convert_element_type(index, jnp.int32),
            lax.reshape(live, (1,))), count


def _params(N: int, D: int, T: int, itemsize: int):
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=N * D, transcendentals=0,
            bytes_accessed=itemsize * D * (N + T)))


@functools.partial(jax.jit, static_argnames=("tm", "w", "interpret"))
def _take(x, index, live, y=None, weight=None, *, tm: int, w: int,
          interpret: bool = False):
    """x[index] for the rows before `live` -> (N, D) of x's type; with `y`
    (N, D) and `weight` (N,) float32 the rows times their weights, of y's
    type, and the rows' dots with y's (N,) float32: `add_rows`'
    transposes."""
    T, D = x.shape
    N = index.shape[0]
    weighted = y is not None
    widen = x.dtype != jnp.float32
    scalars, count = _scalars(index, live, tm)
    tile = pl.BlockSpec((tm, w), lambda s, j, *_: (j, s))
    operands, in_specs = [x], [pl.BlockSpec((T, w), lambda s, j, *_: (0, s))]
    out_shape, out_specs = jax.ShapeDtypeStruct((N, D), x.dtype), tile
    if weighted:
        operands += [y, lax.reshape(weight, (N, 1))]
        in_specs += [tile, pl.BlockSpec((tm, 1), lambda s, j, *_: (j, 0))]
        out_shape = (jax.ShapeDtypeStruct((N, D), y.dtype),
                     jax.ShapeDtypeStruct((D // w, GROUP, N), jnp.float32))
        out_specs = (tile, pl.BlockSpec((None, GROUP, tm),
                                        lambda s, j, *_: (s, 0, j)))
    out = kernel_call(
        functools.partial(_take_kernel, tm=tm, weighted=weighted, widen=widen),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(D // w, count),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=([pltpu.VMEM((T, w), jnp.float32)] if widen else [])
            + [pltpu.VMEM((tm, w), jnp.float32)]),
        out_shape=out_shape, interpret=interpret,
        name="take_rows_weighted" if weighted else "take_rows",
        **_params(N, D, T, x.dtype.itemsize),
    )(*scalars, *operands)
    if not weighted:
        return out
    rows, dots = out
    return rows, lax.reduce_sum(dots[:, 0], (0,))  # the strips' parts


@functools.partial(jax.jit, static_argnames=("T", "tm", "w", "interpret"))
def _add(y, index, live, weight, *, T: int, tm: int, w: int,
         interpret: bool = False):
    """The rows of y (N, D) before `live`, each times its weight (N,)
    float32, added into row index[i] of (T, D) float32 zeros."""
    N, D = y.shape
    scalars, count = _scalars(index, live, tm)
    return kernel_call(
        functools.partial(_add_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(D // w, count),
            in_specs=[pl.BlockSpec((tm, w), lambda s, j, *_: (j, s)),
                      pl.BlockSpec((tm, 1), lambda s, j, *_: (j, 0))],
            out_specs=pl.BlockSpec((T, w), lambda s, j, *_: (0, s)),
            scratch_shapes=[pltpu.VMEM((tm, w), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((T, D), jnp.float32),
        interpret=interpret, name="add_rows",
        **_params(N, D, T, y.dtype.itemsize),
    )(*scalars, y, lax.reshape(weight, (N, 1)))


def _before(live, N: int):
    """(N, 1) mask of the rows before `live`."""
    return (jnp.arange(N) < live)[:, None]


def plain_take_rows(x, index, live):
    """`take_rows` in `jax.numpy`: a row at or past `live` is zeros."""
    return jnp.where(_before(live, index.shape[0]), x[index], 0)


def plain_add_rows(y, index, live, T: int, weight=None):
    """`add_rows` in `jax.numpy`: XLA's scatter-add of the whole chunk."""
    y = y.astype(jnp.float32)
    if weight is not None:
        y = y * weight[:, None]
    y = jnp.where(_before(live, index.shape[0]), y, 0)
    return jnp.zeros((T, y.shape[1]), jnp.float32).at[index].add(y)


def _plain_taken_back(g, index, live, y, weight):
    rows = plain_take_rows(g, index, live)
    return ((rows * weight[:, None]).astype(y.dtype),
            jnp.sum(rows * y.astype(jnp.float32), axis=1))


def _kernel_taken_back(tiles: Tiles, g, index, live, y, weight):
    rows, dots = _take(g, index, live, y, weight, tm=tiles.tm, w=tiles.w)
    return rows, jnp.where(_before(live, index.shape[0])[:, 0], dots, 0)


# The platform's choice is jitted, as `grouped_matmul._forward` has it: a
# call site of a shape that was traced before traces neither branch again.
@functools.partial(jax.jit, static_argnames=("tiles",))
def _taken(x, index, live, *, tiles: Tiles):
    return lax.platform_dependent(
        x, index, live,
        tpu=functools.partial(_take, tm=tiles.tm, w=tiles.w),
        default=plain_take_rows)


@functools.partial(jax.jit, static_argnames=("T", "tiles"))
def _added(y, index, live, weight, *, T: int, tiles: Tiles):
    return lax.platform_dependent(
        y, index, live, weight,
        tpu=functools.partial(_add, T=T, tm=tiles.tm, w=tiles.w),
        default=lambda y, index, live, weight: plain_add_rows(
            y, index, live, T, weight))


@functools.partial(jax.jit, static_argnames=("tiles",))
def _taken_back(g, index, live, y, weight, *, tiles: Tiles):
    return lax.platform_dependent(
        g, index, live, y, weight,
        tpu=functools.partial(_kernel_taken_back, tiles),
        default=_plain_taken_back)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _take_rows(tiles: Tiles, x, index, live):
    return _taken(x, index, live, tiles=tiles)


def _take_rows_fwd(tiles, x, index, live):
    # of x its rows' count and its type alone: no column carries both
    return _taken(x, index, live, tiles=tiles), (x[:, :0], index, live)


def _take_rows_bwd(tiles, res, g):
    like, index, live = res
    dx = _added(g, index, live, jnp.ones(index.shape, jnp.float32),
                T=like.shape[0], tiles=tiles)
    return dx.astype(like.dtype), None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _add_rows(tiles: Tiles, T: int, y, index, live, weight):
    return _added(y, index, live, weight, T=T, tiles=tiles)


def _add_rows_fwd(tiles, T, y, index, live, weight):
    return (_added(y, index, live, weight, T=T, tiles=tiles),
            (y, index, live, weight))


def _add_rows_bwd(tiles, T, res, g):
    y, index, live, weight = res
    dy, dweight = _taken_back(g, index, live, y, weight, tiles=tiles)
    return dy, None, None, dweight


_add_rows.defvjp(_add_rows_fwd, _add_rows_bwd)


def take_rows(x, index, live):
    """Row i of the result is x[index[i]] for i < `live` (a traced count); a
    row at or past `live` is left as found, whatever that is: zeros in the
    plain form, which stands where the shape does not tile (`tiling`), x is
    neither bfloat16 nor float32 or the program is not the TPU's. x (T, D),
    index (N,) int32 of rows of x -> (N, D) of x's type."""
    tiles = tiling(index.shape[0], x.shape[1], x.shape[0])
    if tiles is None or x.dtype not in (jnp.bfloat16, jnp.float32):
        return plain_take_rows(x, index, live)
    return _take_rows(tiles, x, index, live)


def add_rows(y, index, live, T: int, weight=None):
    """(T, D) float32: row t is the sum over i < `live` (a traced count)
    with index[i] == t of weight[i] * y[i] (of y[i] with no `weight`), the
    products and the sums in float32; indices may repeat. y (N, D), index
    (N,) int32 below T, weight (N,) float32. XLA's scatter-add where the
    shape does not tile (`tiling`), y is neither bfloat16 nor float32 or the
    program is not the TPU's."""
    tiles = tiling(*y.shape, T)
    if tiles is None or y.dtype not in (jnp.bfloat16, jnp.float32):
        return plain_add_rows(y, index, live, T, weight)
    if weight is None:
        weight = jnp.ones(index.shape, jnp.float32)
    return _add_rows(tiles, T, y, index, live, weight)
