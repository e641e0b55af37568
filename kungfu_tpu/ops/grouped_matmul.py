"""The experts' grouped matmul as Pallas kernels of the repo's own.

    grouped_matmul(rows (N, K), weights (e, K, M), group_sizes (e,)) -> (N, M)

`lax.ragged_dot`'s contract: the first `group_sizes[0]` rows times
`weights[0]`, the next `group_sizes[1]` times `weights[1]`, and so on;
float32 accumulation, the operands' type out; a row past the last group is
left as found (the callers select such rows away and never multiply them,
`ops/moe._chunk_part`). Its two gradients are the same kind of product: the
rows' is `dy @ weights[g]^T` over the same groups, the weights' the outer
product `rows[g]^T @ dy[g]` a group.

Three kernels over **visits**: a visit is a (row tile, group) pair in which
the tile holds a row of the group, in the order of the rows. A tile that lies
inside one group is visited once; one that straddles g groups g times, one
after another, so what a visit writes stays in VMEM until the tile's last
(`_visits`, a few compare-and-sum fusions of the group sizes: no scatter, no
sort). The grid's extent over visits is their traced count: **work ends at
the last live tile**, and a chunk a quarter full costs a quarter.

- `_gmm` (forward, and transposed the rows' gradient): a grid step holds
  `tm` rows at their whole width K and a weight tile (K, tn). The weight
  tile's block index is the visit's group, so it **stays put while the rows
  of one group pass** and is fetched once a group; with tn = M (whatever the
  shape rule finds room for) the rows are read once too. A tile inside its
  group is one dot and one store. A tile that straddles groups is taken
  `SUB` rows at a time, only the sub-blocks that hold a row of the visit's
  group, and stored under the group's mask: a straddling tile costs its
  sub-blocks once and one more, not the tile a group.
- `_tgmm` (the weights' gradient): the same visits innermost on the grid, a
  float32 accumulator (tk, tn) in VMEM that is zeroed at a group's first
  visit and written at its last. The contraction is over the rows, the
  sublanes of both operands (`dot_general` over dimension 0 of both; Mosaic
  transposes the tile). A group of no rows is visited once so that its
  gradient is written as zeros. On a straddling tile both operands are
  masked to the group's rows: a row of no group may hold anything, and
  0 x NaN is NaN.

The path is chosen **from the shape alone, before anything is traced**
(`tiling`): the kernels where the operands are bfloat16, N is a multiple of
the row tile and K and M of 128; `lax.ragged_dot` otherwise. On the kernels' path `lax.platform_dependent`
takes Mosaic where the program is lowered for the TPU and `lax.ragged_dot`
itself anywhere else (not the interpreted kernel: the tests of the models run
XLA's op on the CPU as they did, and `tests/test_grouped_matmul.py` runs the
kernels interpreted by `_gmm` and `_tgmm`'s own argument). The builders are
jitted, so the stacks of one shape, a layer run again and the backward loop
share one trace a shape.

`tile_visits` is the pure count (numpy or jax) that
`models/transformer.routing_stats` reads for `kungfu_moe_tile_visit_share`.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kungfu_tpu.ops.kernel_call import kernel_call

SUB = 128  # rows of a straddling tile's sub-block
VMEM_LIMIT = 100 << 20  # of the chip's 128 MiB; the default scope is 16
# what a grid step's blocks may take of it by `tiling`'s account. Measured
# (PERF.md, PR 72): `_gmm` at 31.3 and 32.4 MB runs at its rate and at 33.4
# and 39.8 at two thirds of it (12,288 x 3,072 x 2,048 on row tiles of 512);
# `_tgmm` runs at its rate at 53.3 MB (16,384 x 3,584 x 1,024)
GMM_ROOM = 32 << 20
TGMM_ROOM = 56 << 20


class Tiles(NamedTuple):
    """Rows a tile (`tm`); the columns out a grid step of the forward
    product (`tn`, of M) and of the rows' gradient (`tn_r`, of K); the
    weights' gradient's tile (`tk` of K by `tn_t` of M)."""
    tm: int
    tn: int
    tn_r: int
    tk: int
    tn_t: int


def _widest(width: int, fits) -> int:
    """The largest divisor of `width` in whole lane tiles that `fits`, at
    least one lane tile."""
    lanes = width // 128
    for parts in range(1, lanes + 1):
        if lanes % parts == 0 and fits(width // parts):
            return width // parts
    return 128


def tiling(N: int, K: int, M: int, groups: int) -> Optional[Tiles]:
    """The kernels' tiles for bfloat16 rows (N, K) on `groups` weights (K, M),
    or None where the shape takes `lax.ragged_dot`: N, K or M not a multiple
    of 128. The row tile is the largest of 512, 256 and 128 that divides N and
    leaves `_gmm` its weight tile at the whole width both ways within
    `GMM_ROOM`, both buffers of every block and the float32 result counted
    (512 for every shape the cells have but 12,288 x 3,072 x 2,048, which
    takes 256); only rows of 128 narrow the weight tile. The weights'
    gradient's tile is as wide as `TGMM_ROOM` allows."""
    if K % 128 or M % 128 or N % 128 or not groups:
        return None

    def forward(tm, k, tn):  # rows, weights and out twice, the result once
        return 4 * (tm * k + k * tn + tm * tn) + 4 * tm * tn

    def outer(tm, tk, tn):  # operands and out twice, accumulator, result
        return 4 * (tm * tk + tm * tn + tk * tn) + 8 * tk * tn

    tm = next((t for t in (512, 256) if N % t == 0 and max(
        forward(t, K, M), forward(t, M, K)) <= GMM_ROOM), 128)
    tn = _widest(M, lambda t: forward(tm, K, t) <= GMM_ROOM)
    tn_r = _widest(K, lambda t: forward(tm, M, t) <= GMM_ROOM)
    tn_t = _widest(M, lambda t: outer(tm, 128, t) <= TGMM_ROOM)
    tk = _widest(K, lambda t: outer(tm, t, tn_t) <= TGMM_ROOM)
    return Tiles(tm, tn, tn_r, tk, tn_t)


def tile_visits(group_sizes, tm: int):
    """Visits a group: the row tiles of `tm` that hold one of its rows, none
    for a group of no rows. `group_sizes` (..., e) numpy's or jax's; their sum
    over the tiles of a buffer is what the kernels work on of it, and what
    the groups' edges cost (1.0: every tile once)."""
    xp = jnp if isinstance(group_sizes, jax.Array) else np
    ends = xp.cumsum(group_sizes, axis=-1)
    starts = ends - group_sizes
    return xp.where(group_sizes > 0, -(-ends // tm) - starts // tm, 0)


@functools.partial(jax.jit, static_argnames=("tm", "n_tiles", "empty"))
def _visits(group_sizes, *, tm: int, n_tiles: int, empty: bool):
    """The visits in the rows' order, as the kernels' four scalar-prefetch
    arrays, and their count: `group` and `tile` (n_tiles + e - 1,) int32, of
    which the first `count` are real (the rest name the last group and a
    tile inside the buffer and are never run), and the groups' first rows
    and ends (e,). With `empty` a group of no rows is one visit, of whatever
    tile. Jitted: the three products of a layer, its stacks and its second
    run share one trace."""
    # `lax` and not `jax.numpy`, whose every function is a jitted one that
    # is traced and lowered by itself: half of this function's own cost
    sizes = lax.convert_element_type(group_sizes, jnp.int32)
    e = sizes.shape[0]
    ends = lax.cumsum(sizes)
    starts = ends - sizes
    per = lax.select(sizes > 0, lax.div(ends + (tm - 1), tm) - lax.div(starts, tm),
                     lax.full_like(sizes, 0))  # `tile_visits`
    if empty:
        per = lax.max(per, lax.full_like(per, 1))
    upto = lax.cumsum(per)  # visits up to a group's last
    slots = n_tiles + e - 1
    at = lax.iota(jnp.int32, slots)
    group_of = lax.broadcasted_iota(jnp.int32, (slots, e), 1)
    passed = lax.broadcast_in_dim(upto, (slots, e), (1,)) <= lax.broadcast_in_dim(
        at, (slots, e), (0,))
    group = lax.min(lax.reduce_sum(lax.convert_element_type(passed, jnp.int32), (1,)),
                    lax.full_like(at, e - 1))
    # the group's first tile less the visits before the group, by a one-hot
    # sum and not a gather: a fusion beside the compare above
    first = lax.div(starts, tm) - (upto - per)
    mine = lax.broadcast_in_dim(group, (slots, e), (0,)) == group_of
    tile = at + lax.reduce_sum(lax.select(
        mine, lax.broadcast_in_dim(first, (slots, e), (1,)),
        lax.full((slots, e), 0, jnp.int32)), (1,))
    return (group, lax.clamp(0, tile, n_tiles - 1), starts, ends), upto[-1]


# The kernels' bodies are written in `lax`, as `_visits` is: an operator or a
# `jax.numpy` function on a traced value is a jitted function of its own, and
# six kernels a pair of widths are traced for every first step (a third of a
# kernel's trace; PERF.md, PR 72).
_and = lax.bitwise_and
_f32 = functools.partial(lax.convert_element_type, new_dtype=jnp.float32)


def _span(group_ref, tile_ref, starts_ref, ends_ref, at, tm: int):
    """The rows [lo, hi) of visit `at`'s tile that are its group's, counted
    from the tile's first row."""
    g = group_ref[at]
    first = lax.mul(tile_ref[at], tm)
    return lax.sub(starts_ref[g], first), lax.sub(ends_ref[g], first)


def _rows_in(shape, lo, hi):
    """Mask of `shape`: rows i with lo <= i < hi."""
    row = lax.broadcasted_iota(jnp.int32, shape, 0)
    return _and(lax.ge(row, lax.full_like(row, lo)),
                lax.lt(row, lax.full_like(row, hi)))


def _each_sub_block(tm: int, lo, hi, run):
    """`run(part, lo, hi)` for each sub-block of `SUB` rows of a tile that
    holds one of the rows [lo, hi), `part` its rows and the bounds counted
    from its first row. A loop and not `tm // SUB` copies of the body: the
    kernel's trace and its lowering are the first step's (PERF.md, PR 72)."""
    def sub_block(j, carry):
        first = pl.multiple_of(lax.mul(j, SUB), SUB)

        @pl.when(_and(lax.lt(lo, lax.add(first, SUB)), lax.gt(hi, first)))
        def _():
            run(pl.ds(first, SUB), lax.sub(lo, first), lax.sub(hi, first))

        return carry

    lax.fori_loop(0, tm // SUB, sub_block, None)


def _gmm_kernel(group_ref, tile_ref, starts_ref, ends_ref, rows_ref, w_ref,
                out_ref, *, tm: int, transposed: bool):
    lo, hi = _span(group_ref, tile_ref, starts_ref, ends_ref,
                   pl.program_id(1), tm)
    dims = (((1,), (1 if transposed else 0,)), ((), ()))

    def product(rows):
        return lax.dot_general(rows, w_ref[...], dims,
                               preferred_element_type=jnp.float32)

    inside = _and(lax.le(lo, 0), lax.ge(hi, tm))

    @pl.when(inside)
    def _():
        out_ref[...] = lax.convert_element_type(product(rows_ref[...]),
                                                out_ref.dtype)

    @pl.when(lax.bitwise_not(inside))
    def _():
        def masked(part, lo, hi):
            y = product(rows_ref[part, :])
            out_ref[part, :] = lax.convert_element_type(
                lax.select(_rows_in(y.shape, lo, hi), y, _f32(out_ref[part, :])),
                out_ref.dtype)

        _each_sub_block(tm, lo, hi, masked)


def _tgmm_kernel(group_ref, tile_ref, starts_ref, ends_ref, rows_ref, dy_ref,
                 out_ref, acc_ref, *, tm: int):
    at = pl.program_id(2)
    last = lax.sub(pl.num_programs(2), 1)
    g = group_ref[at]
    lo, hi = _span(group_ref, tile_ref, starts_ref, ends_ref, at, tm)
    dims = (((0,), (0,)), ((), ()))

    def add(rows, dy):
        acc_ref[...] = lax.add(acc_ref[...], lax.dot_general(
            rows, dy, dims, preferred_element_type=jnp.float32))

    @pl.when(lax.bitwise_or(lax.eq(at, 0),
                            lax.ne(group_ref[lax.max(lax.sub(at, 1), 0)], g)))
    def _():
        acc_ref[...] = lax.full(acc_ref.shape, 0, acc_ref.dtype)

    inside = _and(lax.le(lo, 0), lax.ge(hi, tm))

    @pl.when(inside)
    def _():
        add(rows_ref[...], dy_ref[...])

    # a group of no rows is visited to be written
    @pl.when(_and(lax.bitwise_not(inside), lax.gt(hi, lo)))
    def _():
        def masked(part, lo, hi):
            def mine(ref):
                a = ref[part, :]
                return lax.convert_element_type(lax.select(
                    _rows_in(a.shape, lo, hi), _f32(a),
                    lax.full(a.shape, 0, jnp.float32)), a.dtype)

            add(mine(rows_ref), mine(dy_ref))

        _each_sub_block(tm, lo, hi, masked)

    @pl.when(lax.bitwise_or(lax.eq(at, last),
                            lax.ne(group_ref[lax.min(lax.add(at, 1), last)], g)))
    def _():
        out_ref[...] = lax.convert_element_type(acc_ref[...], out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "tn", "transposed",
                                             "interpret"))
def _gmm(rows, weights, group_sizes, *, tm: int, tn: int,
         transposed: bool = False, interpret: bool = False):
    """rows (N, K) times weights (e, K, M) over the groups, or with
    `transposed` times weights (e, M, K) transposed -> (N, M)."""
    N, K = rows.shape
    M = weights.shape[1 if transposed else 2]
    scalars, count = _visits(group_sizes, tm=tm, n_tiles=N // tm, empty=False)
    if transposed:
        w_spec = pl.BlockSpec((None, tn, K), lambda n, at, g, t, *_: (g[at], n, 0))
    else:
        w_spec = pl.BlockSpec((None, K, tn), lambda n, at, g, t, *_: (g[at], 0, n))
    return kernel_call(
        functools.partial(_gmm_kernel, tm=tm, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(M // tn, count),
            in_specs=[pl.BlockSpec((tm, K), lambda n, at, g, t, *_: (t[at], 0)),
                      w_spec],
            out_specs=pl.BlockSpec((tm, tn), lambda n, at, g, t, *_: (t[at], n))),
        out_shape=jax.ShapeDtypeStruct((N, M), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * N * K * M, transcendentals=0,
            bytes_accessed=rows.dtype.itemsize * (
                N * K * (M // tn) + weights.size + N * M)),
        interpret=interpret,
        name="grouped_matmul_transposed" if transposed else "grouped_matmul",
    )(*scalars, rows, weights)


@functools.partial(jax.jit, static_argnames=("groups", "tm", "tk", "tn",
                                             "interpret"))
def _tgmm(rows, dy, group_sizes, *, groups: int, tm: int, tk: int, tn: int,
          interpret: bool = False):
    """rows[g]^T (K, n_g) times dy[g] (n_g, M) a group -> (e, K, M), zeros
    for a group of no rows."""
    N, K = rows.shape
    M = dy.shape[1]
    scalars, count = _visits(group_sizes, tm=tm, n_tiles=N // tm, empty=True)
    return kernel_call(
        functools.partial(_tgmm_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(K // tk, M // tn, count),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda k, n, at, g, t, *_: (t[at], k)),
                pl.BlockSpec((tm, tn), lambda k, n, at, g, t, *_: (t[at], n))],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda k, n, at, g, t, *_: (g[at], k, n)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((groups, K, M), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * N * K * M, transcendentals=0,
            bytes_accessed=rows.dtype.itemsize * (
                N * K * (M // tn) + N * M * (K // tk) + groups * K * M)),
        interpret=interpret, name="grouped_matmul_outer",
    )(*scalars, rows, dy)


def _ragged_dot_transposes(rows, weights, group_sizes, dy):
    return jax.vjp(lambda r, w: lax.ragged_dot(r, w, group_sizes),
                   rows, weights)[1](dy)


def _kernel_transposes(tiles: Tiles, rows, weights, group_sizes, dy):
    return (_gmm(dy, weights, group_sizes, tm=tiles.tm, tn=tiles.tn_r,
                 transposed=True),
            _tgmm(rows, dy, group_sizes, groups=weights.shape[0], tm=tiles.tm,
                  tk=tiles.tk, tn=tiles.tn_t))


# Both ways the choice of the platform is jitted too: a call site of a shape
# that was traced before (the gate's product beside the up product's, a layer
# run again, the scan's transposition) traces neither branch again.
@functools.partial(jax.jit, static_argnames=("tiles",))
def _forward(rows, weights, group_sizes, *, tiles: Tiles):
    return lax.platform_dependent(
        rows, weights, group_sizes,
        tpu=functools.partial(_gmm, tm=tiles.tm, tn=tiles.tn),
        default=lax.ragged_dot)


@functools.partial(jax.jit, static_argnames=("tiles",))
def _transposes(rows, weights, group_sizes, dy, *, tiles: Tiles):
    return lax.platform_dependent(
        rows, weights, group_sizes, dy,
        tpu=functools.partial(_kernel_transposes, tiles),
        default=_ragged_dot_transposes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _grouped(tiles: Tiles, rows, weights, group_sizes):
    return _forward(rows, weights, group_sizes, tiles=tiles)


def _grouped_fwd(tiles, rows, weights, group_sizes):
    return (_forward(rows, weights, group_sizes, tiles=tiles),
            (rows, weights, group_sizes))


def _grouped_bwd(tiles, res, dy):
    return (*_transposes(*res, dy, tiles=tiles), None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(rows, weights, group_sizes):
    """rows (N, K) ordered in groups of `group_sizes` (e,), each group times
    its own of weights (e, K, M) -> (N, M) in the rows' type, accumulated in
    float32; `lax.ragged_dot`'s contract, and `lax.ragged_dot` itself where
    the shape does not tile (`tiling`), the operands are not bfloat16 or the
    program is not the TPU's."""
    tiles = tiling(*rows.shape, weights.shape[2], weights.shape[0])
    if tiles is None or not rows.dtype == weights.dtype == jnp.bfloat16:
        return lax.ragged_dot(rows, weights, group_sizes)
    return _grouped(tiles, rows, weights, group_sizes)
