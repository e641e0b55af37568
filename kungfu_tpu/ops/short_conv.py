"""The gated short convolution of an LFM2 layer in one Pallas kernel each way:

    [B | C | x] = bcx           three equal thirds of the projection's output
    z_t = B_t * x_t
    c_t = sum_{i<K} taps_i z_{t-(K-1)+i}        a channel, zeros before the row
    y_t = C_t * c_t

bcx (B, S, 3 D) is the input projection's output as it leaves the matmul, the
taps (K, D) a number a tap and channel, y (B, S, D) what the output projection
reads. No activation, no bias and no state: the convolution is the mixer.
`segments` (B, S) whole numbers, where given, say which document a position
is of, and a tap that would read a position of another document reads zero,
as `ops.gated_delta.causal_conv`'s does. The products and their sums are
float32 between the upcast of bcx and the downcast of y.

Composed from what XLA has (three slices, a product, a padded copy and K
windows, a product, and autodiff's transposes of each) the op is five passes
over (S, D) to (S, 3 D) arrays each way. Here it is two kernels on a grid
over (batch, row blocks), Mosaic where the program is lowered for the TPU and
the same kernels interpreted anywhere else (`gated_delta._on_platform`):

1. `_forward_kernel` reads a row block of bcx once and writes y once. The K
   - 1 rows of z before the block's first come with it: a second view of bcx,
   the `HALO` rows that end where the block begins (3 % of a block of 512),
   zeros in their place at the row's start.
2. `_backward_kernel` reads bcx and dy once and writes bcx's cotangent once,
   in two sweeps over the block in VMEM. Down the rows with z's history in
   hand: c again, dC = dy c, and the taps' gradient dtaps_i = sum_t dc_t
   z_{t-(K-1)+i} with dc = dy C, a float32 accumulator of eight sublanes a
   batch row that stays in VMEM along the grid's row-block axis (`arbitrary`)
   and is added up outside. Up the rows with dc's future in hand: dz_t =
   sum_i taps_i dc_{t+(K-1)-i}, dB = dz x, dx = dz B. The future is the
   `HALO` rows of bcx and dy behind the block's last, zeros at the row's end.

Inside a block both kernels work on `ROWS` x `LANES` tiles that stay in
registers: a channel looks at no other channel, so the lanes are taken a
chunk at a time and the rows in a loop whose carry is the last eight-row tile
seen, turned by each tap's distance (`pltpu.roll` along the sublanes): a
shifted tile is a select between the turned tile and the turned carry.

Between the passes the op keeps bcx, the taps and the segments, nothing of
its own: it costs the same kept or run again under a layer's checkpoint.

The kernels take rows of one document, D a multiple of 128 lanes, S a
multiple of `HALO` rows and K = `TAPS` (LFM2's 3, the one count a model asks
for); anything else takes `plain`, the `jnp` form of the same arithmetic under
autodiff, which is also what the tests compare the kernels with. Packed rows
take it because no configuration packs rows for this mixer yet, and kernels
that were given each position's neighbours of its own document as one whole
number a position (a (rows, 1) int32 block) ran at twice the unpacked
kernels' time on the chip, 9 % under `plain` (PERF.md section 6, PR 57).
Laid along the lanes they cost nothing the trace shows: `ops.ssm_conv`'s
kernels, which are built from this file's pieces (`_moved`, `_shifted`,
`_split`, `_taps_times`, `_block_rows`, `_halo_maps`, for any K up to a
register tile's rows), read each position's depth into its document as a
(rows, 128) int32 block and run the Granite 4.0-H cell's packed rows at the
time of rows of one document (PERF.md section 6, PR 58); a packed cell on
this mixer brings `ssm_conv.document_marks` and `_seen` here. The builders
are jitted so that a model's stacks of one shape, and a layer run again,
share one trace and one lowering of each kernel.

Tensor parallelism: as `ops.gated_norm`'s. The op is handed no mesh; the
channels over `tp` (`models/transformer.param_pspecs`) are the partitioner's,
which refuses a Mosaic call in a program it partitions, so on the TPU the
mixer runs in a program of one device until its caller stands under a
`shard_map`; interpreted (the CPU tests' tp mesh of two) it is plain
operations and partitions like them.

`models/mixers/short_conv._short_conv_mixer` is the caller, under the scope
`sconv_core`. On the chip: PERF.md section 6, PR 57.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kungfu_tpu.ops.gated_delta import (VMEM_LIMIT, _on_platform,
                                        _same_document, _taps_over)
from kungfu_tpu.ops.kernel_call import kernel_call

BLOCK_BYTES = 24 << 20  # of VMEM for a grid step's blocks, double-buffered
HALO = 16  # rows of a neighbouring block a grid step reads: a bfloat16 tile's
ROWS = 16  # of a block at a time in the kernels' loops
LANES = 256  # channels at a time: a loop's tiles stay in registers
TILE = 8  # rows of a float32 register
TAPS = 3  # the taps the kernels are built and tested for: `conv_L_cache`


def plain(bcx, taps, segments=None):
    """The op in `jnp`, for autodiff: bcx (B, S, 3 D), taps (K, D),
    `segments` (B, S) or None -> y (B, S, D) in bcx's type; the windows and
    their masks are `causal_conv`'s own (`gated_delta._taps_over`)."""
    K, D = taps.shape
    b, c, x = (bcx[..., i * D:(i + 1) * D].astype(jnp.float32) for i in range(3))
    z = jnp.pad(b * x, ((0, 0), (K - 1, 0), (0, 0)))
    conv = _taps_over(z, taps, bcx.shape[1], _same_document(segments, K, False))
    return (c * conv).astype(bcx.dtype)


def tiles(S: int, D: int, K: int, segments=None) -> bool:
    """Whether the kernels take the call: one document a row, the channels
    whole lane tiles, the sequence whole halos, `TAPS` taps."""
    return segments is None and D % 128 == 0 and S % HALO == 0 and K == TAPS


def _lanes(D: int) -> int:
    return LANES if D % LANES == 0 else 128


def _block_rows(S: int, row_bytes: int) -> int:
    """The largest divisor of S that whole halos make up and whose rows of
    `row_bytes` in VMEM, double-buffered, stay under `BLOCK_BYTES`; a halo
    where none does."""
    return max((rows for rows in range(HALO, S + 1, HALO)
                if S % rows == 0 and 2 * rows * row_bytes <= BLOCK_BYTES),
               default=HALO)


def _shifted(tiles_, carry, j: int, ahead: bool):
    """The chunk `tiles_` (eight-row float32 tiles in order) moved j rows:
    row r of the result is row r - j of the chunk (r + j, `ahead`), the rows
    that fall off its end taken from `carry`, the tile before it (behind
    it). A tile turned by j holds its own rows where they stay inside it and
    its neighbour's turned likewise where they do not."""
    at = lax.broadcasted_iota(jnp.int32, tiles_[0].shape, 0)
    turn = TILE - j if ahead else j
    inside = at < TILE - j if ahead else at >= j
    turned = [pltpu.roll(t, turn, 0) for t in tiles_]
    beside = pltpu.roll(carry, turn, 0)
    order = ([*turned[1:], beside] if ahead else [beside, *turned[:-1]])
    return jnp.concatenate([jnp.where(inside, own, other)
                            for own, other in zip(turned, order)], axis=0)


def _moved(t, carry, K: int, ahead: bool):
    """[t moved j rows for j = 0..K-1] of a chunk t (`ROWS`, lanes) float32
    (`_shifted`)."""
    parts = _split(t)
    return [t] + [_shifted(parts, carry, j, ahead) for j in range(1, K)]


def _taps_times(taps, moved):
    """sum_j taps[K - 1 - j] * moved[j]: the tap on a position itself is the
    last, the one j rows away the j-th before it."""
    return functools.reduce(
        lambda total, term: total + term,
        (taps[len(taps) - 1 - j] * m for j, m in enumerate(moved)))


def _split(t):
    return [t[i:i + TILE] for i in range(0, t.shape[0], TILE)]


def _forward_kernel(bcx_ref, before_ref, taps_ref, y_ref, *, K: int):
    f32 = jnp.float32
    D = y_ref.shape[2]
    first = (pl.program_id(1) > 0).astype(f32)  # nothing before the row
    for lo in range(0, D, _lanes(D)):
        cols = [slice(i * D + lo, i * D + lo + _lanes(D)) for i in range(3)]
        taps = [taps_ref[i:i + 1, cols[0]] for i in range(K)]
        last = slice(HALO - TILE, HALO)
        history = (before_ref[0, last, cols[0]].astype(f32)
                   * before_ref[0, last, cols[2]].astype(f32)) * first

        def turn(i, carry, cols=cols, taps=taps):
            at = pl.ds(pl.multiple_of(i * ROWS, ROWS), ROWS)
            z = (bcx_ref[0, at, cols[0]].astype(f32)
                 * bcx_ref[0, at, cols[2]].astype(f32))
            conv = _taps_times(taps, _moved(z, carry, K, False))
            y_ref[0, at, cols[0]] = (
                bcx_ref[0, at, cols[1]].astype(f32) * conv).astype(y_ref.dtype)
            return z[ROWS - TILE:]

        lax.fori_loop(0, y_ref.shape[1] // ROWS, turn, history)


def _sublanes(t):
    """(rows, width) float32 -> (8, width): the rows added up a sublane."""
    first, *rest = _split(t)
    return sum(rest, first)


def _backward_kernel(bcx_ref, before_ref, behind_ref, dy_ref, dy_behind_ref,
                     taps_ref, dbcx_ref, dtaps_ref, *, K: int):
    f32 = jnp.float32
    D = dy_ref.shape[2]
    n = dy_ref.shape[1] // ROWS
    first = (pl.program_id(1) > 0).astype(f32)
    last = (pl.program_id(1) < pl.num_programs(1) - 1).astype(f32)

    @pl.when(pl.program_id(1) == 0)
    def _():
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)

    for lo in range(0, D, _lanes(D)):
        cols = [slice(i * D + lo, i * D + lo + _lanes(D)) for i in range(3)]
        taps = [taps_ref[i:i + 1, cols[0]] for i in range(K)]
        end = slice(HALO - TILE, HALO)
        history = (before_ref[0, end, cols[0]].astype(f32)
                   * before_ref[0, end, cols[2]].astype(f32)) * first
        future = (behind_ref[0, :TILE, cols[1]].astype(f32)
                  * dy_behind_ref[0, :TILE, cols[0]].astype(f32)) * last

        def down(i, carry, cols=cols, taps=taps):
            """c again with z's history: dC, and the taps' gradient."""
            tail, sums = carry
            at = pl.ds(pl.multiple_of(i * ROWS, ROWS), ROWS)
            z = (bcx_ref[0, at, cols[0]].astype(f32)
                 * bcx_ref[0, at, cols[2]].astype(f32))
            dy = dy_ref[0, at, cols[0]].astype(f32)
            dc = dy * bcx_ref[0, at, cols[1]].astype(f32)
            backs = _moved(z, tail, K, False)
            conv = _taps_times(taps, backs)
            dbcx_ref[0, at, cols[1]] = (dy * conv).astype(dbcx_ref.dtype)
            return z[ROWS - TILE:], tuple(
                sums[K - 1 - j] + _sublanes(dc * backs[j])
                for j in reversed(range(K)))

        zero = jnp.zeros((TILE, _lanes(D)), f32)
        _, sums = lax.fori_loop(0, n, down, (history, (zero,) * K))
        for i in range(K):
            dtaps_ref[0, i, :, cols[0]] += sums[i]

        def up(i, carry, cols=cols, taps=taps):
            """dz with dc's future: dB and dx."""
            at = pl.ds(pl.multiple_of((n - 1 - i) * ROWS, ROWS), ROWS)
            b = bcx_ref[0, at, cols[0]].astype(f32)
            x = bcx_ref[0, at, cols[2]].astype(f32)
            dc = (dy_ref[0, at, cols[0]].astype(f32)
                  * bcx_ref[0, at, cols[1]].astype(f32))
            dz = _taps_times(taps, _moved(dc, carry, K, True))
            dbcx_ref[0, at, cols[0]] = (dz * x).astype(dbcx_ref.dtype)
            dbcx_ref[0, at, cols[2]] = (dz * b).astype(dbcx_ref.dtype)
            return dc[:TILE]

        lax.fori_loop(0, n, up, future)


def _halo_maps(S: int, rows: int):
    """Where along S, in halos, a grid step s of `rows` rows finds the halo
    that ends where its block begins and the one that begins where it ends;
    at the row's two ends any block, read as zeros."""
    per, halos = rows // HALO, S // HALO
    return (lambda s: jnp.maximum(s * per - 1, 0),
            lambda s: jnp.minimum((s + 1) * per, halos - 1))


def _specs(bcx, *, passes: int):
    """The grid and the block specs by name. `passes`: how many rows of D
    channels in bcx's type a grid step holds, for the block rule."""
    B, S, wide = bcx.shape
    D = wide // 3
    rows = _block_rows(S, passes * D * bcx.dtype.itemsize)
    before, behind = _halo_maps(S, rows)
    return (B, S // rows), dict(
        wide=pl.BlockSpec((1, rows, wide), lambda b, s: (b, s, 0)),
        rows=pl.BlockSpec((1, rows, D), lambda b, s: (b, s, 0)),
        before=pl.BlockSpec((1, HALO, wide), lambda b, s: (b, before(s), 0)),
        behind=pl.BlockSpec((1, HALO, wide), lambda b, s: (b, behind(s), 0)),
        dy_behind=pl.BlockSpec((1, HALO, D), lambda b, s: (b, behind(s), 0)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _forward(bcx, taps, *, interpret: bool):
    B, S, wide = bcx.shape
    K, D = taps.shape
    grid, spec = _specs(bcx, passes=4)
    return kernel_call(
        functools.partial(_forward_kernel, K=K),
        grid=grid,
        in_specs=[spec["wide"], spec["before"],
                  pl.BlockSpec((K, D), lambda b, s: (0, 0))],
        out_specs=spec["rows"],
        out_shape=jax.ShapeDtypeStruct((B, S, D), bcx.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name="short_conv_forward",
    )(bcx, bcx, taps.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _backward(bcx, taps, dy, *, interpret: bool):
    """-> (bcx's cotangent, the taps' as (B, K, 8, D) float32 sums, a
    sublane's share of the positions each)."""
    B, S, wide = bcx.shape
    K, D = taps.shape
    grid, spec = _specs(bcx, passes=7)
    return kernel_call(
        functools.partial(_backward_kernel, K=K),
        grid=grid,
        in_specs=[spec["wide"], spec["before"], spec["behind"], spec["rows"],
                  spec["dy_behind"], pl.BlockSpec((K, D), lambda b, s: (0, 0))],
        out_specs=[spec["wide"],
                   pl.BlockSpec((1, K, TILE, D), lambda b, s: (b, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
                   jax.ShapeDtypeStruct((B, K, TILE, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name="short_conv_backward",
    )(bcx, bcx, bcx, dy, dy, taps.astype(jnp.float32))


@jax.custom_vjp
def short_conv(bcx, taps, segments=None):
    """bcx (B, S, 3 D) = [B | C | x], taps (K, D), `segments` (B, S) whole
    numbers or None -> C * conv_K(B * x) as (B, S, D) in bcx's type, causal
    along S and within a document, float32 inside."""
    return _fwd(bcx, taps, segments)[0]


def _fwd(bcx, taps, segments=None):
    K, D = taps.shape
    if tiles(bcx.shape[1], D, K, segments):
        y = _on_platform(_forward, bcx, taps)
    else:
        y = plain(bcx, taps, segments)
    return y, (bcx, taps, segments)


def _bwd(res, dy):
    bcx, taps, segments = res
    K, D = taps.shape
    if not tiles(bcx.shape[1], D, K, segments):
        dbcx, dtaps = jax.vjp(lambda bcx, taps: plain(bcx, taps, segments),
                              bcx, taps)[1](dy)
        return dbcx, dtaps, None
    dbcx, dtaps = _on_platform(_backward, bcx, taps, dy)
    return dbcx, jnp.sum(dtaps, axis=(0, 2)).astype(taps.dtype), None


short_conv.defvjp(_fwd, _bwd)
