"""The front of a Mamba-2 mixer's scan in one Pallas kernel each way: the
causal convolution with its bias, the silu, and v = Delta x in the scan's
layout, once through HBM forward and once backward.

    u = zxbc[..., inner:]                 the projection's columns from x on
    a_t = sum_{i<K} taps_i u_{t-(K-1)+i} + bias      a channel, within a document
    xbc = silu(a)                         [x | B | C], (B, S, C)
    v[b, h, t, :] = Delta[b, t, h] * x[b, t, h P:(h + 1) P]       (B, H, S, P)

zxbc (B, S, inner + C) is the input projection's output as it leaves the
matmul, z its first `inner` = H P columns, which the op never reads; the taps
(K, C) and the bias (C,) a number a tap and channel; Delta (B, S, H) float32
the step a head and position, the softplus already taken. `segments` (B, S)
whole numbers, where given, say which document a position is of: a tap that
would read a position of another document reads zero, as
`ops.gated_delta.causal_conv`'s does. The products, their sum, the bias and
the silu are float32 and `xbc` is that value rounded once (`causal_conv`
followed by a silu rounds twice); v is x as `xbc` holds it times Delta in
float32, rounded once, so the scan (`ops.ssm_scan`, which reads v as laid
out here) and the norm (`ops.gated_norm`, which reads x from `xbc`) see one
x.

Composed from what XLA has, the scope was a slice of zxbc, the convolution's
fusion, and four passes that make v: x to float32, a copy of that, a copy
into (S, H, P) with P = 64 padded to a lane tile, and the transposition
(34 of the Granite 4.0-H step's 60.7 ms under `ssm_conv`, PERF.md section 6,
PR 58). Here it is two kernels on a grid over (batch, row blocks), Mosaic
where the program is lowered for the TPU and the same kernels interpreted
anywhere else (`gated_delta._on_platform`):

1. `_forward_kernel` reads a row block of zxbc's columns once (two views of
   the one array, x's `inner` columns and the 2 G N of B and C behind them:
   nothing is sliced out) with the `HALO` rows that end where the block
   begins, and writes `xbc` once and each head's columns of v into v's (1,
   H, rows, P) block. No float32 array leaves VMEM.
2. `_backward_kernel` reads the same views, `dxbc` and `dv`, and writes the
   cotangent of zxbc's columns (in `dxbc`'s place, `input_output_aliases`)
   and d Delta once, in one sweep up the rows: the pre-activation again
   from zxbc with the rows before it, the silu's derivative, dx = dxbc +
   Delta dv and d Delta = sum_p dv x (x as `xbc` held it), then da's future
   in hand, du_t = sum_i taps_i da_{t+(K-1)-i}. The grid takes a row's
   blocks last to first (`arbitrary`), and da in the first rows of the
   block behind, which it took the step before, waits in VMEM scratch: no
   halo behind a block is read and nothing is computed twice. dtaps and
   dbias are float32 accumulators of a loop's turn of rows a batch row
   that stay in VMEM along the same axis and are added up outside.

Inside a block both kernels loop over turns of 32 rows (16 where a block is
no multiple of 32) and take all the channels in each, x's and then B and
C's, with `ops.short_conv`'s pieces (`_moved`, `_shifted`, `_taps_times`,
`_block_rows`, `_halo_maps`). What a v5e charges for (PERF.md section 6,
PR 58): a turn of the loop costs 0.3 to 0.4 us whatever it holds, so the
first version, 16 rows x 256 lanes a turn and a loop a lane chunk, ran four
times slower than this one at the same arithmetic; and every operation
across lanes is dear, so

- Delta a feature is one gather along the lanes (`_a_feature`: Mosaic's
  gather stays inside a register tile, so the tiles are stood one under
  another for it, `_tall`), not a broadcast a head;
- d Delta's sum over a head's P features is a fold (`_fold`): a step adds
  each lane its neighbour at half the last distance and puts the halves of
  two tiles that hold whole sums side by side, two turns of a tile and
  log2 P more where a lane sum a head is log2 P turns a tile; the sums
  land in a (rows, 128) tile in an order `_fold_order` computes by folding
  tiles that name their segments, and XLA picks them out of the (B, S, 128)
  array the kernel writes;
- a head's columns go to and come from the scan's layout as 64-lane slices
  of one wide bfloat16 value.

Packed rows take the kernels. The documents' boundaries reach them as
`marks` (B, S, 128) int32, each position's depth into its own document (t
less its document's first position) along all the lanes of a tile: a kernel
reads a (rows, 128) block and keeps tap j's product where the depth is j or
more; towards the future the same array read at the later position. A (rows,
1) block of whole numbers cost `ops.short_conv`'s kernels as much as the
operator itself (PERF.md section 6, PR 57); laid along the lanes the marks
cost nothing that the trace shows. `document_marks` makes them from
`segments`; a model makes them once a step, outside its layer scans
(`models/transformer._hidden`), and the op makes its own where it is handed
none.

Between the passes the op keeps its inputs and nothing of its own: it costs
the same kept or run again under a layer's checkpoint.

The kernels take `inner` and 2 G N whole lane tiles with 2 G N a divisor of
`inner` (the second view's index) and `inner` a power of two of tiles (the
fold's halves), H and P divisors of a lane tile, S a multiple of `HALO` rows
and K = `TAPS` (Mamba-2's 4); anything else takes `plain`, today's
composition (`causal_conv`, a silu, the transposition), which is also what
the tests compare the kernels with; which of the two a pass took is counted
where it is traced, in `kungfu_ssm_conv_rows_total{pass, path}`
(docs/telemetry.md). The builders are jitted so that a model's stacks of one
shape, and a layer run again, share one trace and one lowering of each kernel.

Tensor parallelism: as `ops.gated_norm`'s. The op is handed no mesh; the
channels over `tp` (`models/transformer.param_pspecs`) are the partitioner's,
which refuses a Mosaic call in a program it partitions, so on the TPU the
mixer runs in a program of one device until its caller stands under a
`shard_map`; interpreted (the CPU tests' tp mesh of two) it is plain
operations and partitions like them.

`models/mixers/mamba2._mamba2_mixer` is the caller, under the scope `ssm_conv`.
On the chip: PERF.md section 6, PR 58.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kungfu_tpu.ops import gated_delta
from kungfu_tpu.ops.gated_delta import VMEM_LIMIT, _on_platform
from kungfu_tpu.ops.kernel_call import kernel_call
from kungfu_tpu.ops.short_conv import (HALO, ROWS, TILE, _block_rows,
                                       _halo_maps, _moved, _shifted, _split,
                                       _taps_times)

TAPS = 4  # the taps the kernels are built and tested for: Mamba-2's `d_conv`
MARK_LANES = 128  # a position's depth into its document, along a lane tile
PIECE = 4096  # channels at a time, first to last operation, in a loop's turn


def plain(zxbc, taps, bias, delta, segments=None):
    """The op as XLA composes it, for autodiff: zxbc (B, S, inner + C), taps
    (K, C), bias (C,), delta (B, S, H) float32, `segments` (B, S) or None ->
    (xbc (B, S, C), v (B, H, S, P)) in zxbc's type."""
    B, S, wide = zxbc.shape
    H = delta.shape[2]
    inner = wide - taps.shape[1]
    xbc = jax.nn.silu(gated_delta.causal_conv(zxbc[..., inner:], taps, bias,
                                              segments))
    x = xbc[..., :inner].reshape(B, S, H, inner // H)
    v = (x.astype(jnp.float32) * delta[..., None]).astype(zxbc.dtype)
    return xbc, v.transpose(0, 2, 1, 3)


def tiles(S: int, inner: int, C: int, H: int, K: int) -> bool:
    """Whether the kernels take the shape: x's columns and B and C's whole
    lane tiles and views of the projection's output, whole heads side by
    side in a lane tile, the sequence whole halos, `TAPS` taps."""
    bc = C - inner
    return (inner % 128 == 0 and bc > 0 and bc % 128 == 0 and inner % bc == 0
            and inner % H == 0 and 128 % (inner // H) == 0 and 128 % H == 0
            and inner // 128 & (inner // 128 - 1) == 0  # the fold's halves
            and S % HALO == 0 and K == TAPS)


def document_marks(segments):
    """`segments` (B, S) -> (B, S, 128) int32: each position's depth into
    its own document, t less the document's first position, along a lane
    tile."""
    B, S = segments.shape
    at = lax.broadcasted_iota(jnp.int32, (B, S), 1)
    first = jnp.pad(segments[:, 1:] != segments[:, :-1], ((0, 0), (1, 0)))
    depth = at - lax.cummax(jnp.where(first, at, 0), axis=1)
    return jnp.broadcast_to(depth[..., None], (B, S, MARK_LANES))


def _seen(moved, depth):
    """`moved` (a chunk moved j rows back, j = 0..K-1) with tap j's rows of
    another document as zeros: `depth` (rows, 128) int32 the rows' depths
    into their documents, or None where a row is one document."""
    if depth is None:
        return moved
    depth = _wide(depth, moved[0].shape[1])
    return [moved[0]] + [_where(depth >= j, m) for j, m in enumerate(moved[1:], 1)]


def _where(kept, t):
    """t where `kept`, zeros elsewhere."""
    return lax.select(kept, t, jnp.zeros_like(t))


def _wide(depth, lanes: int):
    return lax.concatenate([depth] * (lanes // MARK_LANES), 1)


def _pieces(inner: int, bc: int, P: int):
    """The C channels in pieces of at most `PIECE`, x's and then B and C's:
    (the view, the piece's columns in the view, its channels of C, its first
    head or None beyond x)."""
    out = []
    for view, width, base in ((0, inner, 0), (1, bc, inner)):
        piece = max(lanes for lanes in range(128, min(PIECE, width) + 1, 128)
                    if width % lanes == 0)
        out += [(view, slice(lo, lo + piece), slice(base + lo, base + lo + piece),
                 lo // P if view == 0 else None)
                for lo in range(0, width, piece)]
    return out


def _row_step(rows: int) -> int:
    """Rows a turn of the kernels' loops: a turn's fixed cost (0.3 to 0.4 us
    on a v5e, PERF.md section 6, PR 58) is paid once for them all."""
    return max(step for step in (32, ROWS) if rows % step == 0)


_ALONG_LANES = lax.GatherDimensionNumbers(
    offset_dims=(), collapsed_slice_dims=(1,), start_index_map=(1,),
    operand_batching_dims=(0,), start_indices_batching_dims=(0,))


def _tall(t):
    """(rows, lanes) -> (lanes / 128 * rows, 128): the register tiles of 128
    lanes one under another, so that one operation along the lanes of a
    tile is one operation for them all."""
    return lax.concatenate([t[:, lo:lo + 128] for lo in range(0, t.shape[1], 128)], 0)


def _wide_again(t, rows: int):
    """`_tall`'s inverse."""
    return lax.concatenate([t[lo:lo + rows] for lo in range(0, t.shape[0], rows)], 1)


def _a_feature(delta, first: int, P: int, lanes: int):
    """Delta (rows, H) float32 -> (rows, lanes): Delta a feature of the
    `lanes` features from head `first` on, a head's P features side by
    side: one gather along the lanes of the tiles one under another
    (Mosaic's gather stays inside a register tile), Delta's heads side by
    side as often as fill a tile."""
    rows, H = delta.shape
    tiles_ = lanes // 128
    wide = lax.concatenate([delta] * (128 // H), 1) if H < 128 else delta
    shape = (tiles_ * rows, 128)
    head = (first + lax.broadcasted_iota(jnp.int32, shape, 0) // rows * (128 // P)
            + lax.broadcasted_iota(jnp.int32, shape, 1) // P)
    tall = lax.gather(lax.concatenate([wide] * tiles_, 0), head[..., None],
                      _ALONG_LANES, (1, 1),
                      mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)
    return _wide_again(tall, rows)


def _folded(products, P: int):
    """(rows, lanes) float32 of lanes / P segments -> (rows, 128) that holds
    every segment's sum in one lane (`_fold_order` says which)."""
    rows = products.shape[0]
    return _fold(_tall(products), rows, P // 2, pltpu.roll, jnp.where,
                 lambda shape: lax.broadcasted_iota(jnp.int32, shape, 1))


def _fold(tall, rows: int, shift: int, roll, where, lane_of):
    """A step adds each lane its neighbour at half the last distance, and
    the halves of two tiles that then hold whole pairs' sums are put side by
    side in one tile: the tiles (one under another, `_tall`) halve with the
    distance until one is left. A lane sum a segment is log2 P turns a
    tile; this is two a tile and log2 P more."""
    while shift:
        if tall.shape[0] == rows:
            tall = tall + roll(tall, shift, 1)
        else:
            half = tall.shape[0] // 2
            a, b = tall[:half], tall[half:]
            up = (lane_of(a.shape) & shift) != 0  # where a + roll(a) is a pair's
            tall = where(up, a + roll(a, shift, 1), b + roll(b, 128 - shift, 1))
        shift //= 2
    return tall


@functools.lru_cache(maxsize=None)
def _fold_order(tiles_: int, P: int):
    """The lane of `_folded`'s tile that holds segment j's sum, j = 0 .. 128
    / P * tiles_ - 1, by folding tiles that name their segments."""
    import numpy as np

    segments = 128 // P * tiles_
    named = np.zeros((segments, tiles_ * 128), np.float32)
    for j in range(segments):
        named[j, j * P:(j + 1) * P] = 1.0
    tall = np.concatenate([named[:, lo:lo + 128]
                           for lo in range(0, tiles_ * 128, 128)])
    tile = _fold(tall, segments, P // 2, np.roll, np.where,
                 lambda shape: np.broadcast_to(np.arange(128), shape))
    return tuple(int(np.argmax(tile[j] == P)) for j in range(segments))


def _forward_kernel(x_ref, bc_ref, x_before, bc_before, taps_ref, bias_ref,
                    delta_ref, *rest, K: int):
    *marks, xbc_ref, v_ref = rest
    f32 = jnp.float32
    P = v_ref.shape[3]
    rows = xbc_ref.shape[1]
    step = _row_step(rows)
    views, befores = (x_ref, bc_ref), (x_before, bc_before)
    pieces = _pieces(x_ref.shape[2], bc_ref.shape[2], P)
    first = (pl.program_id(1) > 0).astype(f32)  # nothing before the row
    history = tuple(befores[view][0, HALO - TILE:HALO, cols].astype(f32) * first
                    for view, cols, _, _ in pieces)

    def turn(i, carry):
        at = pl.ds(pl.multiple_of(i * step, step), step)
        depth = marks[0][0, at, :] if marks else None
        tails = []
        for (view, cols, chan, head), tail in zip(pieces, carry):
            u = views[view][0, at, cols].astype(f32)
            taps = [taps_ref[i:i + 1, chan] for i in range(K)]
            a = (_taps_times(taps, _seen(_moved(u, tail, K, False), depth))
                 + bias_ref[:, chan])
            y = (a * jax.nn.sigmoid(a)).astype(xbc_ref.dtype)
            xbc_ref[0, at, chan] = y
            if head is not None:
                lanes = cols.stop - cols.start
                v = (y.astype(f32) * _a_feature(delta_ref[0, at, :], head, P, lanes)
                     ).astype(v_ref.dtype)
                for j in range(lanes // P):
                    v_ref[0, head + j, at, :] = v[:, j * P:(j + 1) * P]
            tails.append(u[step - TILE:])
        return tuple(tails)

    lax.fori_loop(0, rows // step, turn, history)


def _backward_kernel(x_ref, bc_ref, x_before, bc_before, taps_ref, bias_ref,
                     delta_ref, dxbc_ref, dv_ref, *rest, K: int):
    *marks, du_ref, ddelta_ref, dtaps_ref, dbias_ref, ahead_ref, depth_ref = rest
    f32 = jnp.float32
    P = dv_ref.shape[3]
    rows = dxbc_ref.shape[1]
    step = _row_step(rows)
    n = rows // step
    views, befores = (x_ref, bc_ref), (x_before, bc_before)
    pieces = _pieces(x_ref.shape[2], bc_ref.shape[2], P)
    # the grid takes a row's blocks last to first: nothing before the first
    first = (pl.program_id(1) < pl.num_programs(1) - 1).astype(f32)

    @pl.when(pl.program_id(1) == 0)
    def _():  # nothing behind the row's end
        ahead_ref[...] = jnp.zeros_like(ahead_ref)
        depth_ref[...] = jnp.zeros_like(depth_ref)
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)
        dbias_ref[...] = jnp.zeros_like(dbias_ref)

    history = [befores[view][0, HALO - TILE:HALO, cols].astype(f32) * first
               for view, cols, _, _ in pieces]

    def up(i, carry):
        future, depth_ahead = carry
        r = n - 1 - i
        at = pl.ds(pl.multiple_of(r * step, step), step)
        prev = pl.ds(pl.multiple_of(jnp.maximum(r - 1, 0) * step, step), step)
        depth = marks[0][0, at, :] if marks else None
        ahead, folds = [], []
        for (view, cols, chan, head), ahead_of, before in zip(pieces, future,
                                                             history):
            lanes = cols.stop - cols.start
            taps = [taps_ref[i:i + 1, chan] for i in range(K)]
            u = views[view][0, at, cols].astype(f32)
            tail = lax.select(
                r > 0, views[view][0, prev, cols].astype(f32)[step - TILE:], before)
            backs = _seen(_moved(u, tail, K, False), depth)
            a = _taps_times(taps, backs) + bias_ref[:, chan]
            sig = jax.nn.sigmoid(a)
            y = a * sig
            dy = dxbc_ref[0, at, chan].astype(f32)
            if head is not None:
                dv = lax.concatenate([dv_ref[0, head + j, at, :]
                                      for j in range(lanes // P)], 1).astype(f32)
                folds.append(dv * y.astype(du_ref.dtype).astype(f32))
                dy = dy + dv * _a_feature(delta_ref[0, at, :], head, P, lanes)
            da = dy * (sig + y * (1.0 - sig))
            if depth is None:
                fronts = _moved(da, ahead_of, K, True)
            else:  # position t + j gave position t a tap where its depth is j
                wide, wide_ahead = (_wide(d, lanes) for d in (depth, depth_ahead))
                fronts = [da] + [
                    _shifted(_split(_where(wide >= j, da)),
                             _where(wide_ahead >= j, ahead_of), j, True)
                    for j in range(1, K)]
            du_ref[0, at, chan] = _taps_times(taps, fronts).astype(du_ref.dtype)
            dbias_ref[0, :, chan] += da
            for k in range(K):
                dtaps_ref[0, k, :, chan] += da * backs[K - 1 - k]
            ahead.append(da[:TILE])
        # d Delta a head, in `_fold_order`
        ddelta_ref[0, at, :] = _folded(lax.concatenate(folds, 1), P)
        return tuple(ahead), None if depth is None else depth[:TILE]

    # da's future, the block's first rows', is the block's before it to read
    future, depth_ahead = lax.fori_loop(
        0, n, up, (tuple(ahead_ref[:, chan] for _, _, chan, _ in pieces),
                   depth_ref[...] if marks else None))
    for (_, _, chan, _), tile in zip(pieces, future):
        ahead_ref[:, chan] = tile
    if marks:
        depth_ref[...] = depth_ahead


def _specs(zxbc, taps, delta, *, passes: tuple, up: bool = False):
    """The grid and the block specs by name. `passes`: how many row blocks
    of C channels in zxbc's type and how many of v's shape a grid step
    holds, for the block rule (Delta's and the marks' rows, a lane tile of
    four bytes each, counted four times). `up`: the grid takes a row's
    blocks last to first."""
    B, S, wide = zxbc.shape
    K, C = taps.shape
    H = delta.shape[2]
    inner = wide - C
    bc, P = C - inner, inner // H
    item = zxbc.dtype.itemsize
    rows = _block_rows(S, passes[0] * C * item + passes[1] * H * 128 * item
                       + 4 * 128 * 4)
    blocks = S // rows
    before, _ = _halo_maps(S, rows)

    def at(s):
        return blocks - 1 - s if up else s

    def view(width, col):  # a view's row block, and the halo that ends there
        return (pl.BlockSpec((1, rows, width), lambda b, s: (b, at(s), col)),
                pl.BlockSpec((1, HALO, width), lambda b, s: (b, before(at(s)), col)))

    (x, x_before), (bc_, bc_before) = view(inner, 1), view(bc, wide // bc - 1)
    return (B, blocks), dict(
        x=x, x_before=x_before, bc=bc_, bc_before=bc_before,
        xbc=view(C, 0)[0], delta=view(H, 0)[0], marks=view(MARK_LANES, 0)[0],
        heads=pl.BlockSpec((1, H, rows, P), lambda b, s: (b, 0, at(s), 0)),
        channel=pl.BlockSpec((K, C), lambda b, s: (0, 0)),
        bias=pl.BlockSpec((1, C), lambda b, s: (0, 0)),
        taps_sums=pl.BlockSpec((1, K, _row_step(rows), C),
                               lambda b, s: (b, 0, 0, 0)),
        bias_sums=pl.BlockSpec((1, _row_step(rows), C), lambda b, s: (b, 0, 0)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _forward(zxbc, taps, bias, delta, marks, *, interpret: bool):
    B, S, wide = zxbc.shape
    K, C = taps.shape
    H = delta.shape[2]
    f32 = jnp.float32
    grid, spec = _specs(zxbc, taps, delta, passes=(2, 1))
    packed = () if marks is None else (marks,)
    return kernel_call(
        functools.partial(_forward_kernel, K=K),
        grid=grid,
        in_specs=[spec["x"], spec["bc"], spec["x_before"], spec["bc_before"],
                  spec["channel"], spec["bias"], spec["delta"],
                  *((spec["marks"],) if packed else ())],
        out_specs=[spec["xbc"], spec["heads"]],
        out_shape=[jax.ShapeDtypeStruct((B, S, C), zxbc.dtype),
                   jax.ShapeDtypeStruct((B, H, S, (wide - C) // H), zxbc.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name="ssm_conv_forward",
    )(zxbc, zxbc, zxbc, zxbc, taps.astype(f32), bias.astype(f32)[None],
      delta, *packed)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _backward(zxbc, taps, bias, delta, marks, dxbc, dv, *, interpret: bool):
    """-> (the cotangent of zxbc's columns from `inner` on (B, S, C), d Delta
    as the kernel folds it, (B, S, 128) float32 with a head's number in the
    lane `_fold_order` names, and the taps' and the bias's as
    (B, K, 8, C) and (B, 8, C) float32 sums, a sublane's share of the
    positions each)."""
    B, S, wide = zxbc.shape
    K, C = taps.shape
    f32 = jnp.float32
    grid, spec = _specs(zxbc, taps, delta, passes=(3, 1), up=True)
    packed = () if marks is None else (marks,)
    return kernel_call(
        functools.partial(_backward_kernel, K=K),
        grid=grid,
        in_specs=[spec["x"], spec["bc"], spec["x_before"], spec["bc_before"],
                  spec["channel"], spec["bias"], spec["delta"], spec["xbc"],
                  spec["heads"], *((spec["marks"],) if packed else ())],
        out_specs=[spec["xbc"], spec["marks"], spec["taps_sums"],
                   spec["bias_sums"]],
        out_shape=[jax.ShapeDtypeStruct((B, S, C), zxbc.dtype),
                   jax.ShapeDtypeStruct((B, S, MARK_LANES), f32),
                   jax.ShapeDtypeStruct((B,) + spec["taps_sums"].block_shape[1:], f32),
                   jax.ShapeDtypeStruct((B,) + spec["bias_sums"].block_shape[1:], f32)],
        # da's and the marks' first rows of the block behind, which the grid
        # took the step before
        scratch_shapes=[pltpu.VMEM((TILE, C), f32),
                        pltpu.VMEM((TILE, MARK_LANES), jnp.int32)],
        # the columns' cotangent in dxbc's place, block for block: a turn
        # reads its rows of dxbc before it writes them, and no other does
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name="ssm_conv_backward",
    )(zxbc, zxbc, zxbc, zxbc, taps.astype(f32), bias.astype(f32)[None], delta,
      dxbc, dv, *packed)


def _count(which: str, path: str, zxbc):
    """At trace time, the rows (B x S) that a run of the pass being built
    convolves and the path it takes, added to
    `kungfu_ssm_conv_rows_total{pass, path}`: a sum over the passes traced,
    not over their runs (docs/telemetry.md)."""
    from kungfu_tpu.telemetry import metrics

    metrics.counter(
        "kungfu_ssm_conv_rows_total",
        "rows a run of each Mamba-2 convolution pass traced so far convolves, "
        "by the path it takes: the Pallas kernel or the plain jnp form",
        ("pass", "path")).labels(which, path).inc(zxbc.shape[0] * zxbc.shape[1])


def _path(zxbc, taps, delta) -> str:
    K, C = taps.shape
    return ("kernel" if tiles(zxbc.shape[1], zxbc.shape[2] - C, C,
                              delta.shape[2], K) else "plain")


@jax.custom_vjp
def ssm_conv(zxbc, taps, bias, delta, segments=None, marks=None):
    """zxbc (B, S, inner + C) = [z | x B C], taps (K, C), bias (C,), delta
    (B, S, H) float32, `segments` (B, S) whole numbers or None, `marks`
    their `document_marks` where the caller has made them -> (xbc =
    silu(conv_K(zxbc[..., inner:]) + bias) as (B, S, C), v = delta x as (B,
    H, S, inner / H)), both in zxbc's type, causal along S and within a
    document, float32 inside. zxbc's cotangent is zero before column
    `inner`."""
    return _fwd(zxbc, taps, bias, delta, segments, marks)[0]


def _marks(segments, marks):
    return (document_marks(segments) if marks is None and segments is not None
            else marks)


def _fwd(zxbc, taps, bias, delta, segments=None, marks=None):
    path = _path(zxbc, taps, delta)
    _count("forward", path, zxbc)
    if path == "kernel":
        out = _on_platform(_forward, zxbc, taps, bias, delta,
                           _marks(segments, marks))
    else:
        out = plain(zxbc, taps, bias, delta, segments)
    return tuple(out), (zxbc, taps, bias, delta, segments, marks)


def _bwd(res, cotangents):
    zxbc, taps, bias, delta, segments, marks = res
    dxbc, dv = cotangents
    path = _path(zxbc, taps, delta)
    _count("backward", path, zxbc)
    if path == "plain":
        grads = jax.vjp(lambda *args: plain(*args, segments),
                        zxbc, taps, bias, delta)[1]((dxbc, dv))
        return (*grads, None, None)
    du, folded, dtaps, dbias = _on_platform(
        _backward, zxbc, taps, bias, delta, _marks(segments, marks), dxbc, dv)
    B, S, H = delta.shape
    inner = zxbc.shape[2] - taps.shape[1]
    ddelta = folded[..., jnp.asarray(_fold_order(inner // 128, inner // H))]
    return (jnp.pad(du, ((0, 0), (0, 0), (inner, 0))),  # zeros for z's columns
            jnp.sum(dtaps, axis=(0, 2)).astype(taps.dtype),
            jnp.sum(dbias, axis=(0, 1)).astype(bias.dtype), ddelta, None, None)


ssm_conv.defvjp(_fwd, _bwd)
