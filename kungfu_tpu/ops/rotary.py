"""Pallas rotary pass: cos * t + sin * P t over every head, once through HBM.

P swaps the two halves of a head's rotated features; its sign rides in the
sine table the caller passes, so one kernel is the rotation (sine negative
on the first half) and its transpose on a cotangent (negative on the
second). Per (batch, row block, head block) grid program a block of t is
read once in its own dtype, each head is upcast to float32 in registers,
swapped by a lane rotation of `half` (one way on the first half, the other
way on the second, a select over a lane iota between the two; one rotation
where the whole head is rotated), multiplied and added against the row
block's (rows, hd) float32 cos and sin, and written once in t's dtype. The
tables stay in VMEM across the head blocks of a row block. Inside a block
the kernel loops over `CHUNK_ROWS` rows at a time: as fast as the block
unrolled whole (1.08 ms a sliding Laguna layer, forward and backward, q and
k, against 0.82 of bytes, on the chip; PERF.md, PR 35) and a quarter of its
compile time (0.05 against 0.19 to 0.27 s a kernel, the v5e compiler here).

The kernel also moves t between the two layouts at the attention core's
doors, by its index maps and no copy: `into_heads` reads (B, S, H * hd), a
projection's own output, and writes (B, H, S, hd), what the flash kernels
take; the transpose pass reads (B, H, S, hd) and writes (B, S, H * hd). XLA
writes those transpositions as passes of their own beside a custom call
(10.7 ms of the Laguna cell's `window_core_ms`, PERF.md, PR 33).

Mosaic unless `interpret=True`. `models/blocks._rope` is the caller.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kungfu_tpu.ops.kernel_call import kernel_call

BLOCK_ROWS = 512
BLOCK_BYTES = 1 << 20  # of t a block: in and out, double-buffered, 4 MB of VMEM
CHUNK_ROWS = 64  # of a block at a time in the kernel's loop


def _block_rows(S: int) -> int:
    """The largest divisor of S up to `BLOCK_ROWS` that whole sublane tiles
    make up, or all of S."""
    for rows in range(min(S, BLOCK_ROWS), 7, -1):
        if S % rows == 0 and rows % 8 == 0:
            return rows
    return S


def _block_heads(H: int, hd: int, row_bytes: int) -> int:
    """The most heads a block may hold under `BLOCK_BYTES` whose features
    are whole lane tiles side by side, or all of H."""
    for heads in range(H, 0, -1):
        if (H % heads == 0 and (heads * hd) % 128 == 0
                and heads * row_bytes <= BLOCK_BYTES):
            return heads
    return H


def _kernel(t_ref, cos_ref, sin_ref, o_ref, *, heads, hd, half, into_heads):
    rows = cos_ref.shape[0]
    chunk = CHUNK_ROWS if rows % CHUNK_ROWS == 0 else rows

    def turn(i, carry):
        at = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
        cos, sin = cos_ref[at, :], sin_ref[at, :]  # (chunk, hd) float32
        for h in range(heads):
            across = (0, at, slice(h * hd, (h + 1) * hd))
            apart = (0, h, at, slice(None))
            t = t_ref[across if into_heads else apart].astype(jnp.float32)
            swapped = pltpu.roll(t, half, 1)
            if 2 * half < hd:
                first = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1) < half
                swapped = jnp.where(first, pltpu.roll(t, hd - half, 1), swapped)
            o_ref[apart if into_heads else across] = (
                t * cos + swapped * sin).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, rows // chunk, turn, None)


def rotate(t, cos, sin, *, half: int, into_heads: bool, interpret: bool = False):
    """t (B, S, H * hd) -> (B, H, S, hd) (`into_heads`), or t (B, H, S, hd)
    -> (B, S, H * hd): cos * t + sin * P t a head, P t the features `half`
    further on for the first `half` of a head and `half` back for the next
    `half` (what lies beyond them meets sin 0). cos, sin (S, hd) float32."""
    S, hd = cos.shape
    if into_heads:
        B, H = t.shape[0], t.shape[2] // hd
    else:
        B, H = t.shape[:2]
    rows = _block_rows(S)
    heads = _block_heads(H, hd, rows * hd * t.dtype.itemsize)
    across = pl.BlockSpec((1, rows, heads * hd), lambda b, s, h: (b, s, h))
    apart = pl.BlockSpec((1, heads, rows, hd), lambda b, s, h: (b, h, s, 0))
    table = pl.BlockSpec((rows, hd), lambda b, s, h: (s, 0))
    return kernel_call(
        functools.partial(_kernel, heads=heads, hd=hd, half=half,
                          into_heads=into_heads),
        grid=(B, S // rows, H // heads),  # heads last: the tables stay
        in_specs=[across if into_heads else apart, table, table],
        out_specs=apart if into_heads else across,
        out_shape=jax.ShapeDtypeStruct(
            (B, H, S, hd) if into_heads else (B, S, H * hd), t.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="rotary",
    )(t, cos, sin)
