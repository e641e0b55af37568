"""The end of a Mamba-2 mixer in one Pallas kernel each way: `+ D x`, the gate
and the grouped norm, once through HBM forward and once backward.

    y = rms_G((o + D x) * silu(z)) * scale

o (B, H, S, P) is the state-space scan's output in the layout the scan writes
it, x and z are (B, S, H P) (or wider arrays whose first H P columns they are:
the kernels read those columns by their index maps and nothing is sliced
out), D a number a head, the scale a number a feature, and the mean square is
taken over each of G equal groups of a position's H P features, the gate
first (`RMSNormGated` with `norm_before_gate` false, eps inside the root).
Everything between the upcast of o, x, z and the downcast of y is float32.

Two kernels on a grid over (batch, row blocks), Mosaic where the program is
lowered for the TPU and the same kernels interpreted anywhere else
(`gated_delta._on_platform`):

1. `_forward_kernel` reads a row block of o through o's own index map (no
   transposition pass), the same rows of x and z, and writes y (B, S, H P)
   in z's type. A block holds whole rows, so a group's mean square never
   leaves it. Nothing else is written.
2. `_backward_kernel` reads o, x, z and dy once, makes the gate, the sum and
   the inverse root again, and writes do in the scan's layout, dx and dz as
   (B, S, H P). dD and dscale are sums over the positions: each is one
   float32 accumulator of eight sublanes a batch row that stays in VMEM along
   the grid's row-block axis (`arbitrary`; a v5e has one core, so nothing
   that ran side by side is put in sequence) and is added up outside, the
   features of a head too for dD.

Between the passes the op keeps its five inputs and nothing of its own, so
it costs the same kept or run again under a layer's checkpoint. The backward
call writes do in o's place and dz in dy's (`input_output_aliases`): a grid
step reads its block of each before it writes it and no other step does, and
the Nemotron-3-Nano step holds 0.19 GB less for it by `aot_check.py` (XLA
copies first where a caller still reads o or dy afterwards; the mixer does
not).

A row block is the largest divisor of S that whole `CHUNK_ROWS` make up and
whose blocks (o, x, z, y in and out, dy, do, dx, dz in the backward pass;
double-buffered; o's rows padded to a lane tile where P is under 128) stay
under `BLOCK_BYTES` of VMEM: 256 rows forward and 128 backward at 64 heads of
64 features in bfloat16. Inside a block the kernels loop over `CHUNK_ROWS`
rows at a time. One body serves any (H, P, G) with G | H whose pieces tile:
P a divisor or a multiple of 128 lanes, H P / G a whole number of lane
tiles, S a multiple of `CHUNK_ROWS`. Any other shape takes `plain`, the
`jnp` form of the same arithmetic, which is also what the tests compare the
kernels with; which of the two a pass took is counted where it is traced, in
`kungfu_gated_norm_rows_total{pass, path}` (docs/telemetry.md). The two
builders are jitted so that a model's stacks of one shape, and a layer run
again, share one trace and one lowering of each kernel (the op with its
gradient traced for the Granite step's two stacks and the Nemotron-3-Nano
step's four, on a CPU: 0.5 and 0.9 s, 1.3 and 4.8 without; the first step's
tracing on the chip's host 0.5 s over the parent's, 0.75 without).

On the chip (TPU v5e, 8,192 positions of 64 heads of 64 features, bfloat16;
PERF.md, PR 55): 0.48 to 0.50 ms a forward call and 0.89 a backward call in
both cells' traced steps, G 1 and 8 alike. The scan lays o out with its 64
features padded to a lane tile (134 MB where x, z and y are 67 each), so a
forward call moves 335 MB and a backward call 603: 83 % of the HBM peak
both ways. Rows of 8, 16 or 32 a turn, blocks of half or twice the bytes and
an approximate reciprocal in the gate all read the same to 0.02 ms: the bytes
bind, not the vector unit.

Tensor parallelism: the op is handed no mesh and cannot ask for one, so it
does not fall to `plain` under one. The repo's (`models/transformer.
param_pspecs`, the features over `tp`) is the partitioner's, and JAX refuses
a Mosaic call in a program it partitions over several devices
(`NotImplementedError` at lowering: "wrap the call in a shard_map"), this
op's as the scan's before it in the same mixer: on the TPU a Mamba-2 layer
runs in a program of one device, which is all any cell does, until its
kernels' caller stands under a `shard_map`. There the kernel would be right
only where a shard holds whole groups (tp | G); no caller does that and no
test shows it. Interpreted (the CPU tests' tp mesh of two) it is plain
operations and partitions like them.

`models/mixers/mamba2._mamba2_mixer` is the caller, under the scope `ssm_norm`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kungfu_tpu.ops.gated_delta import VMEM_LIMIT, _on_platform
from kungfu_tpu.ops.kernel_call import kernel_call

BLOCK_BYTES = 24 << 20  # of VMEM for a grid step's blocks, double-buffered
CHUNK_ROWS = 16  # of a block at a time in the kernels' loops: a bfloat16 tile's


def plain(o, x, z, d, scale, groups: int, eps: float):
    """The op in `jnp`, for autodiff: o (B, H, S, P), x and z (B, S, >= H P),
    d (H,), scale (H P,) -> y (B, S, H P) in z's type."""
    B, H, S, P = o.shape
    f32 = jnp.float32
    x32, z32 = (t[..., :H * P].astype(f32) for t in (x, z))
    a = (o.transpose(0, 2, 1, 3).astype(f32)
         + d.astype(f32)[:, None] * x32.reshape(B, S, H, P))
    t = (a.reshape(B, S, groups, -1)
         * jax.nn.silu(z32).reshape(B, S, groups, -1))
    t = t * lax.rsqrt(jnp.mean(jnp.square(t), axis=-1, keepdims=True) + eps)
    return (t.reshape(B, S, H * P) * scale.astype(f32)).astype(z.dtype)


def _block_rows(S: int, row_bytes: int) -> int:
    """The largest divisor of S that whole `CHUNK_ROWS` make up and whose
    rows of `row_bytes` in VMEM, double-buffered, stay under `BLOCK_BYTES`;
    `CHUNK_ROWS` where none does."""
    return max((rows for rows in range(CHUNK_ROWS, S + 1, CHUNK_ROWS)
                if S % rows == 0 and 2 * rows * row_bytes <= BLOCK_BYTES),
               default=CHUNK_ROWS)


def tiles(H: int, P: int, S: int, groups: int) -> bool:
    """Whether the kernels take the shape: whole heads side by side make up
    lane tiles, a group is whole lane tiles, the sequence whole chunks of
    rows."""
    return ((128 % P == 0 or P % 128 == 0) and H % groups == 0
            and (H // groups * P) % 128 == 0 and S % CHUNK_ROWS == 0)


def _group(o_ref, row_refs, feature_refs, at, g: int, width: int):
    """Group g's part of the rows `at` in float32: its columns, o's heads
    side by side, the same columns of each (1, rows, H P) ref of `row_refs`
    and of each (1, H P) float32 ref of `feature_refs`."""
    P = o_ref.shape[3]
    cols = slice(g * width, (g + 1) * width)
    heads = range(g * width // P, (g + 1) * width // P)
    o = jnp.concatenate([o_ref[0, h, at, :] for h in heads], axis=-1)
    return (cols, o.astype(jnp.float32),
            *(ref[0, at, cols].astype(jnp.float32) for ref in row_refs),
            *(ref[:, cols] for ref in feature_refs))


def _forward_kernel(o_ref, x_ref, z_ref, d_ref, scale_ref, y_ref, *,
                    groups: int, eps: float):
    width = y_ref.shape[2] // groups

    def turn(i, carry):
        at = pl.ds(pl.multiple_of(i * CHUNK_ROWS, CHUNK_ROWS), CHUNK_ROWS)
        for g in range(groups):
            cols, o, x, z, d, scale = _group(
                o_ref, (x_ref, z_ref), (d_ref, scale_ref), at, g, width)
            t = (o + d * x) * (z * jax.nn.sigmoid(z))
            r = lax.rsqrt(jnp.mean(t * t, axis=1, keepdims=True) + eps)
            y_ref[0, at, cols] = (t * r * scale).astype(y_ref.dtype)
        return carry

    lax.fori_loop(0, y_ref.shape[1] // CHUNK_ROWS, turn, None)


def _sublanes(t):
    """(rows, width) float32 -> (8, width): the rows added up a sublane, no
    reduction across sublanes."""
    return sum(t[i:i + 8] for i in range(0, t.shape[0], 8))


def _backward_kernel(o_ref, x_ref, z_ref, d_ref, scale_ref, dy_ref,
                     do_ref, dx_ref, dz_ref, dd_ref, dscale_ref, *,
                     groups: int, eps: float):
    P = o_ref.shape[3]
    width = dy_ref.shape[2] // groups

    @pl.when(pl.program_id(1) == 0)
    def _():
        dd_ref[...] = jnp.zeros_like(dd_ref)
        dscale_ref[...] = jnp.zeros_like(dscale_ref)

    def turn(i, carry):
        at = pl.ds(pl.multiple_of(i * CHUNK_ROWS, CHUNK_ROWS), CHUNK_ROWS)
        for g in range(groups):
            cols, o, x, z, dy, d, scale = _group(
                o_ref, (x_ref, z_ref, dy_ref), (d_ref, scale_ref), at, g, width)
            sig = jax.nn.sigmoid(z)
            gate = z * sig
            a = o + d * x
            t = a * gate
            r = lax.rsqrt(jnp.mean(t * t, axis=1, keepdims=True) + eps)
            normed = t * r
            dscale_ref[0, :, cols] += _sublanes(dy * normed)
            u = dy * scale
            dt = r * (u - normed * jnp.mean(u * normed, axis=1, keepdims=True))
            da = dt * gate
            dd_ref[0, :, cols] += _sublanes(da * x)
            dx_ref[0, at, cols] = (da * d).astype(dx_ref.dtype)
            dz_ref[0, at, cols] = (dt * a * (sig + gate * (1.0 - sig))
                                   ).astype(dz_ref.dtype)
            for j, h in enumerate(range(g * width // P, (g + 1) * width // P)):
                do_ref[0, h, at, :] = da[:, j * P:(j + 1) * P].astype(do_ref.dtype)
        return carry

    lax.fori_loop(0, dy_ref.shape[1] // CHUNK_ROWS, turn, None)


def _specs(o, x, *, passes: tuple):
    """The grid and the block specs by name. `passes`: how many row blocks
    of o's shape and how many of y's a grid step holds, for the block rule."""
    B, H, S, P = o.shape
    inner = H * P
    o_row = H * -(-P // 128) * 128 * o.dtype.itemsize  # P padded to lane tiles
    rows = _block_rows(S, passes[0] * o_row + passes[1] * inner * x.dtype.itemsize)
    return (B, S // rows), dict(
        heads=pl.BlockSpec((1, H, rows, P), lambda b, s: (b, 0, s, 0)),
        rows=pl.BlockSpec((1, rows, inner), lambda b, s: (b, s, 0)),
        feature=pl.BlockSpec((1, inner), lambda b, s: (0, 0)),
        sums=pl.BlockSpec((1, 8, inner), lambda b, s: (b, 0, 0)))


def _a_feature(d, scale, P: int):
    """D a feature and the scale, (1, H P) float32 rows."""
    f32 = jnp.float32
    return jnp.repeat(d.astype(f32), P)[None], scale.astype(f32)[None]


@functools.partial(jax.jit, static_argnames=("groups", "eps", "interpret"))
def _forward(o, x, z, d, scale, *, groups: int, eps: float, interpret: bool):
    B, H, S, P = o.shape
    grid, spec = _specs(o, x, passes=(1, 3))
    return kernel_call(
        functools.partial(_forward_kernel, groups=groups, eps=eps),
        grid=grid,
        in_specs=[spec["heads"], spec["rows"], spec["rows"], spec["feature"],
                  spec["feature"]],
        out_specs=spec["rows"],
        out_shape=jax.ShapeDtypeStruct((B, S, H * P), z.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name="gated_norm_forward",
    )(o, x, z, *_a_feature(d, scale, P))


@functools.partial(jax.jit, static_argnames=("groups", "eps", "interpret"))
def _backward(o, x, z, d, scale, dy, *, groups: int, eps: float,
              interpret: bool):
    """-> (do, dx, dz, and dD and dscale a feature as (B, 8, H P) float32
    sums, a sublane's share of the positions each)."""
    B, H, S, P = o.shape
    inner = H * P
    grid, spec = _specs(o, x, passes=(2, 5))
    rows = [jax.ShapeDtypeStruct((B, S, inner), t.dtype) for t in (x, z)]
    sums = jax.ShapeDtypeStruct((B, 8, inner), jnp.float32)
    d_feature, scale_feature = _a_feature(d, scale, P)
    return kernel_call(
        functools.partial(_backward_kernel, groups=groups, eps=eps),
        grid=grid,
        in_specs=[spec["heads"], spec["rows"], spec["rows"], spec["feature"],
                  spec["feature"], spec["rows"]],
        out_specs=[spec["heads"], spec["rows"], spec["rows"], spec["sums"],
                   spec["sums"]],
        out_shape=[jax.ShapeDtypeStruct(o.shape, o.dtype), *rows, sums, sums],
        # do in o's place and dz in dy's, block for block: a block is read
        # before it is written and by no other grid step
        input_output_aliases={0: 0, 5: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name="gated_norm_backward",
    )(o, x, z, d_feature, scale_feature, dy)


def _count(which: str, path: str, o):
    """At trace time, the rows (B x S) that a run of the pass being built
    normalises and the path it takes, added to
    `kungfu_gated_norm_rows_total{pass, path}`: a sum over the passes
    traced, not over their runs (docs/telemetry.md)."""
    from kungfu_tpu.telemetry import metrics

    metrics.counter(
        "kungfu_gated_norm_rows_total",
        "rows a run of each gated-norm pass traced so far normalises, by the "
        "path it takes: the Pallas kernel or the plain jnp form",
        ("pass", "path")).labels(which, path).inc(o.shape[0] * o.shape[2])


def _path(o, groups: int) -> str:
    B, H, S, P = o.shape
    return "kernel" if tiles(H, P, S, groups) else "plain"


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def gated_norm(o, x, z, d, scale, groups: int, eps: float):
    """o (B, H, S, P), x and z (B, S, >= H P: the first H P columns are
    read), d (H,), scale (H P,) -> rms_G((o + d x) * silu(z)) * scale as
    (B, S, H P) in z's type, the mean square over each of `groups` equal
    groups of a row's features, float32 inside. The cotangents of x and z
    are zero beyond column H P."""
    return _fwd(o, x, z, d, scale, groups, eps)[0]


def _fwd(o, x, z, d, scale, groups, eps):
    path = _path(o, groups)
    _count("forward", path, o)
    if path == "kernel":
        y = _on_platform(_forward, o, x, z, d, scale, groups=groups, eps=eps)
    else:
        y = plain(o, x, z, d, scale, groups, eps)
    return y, (o, x, z, d, scale)


def _bwd(groups, eps, res, dy):
    o, x, z, d, scale = res
    B, H, S, P = o.shape
    path = _path(o, groups)
    _count("backward", path, o)
    if path == "plain":
        return jax.vjp(lambda *args: plain(*args, groups, eps), *res)[1](dy)
    do, dx, dz, dd, dscale = _on_platform(_backward, o, x, z, d, scale, dy,
                                          groups=groups, eps=eps)

    def wide(dt, t):  # zeros for the columns that were not read
        return jnp.pad(dt, ((0, 0), (0, 0), (0, t.shape[2] - H * P)))

    return (do, wide(dx, x), wide(dz, z),
            jnp.sum(dd.reshape(-1, H, P), axis=(0, 2)).astype(d.dtype),
            jnp.sum(dscale, axis=(0, 1)).astype(scale.dtype))


gated_norm.defvjp(_fwd, _bwd)
