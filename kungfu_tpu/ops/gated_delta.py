"""The gated delta rule in Pallas kernels, forward and backward, and the
causal depthwise convolution that stands before it in a Gated DeltaNet layer
and, with a bias, before the state-space scan of a Mamba-2 layer.

The definition is a recurrence over the sequence with a (dk, dv) state a
head (arXiv:2412.06464, Gated Delta Networks), S_0 = 0:

    S'_t = exp(g_t) S_{t-1}
    u_t  = beta_t (v_t - S'_t^T k_t)
    S_t  = S'_t + k_t u_t^T
    o_t  = S_t^T q_t

A position at a time it is S matrix-vector products in a row; it lives in
the benchmark's reference. Here the sequence is cut into chunks of C = 64
positions. With G the running sum of g inside a chunk, S the state at the
chunk's start and d_ij = exp(G_i - G_j):

    (I + A) U = beta (V - exp(G) K S),   A_ij = beta_i d_ij k_i.k_j  (j < i)
    O  = (exp(G) Q) S + P U,              P_ij = d_ij q_i.k_j         (j <= i)
    S+ = exp(G_C) S + (exp(G_C - G) K)^T U

so all of a chunk is matrix products, and what runs in sequence is the
state from chunk to chunk. Three kernels, Mosaic where the program is
lowered for the TPU and the same kernels interpreted anywhere else:

1. The solve's pass (`_solve`), every chunk at once: XLA makes A (one
   batched product and the decays) and `_unit_lower_inverse` inverts I + A
   by forward substitution in `_substitution_kernel`, float32, a system a
   lane: a step of the substitution is a multiply and a subtract over 512
   systems at once. T = (I + A)^-1 leaves it in q's type, 33 MB a call of
   eight heads at 16,384 positions. (Six doublings of two float32 products
   at the highest precision, the block substitution this replaced, took 3.2
   ms a call as XLA fusions and would take more on the MXU a chunk at a
   time; the substitution takes 0.3. PERF.md, PR 37.)
2. `_forward_kernel`, grid (batch, blocks of heads, blocks of chunks), the
   last axis in sequence with each head's (dk, dv) float32 state in VMEM
   scratch: a grid step reads 512 positions of q, k, v, g, beta and T
   straight from the (B, H, S, d) arrays, loops over its eight chunks
   (`_chunk`: W = T (beta exp(G) K), U = T (beta V) - W S, exp(G) Q, exp(G_C
   - G) K, P, all in VMEM), and writes o and the state each chunk starts
   from. Four heads a grid step run side by side in the loop's body: their
   chains do not depend on each other, and one chain alone waits for the
   MXU (1.22 ms a call with one head a step, 0.64 with four).
3. `_backward_kernel`, the same grid last block to first with dS in
   scratch: each chunk's local quantities are made again from q, k, v, g,
   beta, T and the kept state, and dq, dk, dv, dg, dbeta and T's cotangent
   leave it; what k, g and beta receive through T is the solve's own
   cotangent (d A = -T^T dT T^T and A's derivative, XLA's) and is added
   outside.

The matrix products take their operands in the type q, k, v come in
(bfloat16 in the model, so float32 inputs give a float32 computation) and
accumulate in float32; g and beta are float32, every decay is an
exponential of a difference G_i - G_j <= 0 taken in float32 (nothing is
divided by a decay, so a strong one underflows to 0 and nothing overflows),
and the state is float32 (a bfloat16 decay of 0.99 is 0.988, and a state
that is read 100 chunks later is then off by 16 %). g and beta go through
the kernels as rows of a chunk, (1, C); a column is made of a row, and a row
of a column, through the diagonal's mask and a sum, so nothing is
transposed.

The backward pass keeps the five inputs and the chunk-boundary states (B H
S/C dk dv float32: 0.5 GB a layer of 32 heads at 16,384 positions). No
state a position exists in either pass, and no array of all chunks but T
and its cotangent (0.1 GB a call of eight heads).

`models/transformer.py` runs it as the core of a layer whose `mixer` is
`"gated_delta"`, under the scope `gdn_core`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kungfu_tpu.ops.kernel_call import kernel_call

CHUNK = 64
BLOCK_CHUNKS = 8  # chunks a grid step of the two passes' kernels: 512 positions
BLOCK_HEADS = 4  # heads a grid step: their chains are independent
SOLVE_LANES = 512  # systems a grid step of the solve's kernel
VMEM_LIMIT = 64 << 20  # of the chip's 128 MiB; the default scope is 16

_HIGHEST = lax.Precision.HIGHEST


def _taps_over(padded, taps, S: int, seen=None):
    """sum_i taps_i padded[:, i:i + S] in float32: the K windows are read
    from the one padded array in its own type inside one fused pass. `seen`,
    where given, is a (B, S, 1) mask a tap: a window's positions that the
    tap may read, zero in the others' place."""
    windows = (padded[:, i:i + S].astype(jnp.float32) * taps[i].astype(jnp.float32)
               for i in range(taps.shape[0]))
    if seen is None:
        return sum(windows)
    return sum(jnp.where(mask, window, 0.0)
               for mask, window in zip(seen, windows, strict=True))


def _same_document(segments, K: int, ahead: bool):
    """K masks (B, S, 1), one a window of `_taps_over`: window i of the
    sequence padded with K - 1 positions at its start (at its end, `ahead`)
    is position t - (K - 1) + i (t + i), and a tap reads it where it is of
    position t's own document."""
    if segments is None:
        return None
    S = segments.shape[1]
    padded = jnp.pad(segments, ((0, 0), (0, K - 1) if ahead else (K - 1, 0)),
                     constant_values=-1)
    return [(padded[:, i:i + S] == segments)[..., None] for i in range(K)]


@jax.custom_vjp
def causal_conv(x, taps, bias=None, segments=None):
    """Causal depthwise convolution over the sequence: x (B, S, channels),
    taps (K, channels), bias (channels,) or none; y_t = sum_i taps_i
    x_{t - (K - 1) + i} + bias, zeros before the start. `segments` (B, S)
    whole numbers, where given, say which document a position is of: a tap
    that would read a position of another document reads zero, so a packed
    row is its documents run one at a time. The products, their sum and the
    bias in float32, the result in x's type. The backward pass is written
    out, the same K windows over the cotangent padded at its end, a
    reduction for the taps and one for the bias, and keeps x, the taps, the
    bias and the segments alone: autodiff keeps each of the K windows in
    float32 as the taps' residual (0.5 GB each at 16,384 positions and 8,192
    channels) and pads a float32 cotangent a window."""
    K = taps.shape[0]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = _taps_over(padded, taps, x.shape[1], _same_document(segments, K, False))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def _causal_conv_bwd(res, dy):
    x, taps, bias, segments = res
    K, S = taps.shape[0], x.shape[1]
    # dx_t = sum_i taps_i dy_{t + (K - 1) - i}: the taps in reverse over dy
    # with zeros after its end
    dx = _taps_over(jnp.pad(dy, ((0, 0), (0, K - 1), (0, 0))), taps[::-1], S,
                    _same_document(segments, K, True))
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    dy32 = dy.astype(jnp.float32)
    seen = _same_document(segments, K, False)

    def read_by(i):  # dy_t x_{t - (K - 1) + i} where tap i reads it
        product = dy32 * padded[:, i:i + S].astype(jnp.float32)
        return product if seen is None else jnp.where(seen[i], product, 0.0)

    dtaps = jnp.stack([jnp.sum(read_by(i), axis=(0, 1)) for i in range(K)])
    dbias = None if bias is None else jnp.sum(dy32, axis=(0, 1)).astype(bias.dtype)
    return dx.astype(x.dtype), dtaps.astype(taps.dtype), dbias, None


causal_conv.defvjp(
    lambda x, taps, bias=None, segments=None: (
        causal_conv(x, taps, bias, segments), (x, taps, bias, segments)),
    _causal_conv_bwd)


def _on_platform(kernel, *args, **static):
    """Mosaic where the program is lowered for the TPU, the same kernel
    interpreted anywhere else."""
    return lax.platform_dependent(
        *args, tpu=functools.partial(kernel, interpret=False, **static),
        default=functools.partial(kernel, interpret=True, **static))


def _substitution_kernel(a_ref, t_ref):
    """Forward substitution, a system a lane: a_ref[i, j] and t_ref[i, j]
    are (lanes,) rows that hold entry (i, j) of every system. Column by
    column, row j of T is final once the columns before it are taken out of
    the rows below: T_i -= A_ij T_j for i > j, a multiply and a subtract on
    float32, nothing divided and nothing reordered. T_j ends at its
    diagonal, so the rows' tiles of eight entries beyond j's are left out."""
    C = a_ref.shape[0]
    tile = 8 if C % 8 == 0 else C
    at = lax.broadcasted_iota(jnp.int32, t_ref.shape[1:], 0)

    def identity(i, carry):
        t_ref[i] = (at == i).astype(t_ref.dtype)
        return carry

    lax.fori_loop(0, C, identity, None)
    for first in range(0, C, tile):
        upto = slice(0, first + tile)

        def column(j, carry, upto=upto):
            row_j = t_ref[j, upto, :]

            def below(i, carry):
                t_ref[i, upto, :] = (t_ref[i, upto, :]
                                     - a_ref[i, pl.ds(j, 1), :] * row_j)
                return carry

            return lax.fori_loop(j + 1, C, below, carry)

        lax.fori_loop(first, min(first + tile, C - 1), column, None)


def _substitution(a, *, interpret: bool):
    """(C, C, n) -> (C, C, n): the inverse of I + a[:, :, s] for each s."""
    C, _, n = a.shape
    lanes = SOLVE_LANES if n % SOLVE_LANES == 0 else n
    block = pl.BlockSpec((C, C, lanes), lambda s: (0, 0, s))
    return kernel_call(
        _substitution_kernel, grid=(n // lanes,), in_specs=[block],
        out_specs=block, out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name="gated_delta_solve")(a)


@jax.custom_vjp
def _unit_lower_inverse(a):
    """(I + a)^-1 for strictly lower triangular a (..., C, C), float32, by
    forward substitution in a kernel of its own (`_substitution_kernel`):
    the systems go to the lanes (a transposition each way, XLA's), so a
    step of the substitution is one multiply and subtract over every system
    at once. As stable as forward substitution because it is that: the
    Neumann product (I - a)(I + a^2)(I + a^4)... cancels binomially large
    terms when successive keys are alike. The derivative is that of an
    inverse, two products: d a = -T^T dT T^T."""
    C = a.shape[-1]
    systems = jnp.moveaxis(a.reshape(-1, C, C), 0, -1)
    T = _on_platform(_substitution, systems)
    return jnp.moveaxis(T, -1, 0).reshape(a.shape)


def _unit_lower_inverse_bwd(T, dT):
    Tt = jnp.swapaxes(T, -1, -2)
    return (-jnp.matmul(jnp.matmul(Tt, dT, precision=_HIGHEST), Tt,
                        precision=_HIGHEST),)


_unit_lower_inverse.defvjp(lambda a: (_unit_lower_inverse(a),) * 2,
                           _unit_lower_inverse_bwd)


def _solve(k, g, beta, chunk: int):
    """The pass before the kernels, every chunk at once: T = (I + A)^-1, (B,
    H, S / chunk, chunk, chunk) in k's type, A_ij = beta_i exp(G_i - G_j)
    k_i.k_j below the diagonal. The inverse in float32, rounded after it."""
    B, H, S, dk = k.shape
    kc = k.reshape(B, H, S // chunk, chunk, dk)
    G = jnp.cumsum(g.reshape(B, H, S // chunk, chunk), axis=-1)
    at = jnp.arange(chunk)
    below = at[:, None] > at[None, :]
    decay = jnp.exp(jnp.where(below, G[..., :, None] - G[..., None, :], -jnp.inf))
    kk = jnp.einsum("...id,...jd->...ij", kc, kc,
                    preferred_element_type=jnp.float32)
    A = beta.reshape(G.shape)[..., :, None] * decay * kk  # 0 where decay is
    return _unit_lower_inverse(A).astype(k.dtype)


def _dot(a, b, dims=((1,), (0,))):
    """a @ b with float32 accumulation, the operands as they come; `dims`
    the contracted axis of each."""
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=jnp.float32)


_NT = ((1,), (1,))  # a @ b^T
_TN = ((0,), (0,))  # a^T @ b


def _to_column(row, eye):
    """(1, C) -> (C, 1) through the diagonal's mask: no transposition."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _to_row(column, eye):
    return jnp.sum(jnp.where(eye, column, 0.0), axis=0, keepdims=True)


def _chunk(q, k, v, g, beta, T, S):
    """One chunk in VMEM: q, k (C, dk), v (C, dv) and T (C, C) in their
    type, g and beta (1, C) float32 as rows, S (dk, dv) float32 the state at
    its start -> what both passes need of it. Products take their operands
    in q's type and accumulate in float32; every decay is an exponential of
    a difference of running sums, in float32."""
    dt, f32 = q.dtype, jnp.float32
    C = q.shape[0]
    at_row = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    at_col = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    lower, eye = at_col <= at_row, at_col == at_row
    G = jnp.sum(jnp.where(lower, g, 0.0), axis=1, keepdims=True)  # (C, 1)
    G_end = jnp.sum(g, axis=1, keepdims=True)  # (1, 1)
    decay = jnp.exp(jnp.where(lower, G - _to_row(G, eye), -jnp.inf))
    b = _to_column(beta, eye)
    eG, to_end, a = jnp.exp(G), jnp.exp(G_end - G), jnp.exp(G_end)
    q32, k32, v32 = q.astype(f32), k.astype(f32), v.astype(f32)
    bk, bv = (b * eG * k32).astype(dt), (b * v32).astype(dt)
    W = _dot(T, bk).astype(dt)
    Qg, Kd = (q32 * eG).astype(dt), (k32 * to_end).astype(dt)
    qk = _dot(q, k, _NT)
    P = (decay * qk).astype(dt)
    Sd = S.astype(dt)
    U = (_dot(T, bv) - _dot(W, Sd)).astype(dt)
    return dict(lower=lower, eye=eye, decay=decay, b=b, eG=eG, to_end=to_end,
                a=a, q32=q32, k32=k32, v32=v32, bk=bk, bv=bv, W=W, Qg=Qg,
                Kd=Kd, qk=qk, P=P, S=S, Sd=Sd, U=U)


def _rows(c, chunk: int):
    return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, T_ref, o_ref,
                    states_ref, S_scr, *, chunk: int):
    """A block of chunks of some heads, first to last; each head's state in
    `S_scr` from one grid step to the next along the sequence, and in
    `states_ref` as each chunk starts from it, for the backward pass."""

    @pl.when(pl.program_id(2) == 0)
    def _():
        S_scr[...] = jnp.zeros_like(S_scr)

    def one(c, carry):
        rows, row = _rows(c, chunk), pl.ds(c, 1)
        for h in range(S_scr.shape[0]):  # independent chains, side by side
            S = S_scr[h]
            states_ref[0, h, c] = S
            x = _chunk(q_ref[0, h, rows, :], k_ref[0, h, rows, :],
                       v_ref[0, h, rows, :], g_ref[0, h, row, :],
                       beta_ref[0, h, row, :], T_ref[0, h, c], S)
            o_ref[0, h, rows, :] = (_dot(x["Qg"], x["Sd"])
                                    + _dot(x["P"], x["U"])).astype(o_ref.dtype)
            S_scr[h] = x["a"] * x["S"] + _dot(x["Kd"], x["U"], _TN)
        return carry

    lax.fori_loop(0, g_ref.shape[2], one, None)


def _backward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, T_ref, states_ref,
                     do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dT_ref,
                     dS_scr, *, chunk: int):
    """The same block last chunk to first, the grid's blocks last to first
    (the index maps), the state's cotangent in `dS_scr`. Each chunk's local
    quantities are made again from the inputs, T and the kept state; what T
    receives goes out in float32 for the solve's own cotangent."""
    blocks = g_ref.shape[2]

    @pl.when(pl.program_id(2) == 0)
    def _():
        dS_scr[...] = jnp.zeros_like(dS_scr)

    def lanes(t):
        return jnp.sum(t, axis=1, keepdims=True)

    def one(i, carry):
        c = blocks - 1 - i
        rows, row = _rows(c, chunk), pl.ds(c, 1)
        for h in range(dS_scr.shape[0]):  # independent chains, side by side
            q, k, T = q_ref[0, h, rows, :], k_ref[0, h, rows, :], T_ref[0, h, c]
            x = _chunk(q, k, v_ref[0, h, rows, :], g_ref[0, h, row, :],
                       beta_ref[0, h, row, :], T, states_ref[0, h, c])
            dt = q.dtype
            dO, dS = do_ref[0, h, rows, :].astype(dt), dS_scr[h]
            dSd, Sd, U, eye = dS.astype(dt), x["Sd"], x["U"], x["eye"]
            b, eG, to_end, a = x["b"], x["eG"], x["to_end"], x["a"]
            q32, k32, v32 = x["q32"], x["k32"], x["v32"]
            # the state's chain: U's cotangent, then the state's own
            dU = (_dot(x["P"], dO, _TN) + _dot(x["Kd"], dSd)).astype(dt)
            dS_scr[h] = (a * dS + _dot(x["Qg"], dO, _TN)
                         - _dot(x["W"], dU, _TN))
            da = jnp.sum(lanes(x["S"] * dS), axis=0, keepdims=True)
            # the products' other operands
            dKd, dQg = _dot(U, dSd, _NT), _dot(dO, Sd, _NT)
            dP = _dot(dO, U, _NT)
            dW = (-_dot(dU, Sd, _NT)).astype(dt)
            dT_ref[0, h, c] = _dot(dW, x["bk"], _NT) + _dot(dU, x["bv"], _NT)
            dbk, dbv = _dot(T, dW, _TN), _dot(T, dU, _TN)
            dqk = (dP * x["decay"]).astype(dt)
            dq_ref[0, h, rows, :] = (eG * dQg + _dot(dqk, k)).astype(dq_ref.dtype)
            dk_ref[0, h, rows, :] = (b * eG * dbk + to_end * dKd
                                     + _dot(dqk, q, _TN)).astype(dk_ref.dtype)
            dv_ref[0, h, rows, :] = (b * dbv).astype(dv_ref.dtype)
            dbk_k = lanes(dbk * k32)
            dbeta_ref[0, h, row, :] = _to_row(eG * dbk_k + lanes(dbv * v32), eye)
            # the decays: every one an exponential of running sums of g
            d_to_end = lanes(dKd * k32) * to_end
            d_log = dP * x["qk"] * x["decay"]  # of exp(G_i - G_j), j <= i
            dG = (lanes(d_log) - _to_column(jnp.sum(d_log, axis=0, keepdims=True), eye)
                  + (b * dbk_k + lanes(dQg * q32)) * eG - d_to_end)
            dG_end = da * a + jnp.sum(d_to_end, axis=0, keepdims=True)
            dg_ref[0, h, row, :] = dG_end + jnp.sum(
                jnp.where(x["lower"], dG, 0.0), axis=0, keepdims=True)
        return carry

    lax.fori_loop(0, blocks, one, None)


def _block_chunks(N: int) -> int:
    """Chunks a grid step: the most up to `BLOCK_CHUNKS` that divide N in
    whole sublane tiles of g's rows, or all of them."""
    for n in range(min(N, BLOCK_CHUNKS), 7, -1):
        if N % n == 0 and n % 8 == 0:
            return n
    return N


def _specs(B, H, S, dk, dv, chunk, back: bool, heads_most: int = BLOCK_HEADS):
    """Block specs by name for a grid (B, H, blocks of chunks), the blocks
    in reverse for the backward pass; `heads_most` heads a grid step
    (`ops.kda` takes the same grid with fewer)."""
    N = S // chunk
    n = _block_chunks(N)
    last = N // n - 1
    heads = max(h for h in range(1, heads_most + 1) if H % h == 0)

    def at(*tail):
        if back:
            return lambda b, h, s: (b, h, last - s) + tail
        return lambda b, h, s: (b, h, s) + tail

    return (B, H // heads, N // n), dict(
        qk=pl.BlockSpec((1, heads, n * chunk, dk), at(0)),
        v=pl.BlockSpec((1, heads, n * chunk, dv), at(0)),
        row=pl.BlockSpec((1, heads, n, chunk), at(0)),
        T=pl.BlockSpec((1, heads, n, chunk, chunk), at(0, 0)),
        state=pl.BlockSpec((1, heads, n, dk, dv), at(0, 0)),
        scratch=pltpu.VMEM((heads, dk, dv), jnp.float32))


_PARAMS = dict(compiler_params=pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=VMEM_LIMIT))


def _forward(q, k, v, g, beta, T, *, chunk: int, interpret: bool):
    """-> (o, the state at each chunk's start (B, H, S / chunk, dk, dv)
    float32)."""
    B, H, S, dk = q.shape
    dv, N = v.shape[-1], S // chunk
    grid, spec = _specs(B, H, S, dk, dv, chunk, back=False)
    rows = (B, H, N, chunk)
    return kernel_call(
        functools.partial(_forward_kernel, chunk=chunk),
        grid=grid,
        in_specs=[spec["qk"], spec["qk"], spec["v"], spec["row"], spec["row"],
                  spec["T"]],
        out_specs=[spec["v"], spec["state"]],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((B, H, N, dk, dv), jnp.float32)],
        scratch_shapes=[spec["scratch"]],
        interpret=interpret, name="gated_delta_forward", **_PARAMS,
    )(q, k, v, g.reshape(rows), beta.reshape(rows), T)


def _backward(q, k, v, g, beta, T, states, do, *, chunk: int, interpret: bool):
    """-> (dq, dk, dv, dg, dbeta, dT): dk, dg and dbeta without what they
    receive through T, and T's cotangent in float32."""
    B, H, S, dk = q.shape
    dv, N = v.shape[-1], S // chunk
    grid, spec = _specs(B, H, S, dk, dv, chunk, back=True)
    rows = (B, H, N, chunk)
    shapes = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)]
    shapes += [jax.ShapeDtypeStruct(rows, jnp.float32)] * 2
    shapes += [jax.ShapeDtypeStruct(T.shape, jnp.float32)]
    *d, dg, dbeta, dT = kernel_call(
        functools.partial(_backward_kernel, chunk=chunk),
        grid=grid,
        in_specs=[spec["qk"], spec["qk"], spec["v"], spec["row"], spec["row"],
                  spec["T"], spec["state"], spec["v"]],
        out_specs=[spec["qk"], spec["qk"], spec["v"], spec["row"], spec["row"],
                   spec["T"]],
        out_shape=shapes,
        scratch_shapes=[spec["scratch"]],
        interpret=interpret, name="gated_delta_backward", **_PARAMS,
    )(q, k, v, g.reshape(rows), beta.reshape(rows), T, states, do)
    return (*d, dg.reshape(g.shape), dbeta.reshape(beta.shape), dT)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def gated_delta_rule(q, k, v, g, beta, chunk: int = CHUNK):
    """q, k (B, H, S, dk), v (B, H, S, dv), g (log decay, <= 0) and beta (B,
    H, S) float32 -> o (B, H, S, dv) in v's type: the recurrence of the
    module's head, S_0 = 0, in its chunkwise form. q and k come normalised
    and scaled as the layer wants them. `chunk` is a power of two that
    divides S, or this raises."""
    return _fwd(q, k, v, g, beta, chunk)[0]


def _fwd(q, k, v, g, beta, chunk):
    """-> (o, what the backward pass keeps: the inputs and the states at the
    chunks' starts)."""
    S = q.shape[2]
    if chunk & (chunk - 1) or S % chunk:
        raise ValueError(f"gated_delta_rule: the sequence length {S} is no "
                         f"multiple of the chunk {chunk}, a power of two")
    T = _solve(k, g, beta, chunk)
    o, states = _on_platform(_forward, q, k, v, g, beta, T, chunk=chunk)
    return o, (q, k, v, g, beta, states)


def _bwd(chunk, res, do):
    q, k, v, g, beta, states = res
    T, back_solve = jax.vjp(lambda k, g, beta: _solve(k, g, beta, chunk),
                            k, g, beta)
    dq, dk, dv, dg, dbeta, dT = _on_platform(
        _backward, q, k, v, g, beta, T, states, do, chunk=chunk)
    dk_T, dg_T, dbeta_T = back_solve(dT.astype(T.dtype))
    return dq, dk + dk_T, dv, dg + dg_T, dbeta + dbeta_T


gated_delta_rule.defvjp(_fwd, _bwd)
