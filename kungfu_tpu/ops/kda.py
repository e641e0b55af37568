"""The delta rule whose decay is a number a key feature (Kimi Delta Attention,
arXiv:2510.26692) in Pallas kernels, forward and backward.

The recurrence of `ops.gated_delta` with a decay a feature of the key in the
place of one a head: g_t is a (dk,) vector of log decays (<= 0), and a head's
(dk, dv) state is decayed a row at a time, S_0 = 0:

    S'_t = Diag(exp(g_t)) S_{t-1}
    u_t  = beta_t (v_t - S'_t^T k_t)
    S_t  = S'_t + k_t u_t^T
    o_t  = S_t^T q_t

A position at a time it lives in the benchmark's reference. Here the sequence
is cut into chunks of C = 64 positions. With G the running sum of g inside a
chunk, (C, dk), S the state at the chunk's start and E_ijc = exp(G_ic - G_jc):

    (I + A) U = beta (V - (exp(G) K) S),   A_ij = beta_i sum_c k_ic E_ijc k_jc  (j < i)
    O  = (exp(G) Q) S + P U,                P_ij = sum_c q_ic E_ijc k_jc         (j <= i)
    S+ = Diag(exp(G_C)) S + (exp(G_C - G) K)^T U

The decay stands inside the products of A and P, and exp(G_i) and exp(-G_j)
may not be formed apart: a feature that decays by e^-20 a position overflows
the second within five positions. So the pairs of a chunk are taken in
sub-blocks of `SUB` = 16 positions (`_pairs`). A sub-block of keys against
the rows below it goes through the sub-block's last row r as a reference,
(X exp(G - r)) (K_J exp(r - G_J))^T, both exponents <= 0 by G's falling, one
matrix product a strip; the pairs inside a sub-block are taken a distance d =
i - j at a time, the keys and their G turned d rows down (`pltpu.roll`), the
difference G_i - G_{i-d} <= 0 exponentiated feature by feature and summed
over the features: sixteen passes over a (C, dk) tile, nothing of a pair's
size by the features ever laid out. Nothing is divided by a decay, a strong
one underflows to 0 and nothing overflows.

Four kernels on `ops.gated_delta`'s grid (batch, blocks of heads, blocks of
chunks), Mosaic where the program is lowered for the TPU and the same kernels
interpreted anywhere else. The running sums live in them: a chunk's (C, dk)
tile added to itself log2 C times, turned down its rows (`_sums`), float32.

1. `_pairs_kernel`, every chunk on its own, takes g: it makes G, the
   running sum of g inside the chunk, A without beta in float32 and P in
   q's type from it, and writes G out for the three kernels below, which
   read it as it is. XLA multiplies beta in and
   `gated_delta._unit_lower_inverse` (the delta rule's own substitution
   kernel) makes T = (I + A)^-1.
2. `_forward_kernel`, the chunks in sequence with each head's float32 state
   in VMEM scratch, as the gated delta rule's: W = T (beta exp(G) K), U = T
   (beta V) - W S, O and the next state, the state's rows decayed by
   exp(G_C).
3. `_backward_kernel`, last block to first with dS in scratch: dq, dk, dv,
   dG, dbeta of what the chunk's products read, and the cotangents of T and
   P in float32.
4. `_pairs_back_kernel`: what q, k and G receive through A and P, the same
   strips and distances transposed. It takes the third kernel's dG, adds
   its own and sums the two from each position to its chunk's end (G's
   sum transposed): what it writes is dg, which the op returns as it is.

q, k, v come in the model's type (bfloat16: the matrix products take them so
and accumulate in float32; float32 inputs give a float32 computation); g and
beta are float32, every decay is an exponential of a difference of running
sums taken in float32, and the state is float32. Between the passes the op
keeps its five inputs and the states at the chunks' starts (B H S/C dk dv
float32): G is not kept, the backward pass's pairs are made again and G
with them.

`models/mixers/kda.py` runs it as the core of a layer whose `mixer` is
`"kda"`, under the scope `kda_core`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kungfu_tpu.ops.gated_delta import (_NT, _PARAMS, _TN, VMEM_LIMIT, _dot,
                                        _on_platform, _rows, _to_column,
                                        _to_row, _unit_lower_inverse)
from kungfu_tpu.ops.gated_delta import _specs as _gd_specs
from kungfu_tpu.ops.kernel_call import kernel_call

CHUNK = 64
SUB = 16  # positions a sub-block of a chunk's pairs
BLOCK_HEADS = 2  # heads a grid step of the two passes' kernels


def _eye(n: int):
    return (lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _sub(C: int) -> int:
    return SUB if C % SUB == 0 else C


def _strip(G, J: int, sub: int):
    """Sub-block J of a chunk's keys against the rows below it, through the
    sub-block's last row as the reference -> (that row's number, exp(G -
    ref) for the rows below (1 where the difference would be positive: the
    rows at or above, which `keep` leaves out), exp(ref - G) on the
    sub-block's rows and 0 elsewhere, the block's place in (C, C))."""
    C = G.shape[0]
    last = (J + 1) * sub - 1
    ref = G[last:last + 1, :]
    at = lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    below = jnp.exp(jnp.minimum(G - ref, 0.0))
    inside = (at >= J * sub) & (at <= last)
    up = jnp.where(inside, jnp.exp(jnp.minimum(ref - G, 0.0)), 0.0)
    row = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    keep = (row > last) & (col >= J * sub) & (col <= last)
    return last, below, up, keep


def _distance(G, k32, d: int, sub: int):
    """The pairs d apart inside a sub-block -> (the keys d rows up, laid
    beside their queries; E = exp(G_i - G_{i-d}) where i - d is of i's own
    sub-block and 0 elsewhere; the pairs' place in (C, C))."""
    C = G.shape[0]
    at = lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    kd = pltpu.roll(k32, d, 0) if d else k32
    Gd = pltpu.roll(G, d, 0) if d else G
    E = jnp.where(at % sub >= d, jnp.exp(jnp.minimum(G - Gd, 0.0)), 0.0)
    on = (lax.broadcasted_iota(jnp.int32, (C, C), 0)
          - lax.broadcasted_iota(jnp.int32, (C, C), 1)) == d
    return kd, E, on


def _lanes(t):
    return jnp.sum(t, axis=1, keepdims=True)


def _sums(x, to_end: bool = False):
    """The running sum down the rows of one chunk's (C, dk) float32 tile,
    each row with the rows above it (`to_end`: with the rows below it, the
    transpose), C a power of two: log2 C turns of the tile added to itself,
    float32 as `jnp.cumsum`'s."""
    C = x.shape[0]
    at = lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    step = 1
    while step < C:  # a row takes the sum `step` rows up (down) where one is
        has, turn = (at < C - step, C - step) if to_end else (at >= step, step)
        x = x + jnp.where(has, pltpu.roll(x, turn, 0), 0.0)
        step *= 2
    return x


def _pairs(q, k, G):
    """One chunk: q, k (C, dk) in their type, G (C, dk) float32 the running
    sum of the log decays -> (A without beta, strictly below the diagonal;
    P, the diagonal with it), (C, C) float32."""
    dt, f32 = q.dtype, jnp.float32
    C = q.shape[0]
    sub = _sub(C)
    q32, k32 = q.astype(f32), k.astype(f32)
    A = jnp.zeros((C, C), f32)
    P = jnp.zeros((C, C), f32)
    for J in range(C // sub - 1):
        _, below, up, keep = _strip(G, J, sub)
        kj = (k32 * up).astype(dt)
        P = P + jnp.where(keep, _dot((q32 * below).astype(dt), kj, _NT), 0.0)
        A = A + jnp.where(keep, _dot((k32 * below).astype(dt), kj, _NT), 0.0)
    for d in range(sub):
        kd, E, on = _distance(G, k32, d, sub)
        w = E * kd
        P = P + jnp.where(on, _lanes(q32 * w), 0.0)
        if d:
            A = A + jnp.where(on, _lanes(k32 * w), 0.0)
    return A, P


def _pairs_back(q, k, G, dA, dP):
    """`_pairs` transposed: dA, dP (C, C) float32 -> (dq, dk, dG), (C, dk)
    float32."""
    dt, f32 = q.dtype, jnp.float32
    C = q.shape[0]
    sub = _sub(C)
    q32, k32 = q.astype(f32), k.astype(f32)
    at = lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    dq = jnp.zeros(G.shape, f32)
    dk = jnp.zeros(G.shape, f32)
    dG = jnp.zeros(G.shape, f32)
    for J in range(C // sub - 1):
        last, below, up, keep = _strip(G, J, sub)
        qs, ks, kj = ((q32 * below).astype(dt), (k32 * below).astype(dt),
                      (k32 * up).astype(dt))
        dPj = jnp.where(keep, dP, 0.0).astype(dt)
        dAj = jnp.where(keep, dA, 0.0).astype(dt)
        dqs, dks = _dot(dPj, kj), _dot(dAj, kj)
        dkj = _dot(dPj, qs, _TN) + _dot(dAj, ks, _TN)
        dq = dq + dqs * below
        dk = dk + dks * below + dkj * up
        d_below = (dqs * q32 + dks * k32) * below  # of G - ref, the rows below
        d_up = dkj * k32 * up  # of ref - G, the sub-block's rows
        d_ref = jnp.sum(d_up - d_below, axis=0, keepdims=True)
        dG = dG + d_below - d_up + jnp.where(at == last, d_ref, 0.0)
    for d in range(sub):
        kd, E, on = _distance(G, k32, d, sub)
        dp = _lanes(jnp.where(on, dP, 0.0))
        coefficient = dp * q32  # of w = E kd
        dq = dq + dp * (E * kd)
        if d:
            da = _lanes(jnp.where(on, dA, 0.0))
            coefficient = coefficient + da * k32
            dk = dk + da * (E * kd)
        to_key = coefficient * E  # the cotangent of the keys d rows up
        of_log = to_key * kd  # of G_i - G_{i-d}
        if d:
            dk = dk + pltpu.roll(to_key, C - d, 0)
            dG = dG + of_log - pltpu.roll(of_log, C - d, 0)
        else:  # a position against itself: E is 1 whatever G is
            dk = dk + to_key
    return dq, dk, dG


def _pairs_kernel(q_ref, k_ref, g_ref, A_ref, P_ref, G_ref, *, chunk: int):
    """A block of chunks of some heads, every chunk on its own: G of the
    chunk's log decays first, for its pairs and for the kernels after it."""
    heads, blocks = A_ref.shape[1], A_ref.shape[2]

    def one(i, carry):
        h, c = i // blocks, i % blocks
        rows = _rows(c, chunk)
        G = _sums(g_ref[0, h, rows, :].astype(jnp.float32))
        A, P = _pairs(q_ref[0, h, rows, :], k_ref[0, h, rows, :], G)
        A_ref[0, h, c] = A
        P_ref[0, h, c] = P.astype(P_ref.dtype)
        G_ref[0, h, rows, :] = G
        return carry

    lax.fori_loop(0, heads * blocks, one, None)


def _pairs_back_kernel(q_ref, k_ref, G_ref, dA_ref, dP_ref, dG_ref, dq_ref,
                       dk_ref, dg_ref, *, chunk: int):
    """`dG_ref` is what G receives from the chunk's other products
    (`_backward_kernel`'s); with the pairs' own it is summed from each
    position to its chunk's end, G being the running sum of g: g's whole
    cotangent."""
    heads, blocks = dA_ref.shape[1], dA_ref.shape[2]

    def one(i, carry):
        h, c = i // blocks, i % blocks
        rows = _rows(c, chunk)
        dq, dk, dG = _pairs_back(q_ref[0, h, rows, :], k_ref[0, h, rows, :],
                                 G_ref[0, h, rows, :], dA_ref[0, h, c],
                                 dP_ref[0, h, c])
        dq_ref[0, h, rows, :] = dq.astype(dq_ref.dtype)
        dk_ref[0, h, rows, :] = dk.astype(dk_ref.dtype)
        dg_ref[0, h, rows, :] = _sums(dG_ref[0, h, rows, :] + dG, to_end=True)
        return carry

    lax.fori_loop(0, heads * blocks, one, None)


def _chunk(q, k, v, G, beta, T, S):
    """One chunk in VMEM: q, k (C, dk), v (C, dv), T (C, C) in their
    type, G (C, dk) float32, beta (1, C) float32 as a row, S (dk, dv) float32
    the state at its start -> what both passes need of it."""
    dt, f32 = q.dtype, jnp.float32
    C, dk = G.shape
    b = _to_column(beta, _eye(C))
    G_end = G[C - 1:C, :]
    eG, to_end, a_row = jnp.exp(G), jnp.exp(G_end - G), jnp.exp(G_end)
    a = _to_column(a_row, _eye(dk))  # (dk, 1): the state's rows decayed
    q32, k32, v32 = q.astype(f32), k.astype(f32), v.astype(f32)
    bk, bv = (b * eG * k32).astype(dt), (b * v32).astype(dt)
    W = _dot(T, bk).astype(dt)
    Qg, Kd = (q32 * eG).astype(dt), (k32 * to_end).astype(dt)
    Sd = S.astype(dt)
    U = (_dot(T, bv) - _dot(W, Sd)).astype(dt)
    return dict(b=b, eG=eG, to_end=to_end, a=a, a_row=a_row, q32=q32, k32=k32,
                v32=v32, bk=bk, bv=bv, W=W, Qg=Qg, Kd=Kd, S=S, Sd=Sd, U=U)


def _forward_kernel(q_ref, k_ref, v_ref, G_ref, beta_ref, T_ref, P_ref, o_ref,
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
                       v_ref[0, h, rows, :], G_ref[0, h, rows, :],
                       beta_ref[0, h, row, :], T_ref[0, h, c], S)
            o_ref[0, h, rows, :] = (
                _dot(x["Qg"], x["Sd"]) + _dot(P_ref[0, h, c], x["U"])
            ).astype(o_ref.dtype)
            S_scr[h] = x["a"] * S + _dot(x["Kd"], x["U"], _TN)
        return carry

    lax.fori_loop(0, beta_ref.shape[2], one, None)


def _backward_kernel(q_ref, k_ref, v_ref, G_ref, beta_ref, T_ref, P_ref,
                     states_ref, do_ref, dq_ref, dk_ref, dv_ref, dG_ref,
                     dbeta_ref, dT_ref, dP_ref, dS_scr, *, chunk: int):
    """The same block last chunk to first, the grid's blocks last to first
    (the index maps), the state's cotangent in `dS_scr`. Each chunk's local
    quantities are made again from the inputs, T, P and the kept state; what
    T and P receive goes out in float32 for the pairs' own backward pass."""
    blocks = beta_ref.shape[2]

    @pl.when(pl.program_id(2) == 0)
    def _():
        dS_scr[...] = jnp.zeros_like(dS_scr)

    def one(i, carry):
        c = blocks - 1 - i
        rows, row = _rows(c, chunk), pl.ds(c, 1)
        for h in range(dS_scr.shape[0]):  # independent chains, side by side
            q, T, P = q_ref[0, h, rows, :], T_ref[0, h, c], P_ref[0, h, c]
            x = _chunk(q, k_ref[0, h, rows, :], v_ref[0, h, rows, :],
                       G_ref[0, h, rows, :], beta_ref[0, h, row, :], T,
                       states_ref[0, h, c])
            dt = q.dtype
            C, dk = x["eG"].shape
            dO, dS = do_ref[0, h, rows, :].astype(dt), dS_scr[h]
            dSd, Sd, U = dS.astype(dt), x["Sd"], x["U"]
            b, eG, to_end, a = x["b"], x["eG"], x["to_end"], x["a"]
            q32, k32, v32 = x["q32"], x["k32"], x["v32"]
            # the state's chain: U's cotangent, then the state's own
            dU = (_dot(P, dO, _TN) + _dot(x["Kd"], dSd)).astype(dt)
            dS_scr[h] = (a * dS + _dot(x["Qg"], dO, _TN)
                         - _dot(x["W"], dU, _TN))
            da = _to_row(_lanes(x["S"] * dS), _eye(dk))  # (1, dk)
            # the products' other operands
            dKd, dQg = _dot(U, dSd, _NT), _dot(dO, Sd, _NT)
            dP_ref[0, h, c] = _dot(dO, U, _NT)
            dW = (-_dot(dU, Sd, _NT)).astype(dt)
            dT_ref[0, h, c] = _dot(dW, x["bk"], _NT) + _dot(dU, x["bv"], _NT)
            dbk, dbv = _dot(T, dW, _TN), _dot(T, dU, _TN)
            dq_ref[0, h, rows, :] = (eG * dQg).astype(dq_ref.dtype)
            dk_ref[0, h, rows, :] = (b * eG * dbk
                                     + to_end * dKd).astype(dk_ref.dtype)
            dv_ref[0, h, rows, :] = (b * dbv).astype(dv_ref.dtype)
            dbeta_ref[0, h, row, :] = _to_row(
                _lanes(eG * dbk * k32) + _lanes(dbv * v32), _eye(C))
            # the decays: every one an exponential of running sums of g
            d_to_end = dKd * k32 * to_end
            dG_end = da * x["a_row"] + jnp.sum(d_to_end, axis=0, keepdims=True)
            at = lax.broadcasted_iota(jnp.int32, (C, 1), 0)
            dG_ref[0, h, rows, :] = ((b * dbk * k32 + dQg * q32) * eG - d_to_end
                                     + jnp.where(at == C - 1, dG_end, 0.0))
        return carry

    lax.fori_loop(0, blocks, one, None)


_specs = functools.partial(_gd_specs, heads_most=BLOCK_HEADS)  # the rule's own grid


_EVERY_CHUNK_ALONE = dict(compiler_params=pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"),
    vmem_limit_bytes=VMEM_LIMIT))


def _pairs_call(q, k, g, *, chunk: int, interpret: bool):
    """g the log decays -> (A without beta (B, H, S / chunk, chunk, chunk)
    float32, P likewise in q's type, G the running sum of g inside each
    chunk, g's shape in float32)."""
    B, H, S, dk = q.shape
    grid, spec = _specs(B, H, S, dk, dk, chunk, back=False)
    shape = (B, H, S // chunk, chunk, chunk)
    return kernel_call(
        functools.partial(_pairs_kernel, chunk=chunk), grid=grid,
        in_specs=[spec["qk"]] * 3, out_specs=[spec["T"]] * 2 + [spec["qk"]],
        out_shape=[jax.ShapeDtypeStruct(shape, jnp.float32),
                   jax.ShapeDtypeStruct(shape, q.dtype),
                   jax.ShapeDtypeStruct(g.shape, jnp.float32)],
        interpret=interpret, name="kda_pairs", **_EVERY_CHUNK_ALONE)(q, k, g)


def _pairs_back_call(q, k, G, dA, dP, dG, *, chunk: int, interpret: bool):
    """dG float32 what G receives beside A and P -> (dq, dk in q's type:
    what they receive through A and P; dg float32, in dG's buffer: all that
    the log decays receive)."""
    B, H, S, dk = q.shape
    grid, spec = _specs(B, H, S, dk, dk, chunk, back=False)
    return kernel_call(
        functools.partial(_pairs_back_kernel, chunk=chunk), grid=grid,
        in_specs=[spec["qk"]] * 3 + [spec["T"]] * 2 + [spec["qk"]],
        out_specs=[spec["qk"]] * 3,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(G.shape, jnp.float32)],
        input_output_aliases={5: 2},
        interpret=interpret, name="kda_pairs_backward", **_EVERY_CHUNK_ALONE,
    )(q, k, G, dA, dP, dG)


def _forward(q, k, v, G, beta, T, P, *, chunk: int, interpret: bool):
    """-> (o, the state at each chunk's start (B, H, S / chunk, dk, dv)
    float32)."""
    B, H, S, dk = q.shape
    dv, N = v.shape[-1], S // chunk
    grid, spec = _specs(B, H, S, dk, dv, chunk, back=False)
    return kernel_call(
        functools.partial(_forward_kernel, chunk=chunk), grid=grid,
        in_specs=[spec["qk"], spec["qk"], spec["v"], spec["qk"], spec["row"],
                  spec["T"], spec["T"]],
        out_specs=[spec["v"], spec["state"]],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((B, H, N, dk, dv), jnp.float32)],
        scratch_shapes=[spec["scratch"]],
        interpret=interpret, name="kda_forward", **_PARAMS,
    )(q, k, v, G, beta.reshape(B, H, N, chunk), T, P)


def _backward(q, k, v, G, beta, T, P, states, do, *, chunk: int,
              interpret: bool):
    """-> (dq, dk, dv, dG, dbeta, dT, dP): dq, dk, dG and dbeta without what
    they receive through T and P, whose cotangents are float32."""
    B, H, S, dk = q.shape
    dv, N = v.shape[-1], S // chunk
    grid, spec = _specs(B, H, S, dk, dv, chunk, back=True)
    rows = (B, H, N, chunk)
    shapes = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)]
    shapes += [jax.ShapeDtypeStruct(G.shape, jnp.float32),
               jax.ShapeDtypeStruct(rows, jnp.float32)]
    shapes += [jax.ShapeDtypeStruct(T.shape, jnp.float32)] * 2
    *d, dbeta, dT, dP = kernel_call(
        functools.partial(_backward_kernel, chunk=chunk), grid=grid,
        in_specs=[spec["qk"], spec["qk"], spec["v"], spec["qk"], spec["row"],
                  spec["T"], spec["T"], spec["state"], spec["v"]],
        out_specs=[spec["qk"], spec["qk"], spec["v"], spec["qk"], spec["row"],
                   spec["T"], spec["T"]],
        out_shape=shapes,
        scratch_shapes=[spec["scratch"]],
        interpret=interpret, name="kda_backward", **_PARAMS,
    )(q, k, v, G, beta.reshape(rows), T, P, states, do)
    return (*d, dbeta.reshape(beta.shape), dT, dP)


def _solve(A, beta, dtype):
    """T = (I + beta A)^-1 a chunk, the inverse in float32 (`gated_delta`'s
    substitution kernel), rounded after it."""
    rows = beta.reshape(A.shape[:-1])[..., None]
    return _unit_lower_inverse(rows * A).astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def kda_rule(q, k, v, g, beta, chunk: int = CHUNK):
    """q, k (B, H, S, dk), v (B, H, S, dv), g (B, H, S, dk) float32 (the log
    decay a key feature, <= 0) and beta (B, H, S) float32 -> o (B, H, S, dv)
    in v's type: the recurrence of the module's head, S_0 = 0, in its
    chunkwise form. q and k come normalised and scaled as the layer wants
    them. `chunk` is a power of two that divides S, or this raises."""
    return _fwd(q, k, v, g, beta, chunk)[0]


def _fwd(q, k, v, g, beta, chunk):
    S = q.shape[2]
    if chunk & (chunk - 1) or S % chunk:
        raise ValueError(f"kda_rule: the sequence length {S} is no multiple "
                         f"of the chunk {chunk}, a power of two")
    A, P, G = _on_platform(_pairs_call, q, k, g, chunk=chunk)
    T = _solve(A, beta, q.dtype)
    o, states = _on_platform(_forward, q, k, v, G, beta, T, P, chunk=chunk)
    return o, (q, k, v, g, beta, states)


def _bwd(chunk, res, do):
    q, k, v, g, beta, states = res
    A, P, G = _on_platform(_pairs_call, q, k, g, chunk=chunk)
    T, back_solve = jax.vjp(lambda A, beta: _solve(A, beta, q.dtype), A, beta)
    dq, dk_, dv, dG, dbeta, dT, dP = _on_platform(
        _backward, q, k, v, G, beta, T, P, states, do, chunk=chunk)
    dA, dbeta_T = back_solve(dT.astype(T.dtype))
    dq_P, dk_P, dg = _on_platform(_pairs_back_call, q, k, G, dA, dP, dG,
                                  chunk=chunk)
    return dq + dq_P, dk_ + dk_P, dv, dg.astype(g.dtype), dbeta + dbeta_T


kda_rule.defvjp(_fwd, _bwd)
