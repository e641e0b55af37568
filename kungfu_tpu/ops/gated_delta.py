"""The gated delta rule as a chunked scan, and the causal depthwise
convolution that stands before it in a Gated DeltaNet layer.

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

so all of a chunk is matrix products, and what runs in sequence is two
thin products a chunk on the state. Three phases:

1. `_local`, every chunk at once: T = (I + A)^-1 (`_unit_lower_inverse`,
   exact block substitution in six doublings, float32), W = T (beta exp(G)
   K), U0 = T (beta V), exp(G) Q, exp(G_C - G) K, P. Every decay is an
   exponential of a difference G_i - G_j <= 0 taken in float32: nothing is
   divided by a decay, so a strong one underflows to 0 and nothing
   overflows.
2. `_states`, a `lax.scan` over the chunks: S+ = a S + Kd^T (U0 - W S),
   the state carried in float32; it gives the state at every chunk's start.
3. `_combine`, every chunk at once again: U = U0 - W S, O = Qg S + P U.

The matrix products take their operands in the type q, k, v come in
(bfloat16 in the model, so float32 inputs give a float32 computation) and
accumulate in float32; g and beta are float32 and every decay is applied in
float32 (a bfloat16 decay of 0.99 is 0.988, and a state that is read 100
chunks later is then off by 16 %).

The backward pass is written out (`jax.custom_vjp`): it keeps the five
inputs and the chunk-boundary states (B H S/C dk dv float32: 0.5 GB a layer
of 32 heads at 16,384 positions), walks the chunks in reverse with the
transposed recurrence dS = C_n + a dS+ - W^T (Kd dS+), again two thin
products a chunk, and then takes the cotangents of phases 1 and 3 for all
chunks at once. No state a position exists in either pass. Phases 1 and 3
hold about 0.1 GB a head for all chunks at once at 16,384 positions: a
caller with many heads and little room hands them over a block at a time,
as the model's mixer does.

`models/transformer.py` runs it as the core of a layer whose `mixer` is
`"gated_delta"`, under the scope `gdn_core`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 64

_HIGHEST = lax.Precision.HIGHEST


def _taps_over(padded, taps, S: int):
    """sum_i taps_i padded[:, i:i + S] in float32: the K windows are read
    from the one padded array in its own type inside one fused pass."""
    return sum(padded[:, i:i + S].astype(jnp.float32) * taps[i].astype(jnp.float32)
               for i in range(taps.shape[0]))


@jax.custom_vjp
def causal_conv(x, taps):
    """Causal depthwise convolution over the sequence, no bias: x (B, S,
    channels), taps (K, channels); y_t = sum_i taps_i x_{t - (K - 1) + i},
    zeros before the start. The products and their sum in float32, the
    result in x's type. The backward pass is written out, the same K
    windows over the cotangent padded at its end and a reduction for the
    taps, and keeps x and the taps alone: autodiff keeps each of the K
    windows in float32 as the taps' residual (0.5 GB each at 16,384
    positions and 8,192 channels) and pads a float32 cotangent a window."""
    K = taps.shape[0]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return _taps_over(padded, taps, x.shape[1]).astype(x.dtype)


def _causal_conv_bwd(res, dy):
    x, taps = res
    K, S = taps.shape[0], x.shape[1]
    # dx_t = sum_i taps_i dy_{t + (K - 1) - i}: the taps in reverse over dy
    # with zeros after its end
    dx = _taps_over(jnp.pad(dy, ((0, 0), (0, K - 1), (0, 0))), taps[::-1], S)
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    dy32 = dy.astype(jnp.float32)
    dtaps = jnp.stack([
        jnp.sum(dy32 * padded[:, i:i + S].astype(jnp.float32), axis=(0, 1))
        for i in range(K)])
    return dx.astype(x.dtype), dtaps.astype(taps.dtype)


causal_conv.defvjp(lambda x, taps: (causal_conv(x, taps), (x, taps)),
                   _causal_conv_bwd)


@jax.custom_vjp
def _unit_lower_inverse(a):
    """(I + a)^-1 for strictly lower triangular a (..., C, C), C a power of
    two, float32. Block substitution by doubling: with T the inverse of the
    block diagonal of I + a at block size b and E the blocks of a that join
    two such blocks into one of size 2b, the inverse at 2b is T - T E T
    (exactly: (T E)^2 = 0). Six doublings of two products each at C = 64,
    each as stable as forward substitution, where the Neumann product (I -
    a)(I + a^2)(I + a^4)... cancels binomially large terms when successive
    keys are alike. The derivative is that of an inverse, two products: d a
    = -T^T dT T^T."""
    C = a.shape[-1]
    at = jnp.arange(C)
    T = jnp.broadcast_to(jnp.eye(C, dtype=a.dtype), a.shape)
    b = 1
    while b < C:
        joined = ((at[:, None] // (2 * b) == at[None, :] // (2 * b))
                  & (at[:, None] // b != at[None, :] // b))
        E = jnp.where(joined, a, 0)
        T = T - jnp.matmul(jnp.matmul(T, E, precision=_HIGHEST), T,
                           precision=_HIGHEST)
        b *= 2
    return T


def _unit_lower_inverse_bwd(T, dT):
    Tt = jnp.swapaxes(T, -1, -2)
    return (-jnp.matmul(jnp.matmul(Tt, dT, precision=_HIGHEST), Tt,
                        precision=_HIGHEST),)


_unit_lower_inverse.defvjp(lambda a: (_unit_lower_inverse(a),) * 2,
                           _unit_lower_inverse_bwd)


def _mm(a, b, spec: str):
    """einsum with float32 accumulation, the operands as they come."""
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def _local(q, k, v, g, beta):
    """Phase 1 on (N, B, H, C, d) q, k, v and (N, B, H, C) float32 g, beta,
    N chunks of C positions: -> (W, U0, Qg, Kd, P, a). W, Qg, Kd (.., C,
    dk) and P (.., C, C) in q's type, operands of the products to come; U0
    (.., C, dv), from which W S is subtracted, and a = exp(G_C) (..) in
    float32."""
    dt, f32 = q.dtype, jnp.float32
    C = q.shape[-2]
    G = jnp.cumsum(g, axis=-1)
    at = jnp.arange(C)
    lower = at[:, None] >= at[None, :]
    decay = jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :], -jnp.inf))
    A = jnp.where(at[:, None] > at[None, :],
                  beta[..., :, None] * decay * _mm(k, k, "...id,...jd->...ij"), 0)
    T = _unit_lower_inverse(A).astype(dt)
    k32 = k.astype(f32)
    eG = jnp.exp(G)[..., None]
    W = _mm(T, (beta[..., None] * eG * k32).astype(dt), "...ij,...jd->...id")
    U0 = _mm(T, (beta[..., None] * v.astype(f32)).astype(dt), "...ij,...jd->...id")
    Qg = (q.astype(f32) * eG).astype(dt)
    Kd = (k32 * jnp.exp(G[..., -1:] - G)[..., None]).astype(dt)
    P = (decay * _mm(q, k, "...id,...jd->...ij")).astype(dt)
    return W.astype(dt), U0, Qg, Kd, P, jnp.exp(G[..., -1])


def _next_state(S, W, U0, Kd, a):
    """S+ = a S + Kd^T (U0 - W S) and U, for one chunk or for all."""
    dt = W.dtype
    U = U0 - _mm(W, S.astype(dt), "...cd,...dv->...cv")
    return (a[..., None, None] * S
            + _mm(Kd, U.astype(dt), "...cd,...cv->...dv")), U


def _states(W, U0, Kd, a):
    """Phase 2: the state at the start of each chunk, (N, B, H, dk, dv)
    float32, S_0 = 0."""
    def step(S, chunk):
        return _next_state(S, *chunk)[0], S

    S0 = jnp.zeros(a.shape[1:] + (W.shape[-1], U0.shape[-1]), jnp.float32)
    return lax.scan(step, S0, (W, U0, Kd, a))[1]


def _combine(local, S):
    """Phase 3 with the states S at the chunks' starts: -> (O in float32,
    the states at the chunks' ends)."""
    W, U0, Qg, Kd, P, a = local
    dt = W.dtype
    S_next, U = _next_state(S, W, U0, Kd, a)
    O = (_mm(Qg, S.astype(dt), "...cd,...dv->...cv")
         + _mm(P, U.astype(dt), "...ij,...jv->...iv"))
    return O, S_next


def _chunked(x, chunk: int):
    """(B, H, S, ...) -> (N, B, H, chunk, ...)."""
    B, H, S = x.shape[:3]
    x = x.reshape((B, H, S // chunk, chunk) + x.shape[3:])
    return jnp.moveaxis(x, 2, 0)


def _unchunked(x):
    x = jnp.moveaxis(x, 0, 2)
    return x.reshape(x.shape[:2] + (-1,) + x.shape[4:])


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
    local = _local(*(_chunked(x, chunk) for x in (q, k, v, g, beta)))
    W, U0, _, Kd, _, a = local
    states = _states(W, U0, Kd, a)
    O, _ = _combine(local, states)
    return _unchunked(O).astype(v.dtype), (q, k, v, g, beta, states)


def _bwd(chunk, res, do):
    q, k, v, g, beta, states = res
    inputs = tuple(_chunked(x, chunk) for x in (q, k, v, g, beta))
    local, back_local = jax.vjp(_local, *inputs)
    W, _, Qg, Kd, P, a = local
    dt = W.dtype
    dO = _chunked(do, chunk).astype(dt)
    # what a chunk's outputs say of its U: with Qg^T dO, the part of dS
    # that waits for no later chunk
    dU_O = _mm(P, dO, "...ij,...iv->...jv")

    def step(dS_next, chunk_):
        W, Qg, Kd, a, dO, dU_O = chunk_
        dU = dU_O + _mm(Kd, dS_next.astype(dt), "...cd,...dv->...cv")
        dS = (a[..., None, None] * dS_next
              + _mm(Qg, dO, "...cd,...cv->...dv")
              - _mm(W, dU.astype(dt), "...cd,...cv->...dv"))
        return dS, dS_next

    zero = jnp.zeros(states.shape[1:], jnp.float32)
    _, dS_next = lax.scan(step, zero, (W, Qg, Kd, a, dO, dU_O), reverse=True)
    _, back_combine = jax.vjp(lambda local: _combine(local, states), local)
    (d_local,) = back_combine((dO.astype(jnp.float32), dS_next))
    return tuple(_unchunked(d) for d in back_local(d_local))


gated_delta_rule.defvjp(_fwd, _bwd)
