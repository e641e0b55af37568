"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
hyper-connections, arXiv:2409.19606): n residual streams a position, mixed
around every branch of a layer by three maps the layer computes from them.

A position's state is X in R^(n x C), held as vec(X): (B, S, n * C), the
streams side by side on the feature axis, stream j at features j * C to
(j + 1) * C. (Laid out (B, S, n, C) the n of 4 would stand on a tile's
sublanes, a quarter of them filled: side by side every tile is full, vec(X)
is a row as the product with Phi wants it, and a stream is a slice at a
multiple of C.) Around a branch F:

    r = vec(X) / sqrt(mean(vec(X)^2) + 1e-6)          over all n C features
    [Ht_pre (n) | Ht_post (n) | Ht_res (n x n)] = [a_pre, a_post, a_res] * (r Phi) + b
    H_pre = sigmoid(Ht_pre);  H_post = 2 sigmoid(Ht_post)
    H_res = SK(clip(Ht_res, lo, hi)):  M = exp(.), then `iters` times
            M <- M / (column sums + eps), M <- M / (row sums + eps)
    u = sum_j H_pre[j] X[j];   y = F(u);   X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

`maps` makes the three (float32, the product with Phi at the highest
precision, as the router's), `read` u and `write` X' (the streams in their own
type, the sums accumulated in float32). Plain `jax.numpy`: XLA makes each of
the two mixings a few passes over the streams, and the Sinkhorn passes a loop
of small fusions, elementwise because the maps stand with the positions along
the lanes, (n, n, 8, T / 8), where row and column sums are sums of whole
tiles: laid out (T, n, n) a position's 16 numbers fill 16 of a tile's 1,024
places and every sum is across lanes. Nothing here is a kernel yet (PERF.md
section 7); the layer puts `read` and `write` between optimization barriers
(`models/transformer._read`, `_taken`), so that they are ops of their own.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax


# Sinkhorn passes a trip of the loop they run in: unrolled whole, 20 passes of
# six branches are most of the small configuration's compile on the CPU (21 s
# of 27; 7 s at 5 or at 1); a pass a trip pays a loop's turn for four small
# fusions
SINKHORN_UNROLL = 5
# what the maps are computed in: float32; anything less is a fault the tests make
MAP_DTYPE = jnp.float32


class Maps(NamedTuple):
    """A branch's three maps, float32, the positions last: `pre` and `post`
    (n, B, S), `res` (n, n, B, S) with res[i, j] what stream i takes of
    stream j."""
    pre: jax.Array
    post: jax.Array
    res: jax.Array


def enter(x, n: int):
    """(B, S, C) -> the n streams (B, S, n * C), each a copy of x
    (hyper-connections, section 3)."""
    return jnp.tile(x, (1, 1, n))


def leave(X, n: int):
    """The streams (B, S, n * C) -> their sum (B, S, C), in float32 and
    back in the streams' type."""
    return sum(s.astype(jnp.float32) for s in _streams(X, n)).astype(X.dtype)


def _streams(X, n: int):
    C = X.shape[-1] // n
    return [X[..., j * C:(j + 1) * C] for j in range(n)]


@functools.partial(jax.checkpoint, prevent_cse=False, static_argnums=(1, 2, 3))
def sinkhorn(logits, iters: int, eps: float, clamp: Tuple[float, float]):
    """logits (n, n, ...) float32 -> the Sinkhorn-Knopp projection of
    exp(clip(logits)) towards the doubly stochastic matrices: `iters` times
    the columns over their sums (+ eps), then the rows over theirs (the
    paper's T_r(T_c(M))), so the rows sum to one last. The sums are written
    as sums of slices along the two leading axes, elementwise in whatever
    stands behind them. Keeps the logits and makes the passes again in the
    backward pass."""
    n = logits.shape[0]

    def one_pass(_, M):
        M = M / (sum(M[i] for i in range(n)) + eps)[None]
        return M / (sum(M[:, j] for j in range(n)) + eps)[:, None]

    return lax.fori_loop(0, iters, one_pass, jnp.exp(jnp.clip(logits, *clamp)),
                         unroll=SINKHORN_UNROLL)


def maps(X, phi, a, b, n: int, iters: int, eps: float,
         clamp: Tuple[float, float]) -> Maps:
    """The three maps of one branch from the streams X (B, S, n * C), `phi`
    (n * C, 2 n + n^2), `a` (3,) and `b` (2 n + n^2,). r is never written:
    r Phi = (vec(X) Phi) / sqrt(mean(vec(X)^2) + 1e-6), the product of the
    streams as they are."""
    B, S, _ = X.shape
    f32 = jnp.float32
    X32 = X.astype(f32)
    rms = lax.rsqrt(jnp.mean(jnp.square(X32), axis=-1, keepdims=True) + 1e-6)
    logits = jnp.dot(X32, phi.astype(f32), precision=lax.Precision.HIGHEST) * rms
    gain = jnp.concatenate([jnp.full((n,), a[0]), jnp.full((n,), a[1]),
                            jnp.full((n * n,), a[2])]).astype(f32)
    logits = (logits * gain + b.astype(f32)).astype(MAP_DTYPE)
    # the positions along the lanes: (2 n + n^2, rows of a tile, the rest)
    rows = 8 if (B * S) % 8 == 0 else 1
    logits = logits.reshape(B * S, -1).T.reshape(-1, rows, B * S // rows)
    pre = jax.nn.sigmoid(logits[:n])
    post = 2.0 * jax.nn.sigmoid(logits[n:2 * n])
    res = sinkhorn(logits[2 * n:].reshape(n, n, rows, -1), iters, eps, clamp)
    return Maps(pre.reshape(n, B, S).astype(f32),
                post.reshape(n, B, S).astype(f32),
                res.reshape(n, n, B, S).astype(f32))


def read(X, pre):
    """u = sum_j H_pre[j] X[j]: (B, S, n * C), (n, B, S) -> (B, S, C)."""
    n = pre.shape[0]
    return sum(pre[j][..., None] * s.astype(jnp.float32)
               for j, s in enumerate(_streams(X, n))).astype(X.dtype)


def write(X, y, res, post):
    """X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y: the streams (B, S, n *
    C), the branch's output y (B, S, C), (n, n, B, S) and (n, B, S) -> the
    streams."""
    n = post.shape[0]
    streams = [s.astype(jnp.float32) for s in _streams(X, n)]
    y = y.astype(jnp.float32)
    return jnp.concatenate(
        [(sum(res[i, j][..., None] * s for j, s in enumerate(streams))
          + post[i][..., None] * y).astype(X.dtype) for i in range(n)], axis=-1)
