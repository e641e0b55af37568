"""Plain float32 reference of the LFM2-24B-A2B cell's loss, written from the
layer equations of ISSUE 57 (the source's `config.json`, `model_type`
lfm2_moe, read with the LFM2 family's conventions where it is silent; the
configuration file lists each such reading under `assumed`). It imports
nothing from kungfu_tpu and nothing from the other references; it reads the
program's parameter tree: embed, ln_f_scale, and `layers`, a tuple with one
entry for each run of successive layers of one kind, the run's layers stacked
on a leading axis. A layer's leaves say what it is: `conv_in`, `conv_w`,
`conv_out` of a convolution layer or wq, wk, wv, wo, q_norm_scale, k_norm_scale of an
attention layer, then w_gate, w_up, w_down of a dense feed-forward or router,
router_bias and w_gate, w_up, w_down (the experts held, on the next axis) of
an expert layer.

    every layer:   h = h + op(rms(h; w_1));   h = h + ff(rms(h; w_2))
    rms(x; w) = x / sqrt(mean(x^2) + eps) w

    conv:      [B | C | x] = u W_in           three equal thirds, in that order
               z_t = B_t * x_t
               c_t = sum_{i<K} k_i z_{t-(K-1)+i}     a channel, 0 before the row
               op  = (C * c) W_out            no activation, no bias, no state

    attention: q = u W_q (H heads of hd), k = u W_k, v = u W_v (Hkv heads)
               q = rot(rms(q; w_qn)), k = rot(rms(k; w_kn))   the norm over a head's hd
               a_h = softmax(causal(q_h k_{h // (H / Hkv)}^T / sqrt(hd))) v_{h // (H / Hkv)}
               op  = concat_h(a_h) W_o
               rot(t) = t cos(theta) + rotate_half(t) sin(theta),  theta_{p,i} = p base^(-2i / hd)

    dense:     ff = W_down (silu(W_gate n) * W_up n)
    experts:   s = sigmoid(n W_r) over all E experts; e_1..e_k the k largest of s + b;
               w_j = scale * s_{e_j} / sum_j s_{e_j}
               ff = sum_{j: e_j held here} w_j expert_{e_j}(n)

    loss = mean_t -log softmax(rms(h_L; w_f) E^T)[id_{t+1}]       on the tied embedding

over positions 0..S-1 of a batch of S + 1 ids, the rows of the vocabulary
held here. The convolution is K shifted products with zeros moved in at the
row's start: no padded copy, no window and no kernel to share a fault with
the program. The attention is dense scores under the mask, a block of queries
at a time; the dense feed-forward and the loss a block of positions at a
time; every held expert is run over every token in a Python loop and masked.
What the experts on other chips would have added is left out, as in the
program: the share is the model here. The bias b is a constant: only the
choice reads it, and the choice has no derivative.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def short_conv(u, w):
    """The convolution operator on normed hidden states u (b, s, d)."""
    d = u.shape[-1]
    bcx = u @ w["conv_in"]
    gate_in, gate_out, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    z = gate_in * x
    taps = w["conv_w"]  # (K, d)
    K, s = taps.shape[0], z.shape[1]
    conv = jnp.zeros_like(z)
    for i in range(K):
        back = K - 1 - i
        moved = jnp.concatenate([jnp.zeros_like(z[:, :back]), z[:, :s - back]],
                                axis=1)
        conv = conv + taps[i] * moved
    return (gate_out * conv) @ w["conv_out"]


def _rot(t, base: float):
    """t (..., s, r): rotate-half over all r features at positions 0..s-1."""
    s, r = t.shape[-2], t.shape[-1]
    freq = base ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    half = jnp.concatenate([-t[..., r // 2:], t[..., :r // 2]], axis=-1)
    return t * jnp.cos(angles) + half * jnp.sin(angles)


def _causal(q, k, v, block: int):
    """q (b, H, s, hd), k and v (b, Hkv, s, hd) -> (b, H, s, hd), `block`
    queries at a time; a block keeps its inputs and recomputes its scores in
    the backward pass."""
    b, n_heads, s, hd = q.shape
    kv_heads = k.shape[1]
    group = n_heads // kv_heads
    block = min(block, s)
    assert s % block == 0 and n_heads % kv_heads == 0, (s, block, n_heads)

    @jax.checkpoint
    def one(args):
        qb, start = args  # (b, Hkv, group, block, hd)
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.einsum("bkgqd,bksd->bkgqs", qb, k) / jnp.sqrt(jnp.float32(hd))
        scores = jnp.where(seen, scores, -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        probs = jnp.exp(scores)
        probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
        return jnp.einsum("bkgqs,bksd->bkgqd", probs, v)

    # query head h = key/value head h // group, and place h % group in it
    blocks = q.reshape(b, kv_heads, group, s // block, block, hd)
    out = jax.lax.map(one, (blocks.transpose(3, 0, 1, 2, 4, 5),
                            jnp.arange(0, s, block)))
    return out.transpose(1, 2, 3, 0, 4, 5).reshape(b, n_heads, s, hd)


def attention(u, w, hyper: dict):
    b, s, _ = u.shape
    H, Hkv, hd = hyper["heads"], hyper["kv_heads"], hyper["head_dim"]
    eps, base = hyper["eps"], hyper["rope_theta"]

    def heads(t, n):
        return t.reshape(b, s, n, hd).transpose(0, 2, 1, 3)

    q = _rot(_rms(heads(u @ w["wq"], H), w["q_norm_scale"], eps), base)
    k = _rot(_rms(heads(u @ w["wk"], Hkv), w["k_norm_scale"], eps), base)
    ctx = _causal(q, k, heads(u @ w["wv"], Hkv), hyper["query_block"])
    return ctx.transpose(0, 2, 1, 3).reshape(b, s, H * hd) @ w["wo"]


def _swiglu(n, w_gate, w_up, w_down):
    return (_silu(n @ w_gate) * (n @ w_up)) @ w_down


def dense(n, w, block: int):
    """The dense feed-forward on n (t, d), `block` rows at a time (8,192 x
    11,776 float32 gates are 0.39 GB, and the backward pass holds six such
    arrays); a block keeps its rows and runs again in the backward pass."""
    t, d = n.shape
    block = min(block, t)
    assert t % block == 0, (t, block)
    some = jax.checkpoint(
        lambda rows: _swiglu(rows, w["w_gate"], w["w_up"], w["w_down"]))
    return jax.lax.map(some, n.reshape(t // block, block, d)).reshape(t, d)


def routing(n, router, bias, top_k: int, scale: float):
    """(chosen (t, top_k), their weights (t, top_k)) of normed tokens n: the
    choice on sigmoid scores + bias, the weights from the scores alone."""
    scores = 1.0 / (1.0 + jnp.exp(-(n @ router)))
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, scale * top / jnp.sum(top, axis=-1, keepdims=True)


def experts(n, w, hyper: dict):
    """The expert layer on normed tokens n (t, d) -> (y (t, d), chosen): the
    held experts' part, each run over every token and masked."""
    chosen, weights = routing(n, w["router"], w["router_bias"], hyper["top_k"],
                              hyper["routed_scale"])
    one = jax.checkpoint(_swiglu)
    y = jnp.zeros_like(n)
    for e in range(w["w_gate"].shape[0]):  # the experts held here
        mine = jnp.sum(jnp.where(chosen == hyper["first_held"] + e, weights, 0.0),
                       axis=-1)
        y = y + mine[:, None] * one(n, w["w_gate"][e], w["w_up"][e],
                                    w["w_down"][e])
    return y, chosen


def _layer(x, w, hyper: dict):
    """One layer; `w` its weights (no leading axis). -> (x, chosen or None)."""
    b, s, d = x.shape
    eps = hyper["eps"]
    u = _rms(x, w["ln1_scale"], eps)
    x = x + (short_conv(u, w) if "conv_in" in w else attention(u, w, hyper))
    n = _rms(x, w["ln2_scale"], eps).reshape(b * s, d)
    if "router" not in w:
        return x + dense(n, w, hyper["position_block"]).reshape(b, s, d), None
    y, chosen = experts(n, w, hyper)
    return x + y.reshape(b, s, d), chosen


def forward(params, tokens, **hyper):
    """-> (the final normed hidden states (b, s, d), [the experts chosen
    (tokens, top_k) of each expert layer]). `hyper`: heads, kv_heads,
    head_dim, rope_theta, eps, top_k, routed_scale, first_held, query_block,
    position_block. Each layer keeps its input and recomputes the rest in the
    backward pass."""
    layer = jax.checkpoint(functools.partial(_layer, hyper=hyper))
    x = params["embed"][tokens]
    chosen = []
    for stack in params["layers"]:
        for at in range(stack["ln1_scale"].shape[0]):
            x, took = layer(x, jax.tree.map(lambda leaf: leaf[at], stack))
            if took is not None:
                chosen.append(took)
    return _rms(x, params["ln_f_scale"], hyper["eps"]), chosen


def _head_loss(normed, head, targets, block: int):
    """mean_t -log softmax(normed_t head^T)[target_t], `block` positions at
    a time."""
    b, s, d = normed.shape
    block = min(block, s)
    assert s % block == 0, (s, block)

    @jax.checkpoint
    def some(total, xs):
        rows, picks = xs  # (b, block, d), (b, block)
        logits = rows @ head.T
        shifted = logits - jnp.max(logits, axis=-1, keepdims=True)
        logp = shifted - jnp.log(jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True))
        return total - jnp.sum(jnp.take_along_axis(logp, picks[..., None],
                                                   axis=-1)), None

    total, _ = jax.lax.scan(
        some, jnp.float32(0.0),
        (normed.reshape(b, s // block, block, d).transpose(1, 0, 2, 3),
         targets.reshape(b, s // block, block).transpose(1, 0, 2)))
    return total / (b * s)


def loss(params, batch, **hyper):
    normed, _ = forward(params, batch[:, :-1], **hyper)
    return _head_loss(normed, params["embed"], batch[:, 1:],
                      hyper["position_block"])


def loss_and_grads(params, batch, **hyper):
    """Float32 throughout; on a TPU a float32 matmul runs in lower
    precision unless this is set."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(functools.partial(loss, **hyper)))(
            params, batch)


def logits(params, batch, **hyper):
    """(b, s, vocabulary) float32: for the tests' sizes."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t: forward(p, t, **hyper)[0] @ p["embed"].T)(
            params, batch[:, :-1])


def chosen_experts(params, batch, **hyper):
    """(expert layers, tokens, top_k) expert ids the reference's router
    chooses: what the family counts the program's choices against."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack(jax.jit(lambda p, t: forward(p, t, **hyper)[1])(
            params, batch[:, :-1]))
