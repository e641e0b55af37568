"""Plain float32 reference of the Laguna cell's loss, written from the layer
equations of ISSUE 33 (the source's `config.json` read with the transformers
library's conventions where it is silent; the configuration file lists each
such reading under `assumed`). It imports nothing from kungfu_tpu; it reads
the program's parameter tree: embed, lm_head, ln_f_scale, and `layers`, a
tuple with one entry for each run of successive layers of one kind, the
run's layers stacked on a leading axis: ln1_scale, ln2_scale, wq, wk, wv,
wo, w_head_gate, then w_gate, w_up, w_down of a dense feed-forward, or
router, w_gate, w_up, w_down (the experts held, on the next axis),
shared_gate, shared_up, shared_down of an expert layer.

    h     = rms(x_l; s1)
    q     = h W_q  as H_l heads;  k = h W_k,  v = h W_v  as 8 heads;  head size 128
    q, k  = rope_l(q), rope_l(k)
    a_h   = softmax(mask_l(q_h k_{h // g}^T / sqrt(128))) v_{h // g},   g = H_l / 8
    a_h   = sigmoid(h W_g)[:, h] a_h
    y     = x_l + concat_h(a_h) W_o
    n     = rms(y; s2)
    dense:   x_l+1 = y + W_down (silu(W_gate n) * W_up n)
    experts: p = softmax(n W_r) over all E experts; e_1..e_k the k largest;
             w_j = scale * p_{e_j} / sum_j p_{e_j}
             x_l+1 = y + sum_{j: e_j held here} w_j expert_{e_j}(n) + expert_shared(n)
    loss  = mean_t -log softmax(rms(x_L; s_f) W_head^T)[target_t]   over the rows held
    rms(x; s) = x / sqrt(mean(x^2) + eps) * s

mask_l is causal in a full layer, and causal within the window in a sliding
one: query i sees key j iff 0 <= i - j < window. rope_l turns the first
`rotary` features of each head by rotate-half and passes the rest:

    rope(t)  = t cos(theta) + rotate_half(t) sin(theta),  theta_{s,i} = s f_i
    default:  f_i = base^(-2i / rotary)
    yarn:     f_i = (base^(-2i/rotary) / factor) r_i + base^(-2i/rotary) (1 - r_i),
              r_i = clip((i - low) / (high - low), 0, 1),
              low, high = floor, ceil of rotary ln(original / (2 pi b)) / (2 ln base)
              at b = beta_fast, beta_slow, clamped to [0, rotary - 1];
              cos and sin times attention_factor

The attention is dense, the masks written out, computed a block of queries
at a time (the scores of one sliding layer, 72 heads at 8,192 positions, are
19 GB at once). Every held expert is run over every token in a Python loop
and masked: no sort, no groups and no kernel to share a fault with the
program. What the experts on other chips would have added is left out, as in
the program: the share is the model here.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _frequencies(rope: dict, head_dim: int):
    """(f_i for the rotary // 2 pairs, the factor on cos and sin)."""
    rotary = int(head_dim * rope["partial_rotary_factor"])
    base = float(rope["rope_theta"])
    own = base ** (-jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    if rope["rope_type"] == "default":
        return own, 1.0

    def pair_turning(times):
        return (rotary * math.log(rope["original_max_position_embeddings"]
                                  / (times * 2 * math.pi)) / (2 * math.log(base)))

    low = max(math.floor(pair_turning(rope["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(rope["beta_slow"])), rotary - 1)
    ramp = jnp.clip((jnp.arange(rotary // 2, dtype=jnp.float32) - low)
                    / ((high - low) or 0.001), 0.0, 1.0)
    return (own / rope["factor"] * ramp + own * (1.0 - ramp),
            rope["attention_factor"])


def _rope(t, rope: dict):
    """t (b, heads, s, head_dim): rotate-half over the leading rotary
    features, the rest as they are."""
    s, head_dim = t.shape[2], t.shape[3]
    freq, factor = _frequencies(rope, head_dim)
    rotary = 2 * freq.shape[0]
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    turn, rest = t[..., :rotary], t[..., rotary:]
    half = jnp.concatenate([-turn[..., rotary // 2:], turn[..., :rotary // 2]],
                           axis=-1)
    turned = turn * (factor * jnp.cos(angles)) + half * (factor * jnp.sin(angles))
    return jnp.concatenate([turned, rest], axis=-1)


def _attention(q, k, v, window, block: int):
    """q (b, H, s, hd), k and v (b, H / g, s, hd) -> (b, H, s, hd), `block`
    queries at a time; a block keeps its inputs and recomputes its scores
    in the backward pass."""
    b, n_heads, s, hd = q.shape
    kv_heads = k.shape[1]
    group = n_heads // kv_heads
    block = min(block, s)
    assert s % block == 0, (s, block)

    @jax.checkpoint
    def one(args):
        qb, start = args  # (b, kv heads, group, block, hd)
        behind = (start + jnp.arange(block))[:, None] - jnp.arange(s)[None, :]
        seen = behind >= 0
        if window:
            seen = seen & (behind < window)
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


def _swiglu(n, w_gate, w_up, w_down):
    gate = n @ w_gate
    return (gate / (1.0 + jnp.exp(-gate)) * (n @ w_up)) @ w_down


def routing(n, router, top_k: int, scale: float, renormalise: bool):
    """(chosen (t, top_k), their weights (t, top_k)) of normed tokens n."""
    logits = n @ router
    shifted = logits - jnp.max(logits, axis=-1, keepdims=True)
    probs = jnp.exp(shifted) / jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True)
    top, chosen = jax.lax.top_k(probs, top_k)
    if renormalise:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return chosen, scale * top


def _layer(x, w, spec: dict, hyper: dict):
    """One layer; `w` its weights (no leading axis). -> (x, chosen or None)."""
    b, s, d = x.shape
    hd, kv_heads, eps = hyper["head_dim"], hyper["kv_heads"], hyper["eps"]
    h = _rms(x, w["ln1_scale"], eps)

    def heads(t, n):
        return t.reshape(b, s, n, hd).transpose(0, 2, 1, 3)

    q = _rope(heads(h @ w["wq"], spec["heads"]), spec["rope"])
    k = _rope(heads(h @ w["wk"], kv_heads), spec["rope"])
    v = heads(h @ w["wv"], kv_heads)
    ctx = _attention(q, k, v, spec["window"], hyper["query_block"])
    gate = 1.0 / (1.0 + jnp.exp(-(h @ w["w_head_gate"])))  # (b, s, H)
    ctx = ctx * gate.transpose(0, 2, 1)[..., None]
    x = x + ctx.transpose(0, 2, 1, 3).reshape(b, s, spec["heads"] * hd) @ w["wo"]

    n = _rms(x, w["ln2_scale"], eps)
    if spec["ffn"] == "dense":
        return x + _swiglu(n, w["w_gate"], w["w_up"], w["w_down"]), None
    n = n.reshape(b * s, d)
    chosen, weights = routing(n, w["router"], hyper["top_k"],
                              hyper["routed_scale"], hyper["renormalise"])
    y = _swiglu(n, w["shared_gate"], w["shared_up"], w["shared_down"])
    for e in range(w["w_gate"].shape[0]):  # the experts held here
        mine = jnp.sum(jnp.where(chosen == hyper["first_held"] + e, weights, 0.0),
                       axis=-1)
        y = y + mine[:, None] * _swiglu(n, w["w_gate"][e], w["w_up"][e],
                                        w["w_down"][e])
    return x + y.reshape(b, s, d), chosen


def _runs(specs):
    """[(stack, index in it)] a layer: successive layers of one kind are one
    stack of the program's tree."""
    places, stack, at = [], -1, 0
    for i, spec in enumerate(specs):
        if i and spec == specs[i - 1]:
            at += 1
        else:
            stack, at = stack + 1, 0
        places.append((stack, at))
    return places


def forward(params, batch, *, layers, **hyper):
    """-> (loss, [the experts chosen (tokens, top_k) of each expert layer]).
    `layers`: a dict a layer, {"heads", "window" (0: none), "rope" (the
    source's group for the layer's kind), "ffn" ("dense" | "sparse")};
    `hyper`: head_dim, kv_heads, eps, top_k, routed_scale, renormalise,
    first_held, query_block. Each layer keeps its input and recomputes the
    rest in the backward pass."""
    tokens, targets = batch[:, :-1], batch[:, 1:]
    x = params["embed"][tokens]
    chosen = []
    for spec, (stack, at) in zip(layers, _runs(layers), strict=True):
        w = jax.tree.map(lambda leaf: leaf[at], params["layers"][stack])
        x, took = jax.checkpoint(
            functools.partial(_layer, spec=spec, hyper=hyper))(x, w)
        if took is not None:
            chosen.append(took)
    logits = _rms(x, params["ln_f_scale"], hyper["eps"]) @ params["lm_head"].T
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.mean(picked), chosen


def loss(params, batch, **hyper):
    return forward(params, batch, **hyper)[0]


def loss_and_grads(params, batch, **hyper):
    """Float32 throughout; on a TPU a float32 matmul runs in lower
    precision unless this is set."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(functools.partial(loss, **hyper)))(
            params, batch)


def chosen_experts(params, batch, **hyper):
    """(expert layers, tokens, top_k) expert ids the reference's router
    chooses: what the family counts the program's choices against."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack(jax.jit(functools.partial(forward, **hyper))(
            params, batch)[1])
