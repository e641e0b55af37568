"""Plain float32 reference of the SmallThinker-21BA3B-Instruct cell's loss,
written from the layer equations of ISSUE 65 (the source's `config.json`,
`model_name` smallthinker_21b_instruct, read with the published
implementation's order of a layer where the config names a mechanism and not
its equations; the configuration file lists each such reading under
`assumed`). It imports nothing from kungfu_tpu and nothing from the other
references; it reads the program's parameter tree: embed, lm_head, ln_f_scale,
and `layers`, a tuple with one entry for each run of successive layers of one
kind, the run's layers stacked on a leading axis: ln1_scale, ln2_scale, wq,
wk, wv, wo, router, w_gate, w_up, w_down (the experts held, on the next axis).

    every layer, h (S, D) the residual stream at its input:
    routing:   r = h W_r                        h itself, before any norm
               s = softmax(r) over all E experts;  e_1..e_k the k largest
               g_j = s_{e_j} / sum_j s_{e_j}
    mixer:     u = rms(h; w_1);  q = u W_q (H heads of hd), k = u W_k, v = u W_v (Hkv heads)
               rope layer:  q = rot(q), k = rot(k)
                            rot(t) = t cos(theta) + rotate_half(t) sin(theta), theta_{p,i} = p base^(-2i / hd)
               other layer: no position signal of any kind
               a[t, j, s] = softmax over the seen s of q[t, j] . k[s, j // (H / Hkv)] / sqrt(hd)
               seen: 0 <= t - s < window in a window layer, s <= t in a full one
               h' = h + concat_j(sum_s a[t, j, s] v[s, j // (H / Hkv)]) W_o
    experts:   m = rms(h'; w_2)
               h'' = h' + sum_{j: e_j held here} g_j W_down,e_j (relu(W_gate,e_j m) * W_up,e_j m)
    loss = mean_t -log softmax(rms(h_L; w_f) W_head^T)[id_{t+1}]
    rms(x; w) = x / sqrt(mean(x^2) + eps) w

over positions 0..S-1 of a batch of S + 1 ids, the rows of the vocabulary
held here. Where this departs from the published description, a line each:
- the config does not say what the router reads; the published implementation
  routes from the layer's input before `input_layernorm` (llama.cpp's graph
  `llm_build_smallthinker`: `ffn_moe_logits = ffn_gate_inp . inpL`), and so
  does this;
- the config counts `primary` experts and names no others: the 64 routed
  experts are all there are, none shared, no dense layer;
- `rope_layout` and `sliding_window_layout` are read a layer each and agree
  in this model; a layer could have one without the other here;
- no q/k norm and no bias: no key names one;
- what the experts on other chips would have added is left out, as in the
  program: the share is the model here;
- the routers' matrices are constants of the loss where the cell does not
  train them (`routers_trained` false); the gates' derivative still reaches
  the residual stream at the layer's input.

The attention is dense under the mask written out, a block of query rows at a
time (the scores of one layer, 28 heads at 16,384 positions, are 30 GB at
once); every held expert is run over every token in a Python loop and masked;
the head a block of positions at a time. No sort, no groups, no online softmax
and no kernel to share a fault with the program. A block keeps its inputs and
runs again in the backward pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rot(t, base: float):
    """Rotate-half over all of t's last axis (b, heads, s, hd) at positions
    0..s-1: feature i with i + hd / 2."""
    s, r = t.shape[-2], t.shape[-1]
    freq = base ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    half = jnp.concatenate([-t[..., r // 2:], t[..., :r // 2]], axis=-1)
    return t * jnp.cos(angles) + half * jnp.sin(angles)


def _attention(q, k, v, window: int, block: int):
    """q (b, H, s, hd), k and v (b, Hkv, s, hd) -> (b, H, s, hd), `block`
    queries at a time, dense under the mask; a block keeps its inputs and
    makes its scores again in the backward pass."""
    b, n_heads, s, hd = q.shape
    kv_heads = k.shape[1]
    group = n_heads // kv_heads
    block = min(block, s)
    assert s % block == 0 and n_heads % kv_heads == 0, (s, block, n_heads)

    @jax.checkpoint
    def one(args):
        qb, start = args  # (b, kv heads, group, block, hd)
        behind = (start + jnp.arange(block))[:, None] - jnp.arange(s)[None, :]
        seen = behind >= 0
        if window:
            seen = seen & (behind < window)
        scores = jnp.einsum("bkgqd,bksd->bkgqs", qb, k) / jnp.sqrt(jnp.float32(hd))
        scores = jnp.where(seen, scores, -jnp.inf)
        probs = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
        probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
        return jnp.einsum("bkgqs,bksd->bkgqd", probs, v)

    # query head j reads key/value head j // group, and is place j % group in it
    blocks = q.reshape(b, kv_heads, group, s // block, block, hd)
    out = jax.lax.map(one, (blocks.transpose(3, 0, 1, 2, 4, 5),
                            jnp.arange(0, s, block)))
    return out.transpose(1, 2, 3, 0, 4, 5).reshape(b, n_heads, s, hd)


def routing(h, router, top_k: int):
    """(chosen (t, top_k), their gates (t, top_k)) of rows h (t, d): softmax
    scores over all experts, the chosen over their sum."""
    logits = h @ router
    shifted = logits - jnp.max(logits, axis=-1, keepdims=True)
    scores = jnp.exp(shifted) / jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True)
    top, chosen = jax.lax.top_k(scores, top_k)
    return chosen, top / jnp.sum(top, axis=-1, keepdims=True)


def _reglu(m, w_gate, w_up, w_down):
    return (jnp.maximum(m @ w_gate, 0.0) * (m @ w_up)) @ w_down


def experts(m, chosen, gates, w, first_held: int):
    """The held experts' part of the layer on normed rows m (t, d) under the
    routing (chosen, gates): the experts `first_held` and as many as `w`
    stacks, each run over every row and masked."""
    one = jax.checkpoint(_reglu)
    y = jnp.zeros_like(m)
    for e in range(w["w_gate"].shape[0]):
        mine = jnp.sum(jnp.where(chosen == first_held + e, gates, 0.0), axis=-1)
        y = y + mine[:, None] * one(m, w["w_gate"][e], w["w_up"][e], w["w_down"][e])
    return y


def _layer(h, w, spec: tuple, hyper: dict):
    """One layer; `w` its weights (no leading axis), `spec` = (rotary
    positions or none, the window or 0) -> (h, the experts chosen)."""
    b, s, d = h.shape
    rope, window = spec
    H, Hkv, hd, eps = (hyper["heads"], hyper["kv_heads"], hyper["head_dim"],
                       hyper["eps"])
    router = w["router"] if hyper["routers_trained"] else jax.lax.stop_gradient(
        w["router"])
    chosen, gates = routing(h.reshape(b * s, d), router, hyper["top_k"])

    def heads(t, n):
        return t.reshape(b, s, n, hd).transpose(0, 2, 1, 3)

    u = _rms(h, w["ln1_scale"], eps)
    q, k, v = heads(u @ w["wq"], H), heads(u @ w["wk"], Hkv), heads(u @ w["wv"], Hkv)
    if rope:
        q, k = _rot(q, hyper["rope_theta"]), _rot(k, hyper["rope_theta"])
    ctx = _attention(q, k, v, window, hyper["query_block"])
    h = h + ctx.transpose(0, 2, 1, 3).reshape(b, s, H * hd) @ w["wo"]
    m = _rms(h, w["ln2_scale"], eps).reshape(b * s, d)
    y = experts(m, chosen, gates, w, hyper["first_held"])
    return h + y.reshape(b, s, d), chosen


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


def forward(params, tokens, *, layers, **hyper):
    """-> (the final normed hidden states (b, s, d), [the experts chosen
    (tokens, top_k) of each layer]). `layers`: (rotary, window) a layer;
    `hyper`: heads, kv_heads, head_dim, rope_theta, eps, top_k, first_held,
    routers_trained, query_block, position_block. Each layer keeps its input
    and recomputes the rest in the backward pass."""
    h = params["embed"][tokens]
    chosen = []
    for spec, (stack, at) in zip(layers, _runs(layers), strict=True):
        w = jax.tree.map(lambda leaf: leaf[at], params["layers"][stack])
        h, took = jax.checkpoint(
            functools.partial(_layer, spec=spec, hyper=hyper))(h, w)
        chosen.append(took)
    return _rms(h, params["ln_f_scale"], hyper["eps"]), chosen


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
    """-> (loss, the experts chosen (layers, tokens, top_k))."""
    normed, chosen = forward(params, batch[:, :-1], **hyper)
    return _head_loss(normed, params["lm_head"], batch[:, 1:],
                      hyper["position_block"]), jnp.stack(chosen)


@functools.lru_cache(maxsize=None)
def _jitted(hyper: tuple):
    """The jitted loss and gradients for one set of sizes: a second call with
    the same sizes does not compile again."""
    return jax.jit(jax.value_and_grad(functools.partial(loss, **dict(hyper)),
                                      has_aux=True))


def loss_and_grads(params, batch, **hyper):
    """-> ((loss, the experts chosen), gradients): the choices come with the
    loss, so that what counts them compiles no program of its own. Float32
    throughout; on a TPU a float32 matmul runs in lower precision unless this
    is set."""
    with jax.default_matmul_precision("highest"):
        return _jitted(tuple(sorted(hyper.items())))(params, batch)


def chosen_experts(params, batch, **hyper):
    """(layers, tokens, top_k) expert ids the reference's routers choose:
    what the family counts the program's choices against."""
    return loss_and_grads(params, batch, **hyper)[0][1]
