"""Plain float32 reference of the GLM-4.7-Flash cell's loss, written from the
layer equations of ISSUE 41 (the source's `config.json`, `model_type`
glm4_moe_lite, read with the DeepSeek-V3 family's conventions where it is
silent; the configuration file lists each such reading under `assumed`). It
imports nothing from kungfu_tpu; it reads the program's parameter tree:
embed, lm_head, ln_f_scale, `layers` (a tuple with one entry for each run of
successive layers of one kind, the run's layers stacked on a leading axis)
and `mtp` (enorm_scale, hnorm_scale, eh_proj, ln_f_scale and `layer`, one
layer's leaves with no leading axis). A layer's leaves: ln1_scale, ln2_scale,
w_q_down, q_latent_norm, w_q_up, w_kv_down, kv_latent_norm, w_kv_up, wo, then
w_gate, w_up, w_down of the dense feed-forward, or router, router_bias,
w_gate, w_up, w_down (the experts held, on the next axis), shared_gate,
shared_up, shared_down of an expert layer.

    h      = rms(x_l; s1)
    c_q    = rms(h W_qa; s_q)                                     (rank 768)
    [q_nope | q_rope]_head = c_q W_qb         a head at a time, 192 + 64 features
    [c_kv | k_r] = h W_kva                    512 + 64 features;  c_kv = rms(c_kv; s_kv)
    [k_nope | v]_head = c_kv W_kvb            a head at a time, 192 + 256 features
    q_head = [q_nope | rot(q_rope)],   k_head = [k_nope | rot(k_r)]   (one k_r for all heads)
    a_head = softmax(causal(q_head k_head^T / sqrt(256))) v_head
    y      = x_l + concat_head(a_head) W_o
    n      = rms(y; s2)
    dense:   x_l+1 = y + W_down (silu(W_gate n) * W_up n)
    experts: s = sigmoid(n W_r) over all E experts; e_1..e_k the k largest of s + b;
             w_j = scale * s_{e_j} / sum_j s_{e_j}
             x_l+1 = y + sum_{j: e_j held here} w_j expert_{e_j}(n) + expert_shared(n)
    main   = mean_i -log softmax(rms(x_L; s_f) W_head^T)_i [t_{i+1}]      over the rows held
    h'_i   = [rms(E(t_{i+1}); s_e) | rms(x_L,i; s_h)] W_eh
    z      = block_experts(h')                (a layer of the kind above, weights of its own)
    mtp    = mean_i -log softmax(rms(z; s_f') W_head^T)_i [t_{i+2}]
    loss   = main + weight * mtp
    rms(x; s) = x / sqrt(mean(x^2) + eps) * s
    rot(t)    = t cos(theta) + rotate_half(t) sin(theta),  theta_{p,i} = p base^(-2i / 64)

over positions p = 0..S-1 of a batch of S + 2 ids t. The attention is dense,
the mask written out, computed a block of queries at a time (the scores of
20 heads at 8,192 positions are 5.4 GB at once). Every held expert is run
over every token in a Python loop and masked: no sort, no groups and no
kernel to share a fault with the program. What the experts on other chips
would have added is left out, as in the program: the share is the model here.
The bias b is a constant: only the choice reads it, and the choice has no
derivative.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rot(t, base: float):
    """t (..., s, r): rotate-half over all r features at positions 0..s-1."""
    s, r = t.shape[-2], t.shape[-1]
    freq = base ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    half = jnp.concatenate([-t[..., r // 2:], t[..., :r // 2]], axis=-1)
    return t * jnp.cos(angles) + half * jnp.sin(angles)


def attention(q, k, v, block: int):
    """Causal softmax attention, q and k (b, H, s, hd), v (b, H, s, vd) ->
    (b, H, s, vd), `block` queries at a time; a block keeps its inputs and
    recomputes its scores in the backward pass."""
    b, n_heads, s, hd = q.shape
    block = min(block, s)
    assert s % block == 0, (s, block)

    @jax.checkpoint
    def one(args):
        qb, start = args  # (b, H, block, hd)
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.einsum("bhqd,bhsd->bhqs", qb, k) / jnp.sqrt(jnp.float32(hd))
        scores = jnp.where(seen, scores, -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        probs = jnp.exp(scores)
        probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
        return jnp.einsum("bhqs,bhsd->bhqd", probs, v)

    blocks = q.reshape(b, n_heads, s // block, block, hd).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(one, (blocks, jnp.arange(0, s, block)))
    return out.transpose(1, 2, 0, 3, 4).reshape(b, n_heads, s, v.shape[-1])


def latent_attention(h, w, hyper: dict):
    """The mixer on normed hidden states h (b, s, d) -> (b, s, d)."""
    b, s, _ = h.shape
    heads, nope, rope, value = (hyper[k] for k in ("heads", "nope", "rope", "value"))
    rank, eps, base = hyper["kv_rank"], hyper["eps"], hyper["rope_theta"]
    c_q = _rms(h @ w["w_q_down"], w["q_latent_norm"], eps)
    q = (c_q @ w["w_q_up"]).reshape(b, s, heads, nope + rope).transpose(0, 2, 1, 3)
    down = h @ w["w_kv_down"]
    c_kv, k_r = _rms(down[..., :rank], w["kv_latent_norm"], eps), down[..., rank:]
    kv = (c_kv @ w["w_kv_up"]).reshape(b, s, heads, nope + value).transpose(0, 2, 1, 3)
    q = jnp.concatenate([q[..., :nope], _rot(q[..., nope:], base)], axis=-1)
    k_r = jnp.broadcast_to(_rot(k_r, base)[:, None], (b, heads, s, rope))
    k = jnp.concatenate([kv[..., :nope], k_r], axis=-1)
    ctx = attention(q, k, kv[..., nope:], hyper["query_block"])
    return ctx.transpose(0, 2, 1, 3).reshape(b, s, heads * value) @ w["wo"]


def _swiglu(n, w_gate, w_up, w_down):
    gate = n @ w_gate
    return (gate / (1.0 + jnp.exp(-gate)) * (n @ w_up)) @ w_down


def routing(n, router, bias, top_k: int, scale: float):
    """(chosen (t, top_k), their weights (t, top_k)) of normed tokens n: the
    choice on sigmoid scores + bias, the weights from the scores alone."""
    scores = 1.0 / (1.0 + jnp.exp(-(n @ router)))
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, scale * top / jnp.sum(top, axis=-1, keepdims=True)


def experts(n, w, hyper: dict):
    """The expert layer on normed tokens n (t, d) -> (y (t, d), chosen):
    the held experts' part and the shared expert."""
    chosen, weights = routing(n, w["router"], w["router_bias"], hyper["top_k"],
                              hyper["routed_scale"])
    y = _swiglu(n, w["shared_gate"], w["shared_up"], w["shared_down"])
    for e in range(w["w_gate"].shape[0]):  # the experts held here
        mine = jnp.sum(jnp.where(chosen == hyper["first_held"] + e, weights, 0.0),
                       axis=-1)
        y = y + mine[:, None] * _swiglu(n, w["w_gate"][e], w["w_up"][e],
                                        w["w_down"][e])
    return y, chosen


def _block(x, w, hyper: dict):
    """One layer; `w` its weights (no leading axis): an expert layer where
    it has a router, else the dense feed-forward. -> (x, chosen or None)."""
    b, s, d = x.shape
    eps = hyper["eps"]
    x = x + latent_attention(_rms(x, w["ln1_scale"], eps), w, hyper)
    n = _rms(x, w["ln2_scale"], eps)
    if "router" not in w:
        return x + _swiglu(n, w["w_gate"], w["w_up"], w["w_down"]), None
    y, chosen = experts(n.reshape(b * s, d), w, hyper)
    return x + y.reshape(b, s, d), chosen


@functools.partial(jax.checkpoint, static_argnums=(4,))
def _head_loss(x, scale, head, targets, eps):
    logits = _rms(x, scale, eps) @ head.T
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def forward(params, batch, **hyper):
    """-> ((main loss, MTP loss), [the experts chosen (tokens, top_k) of each
    expert layer, the MTP module's last]). batch: ids (b, S + 2). `hyper`:
    heads, nope, rope, value, kv_rank, rope_theta, eps, top_k, routed_scale,
    first_held, query_block. Each layer keeps its input and recomputes the
    rest in the backward pass."""
    s = batch.shape[1] - 2
    tokens, targets, ahead = batch[:, :s], batch[:, 1:s + 1], batch[:, 2:]
    block = jax.checkpoint(functools.partial(_block, hyper=hyper))
    x = params["embed"][tokens]
    chosen = []
    for stack in params["layers"]:
        for at in range(stack["ln1_scale"].shape[0]):
            x, took = block(x, jax.tree.map(lambda leaf: leaf[at], stack))
            if took is not None:
                chosen.append(took)
    eps = hyper["eps"]
    main = _head_loss(x, params["ln_f_scale"], params["lm_head"], targets, eps)
    mtp = params["mtp"]
    both = jnp.concatenate([_rms(params["embed"][targets], mtp["enorm_scale"], eps),
                            _rms(x, mtp["hnorm_scale"], eps)], axis=-1)
    z, took = block(both @ mtp["eh_proj"], mtp["layer"])
    chosen.append(took)
    return (main, _head_loss(z, mtp["ln_f_scale"], params["lm_head"], ahead,
                             eps)), chosen


def losses(params, batch, *, mtp_weight: float = 0.0, **hyper):
    """(main, MTP), each unweighed, float32 throughout."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, b: forward(p, b, **hyper)[0])(params, batch)


def loss(params, batch, *, mtp_weight: float, **hyper):
    main, mtp = forward(params, batch, **hyper)[0]
    return main + mtp_weight * mtp


def loss_and_grads(params, batch, **hyper):
    """Float32 throughout; on a TPU a float32 matmul runs in lower
    precision unless this is set."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(functools.partial(loss, **hyper)))(
            params, batch)


def chosen_experts(params, batch, *, mtp_weight: float = 0.0, **hyper):
    """(expert layers, tokens, top_k) expert ids the reference's router
    chooses, the MTP module's layer last: what the family counts the
    program's choices against."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack(jax.jit(lambda p, b: forward(p, b, **hyper)[1])(
            params, batch))
