"""Plain float32 reference of the OLMoE cell's loss, written from the layer
equations of `model_type` olmoe's published modelling code. It imports
nothing from kungfu_tpu; it reads the program's parameter tree (embed,
lm_head, ln_f_scale, layers.{ln1_scale, ln2_scale, wqkv, wo, q_norm_scale,
k_norm_scale, router, w_gate, w_up, w_down}, the layers stacked on a
leading axis, the experts on the next).

    x_0   = E[tokens]
    q,k,v = split(rms(x_l) W_qkv);  q = rms(q; s_q), k = rms(k; s_k)   over all D features
    a_l   = x_l + softmax(mask(rope(q) rope(k)^T / sqrt(hd))) v  W_o   per head
    p     = softmax(rms(a_l) W_r)  over all E experts; the top_k largest, as they are
    x_l+1 = a_l + sum_{e in top_k} p_e W_down,e (silu(W_gate,e n) * W_up,e n),  n = rms(a_l)
    loss  = mean_t -log softmax(rms(x_L) W_head^T)[target_t]
            + aux_coef * mean_l E sum_e f_e P_e + z_coef * mean_l mean_t logsumexp(W_r n)^2
    rms(x; s) = x / sqrt(mean(x^2) + eps) * s
    rope(t)   = t cos(theta) + rotate_half(t) sin(theta),  theta_{s,i} = s / base^(2i/hd)

f_e is the share of the T * top_k token-choices that went to expert e and
P_e the mean of p_e over the tokens. Every expert is run over every token
and masked (0/1 times p): 8 times the operations the model needs, and no
sort, no groups and no kernel to share a fault with the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(t, theta):
    """t (b, heads, s, hd): rotate-half over the whole head dimension."""
    s, hd = t.shape[2], t.shape[3]
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    half = jnp.concatenate([-t[..., hd // 2:], t[..., :hd // 2]], axis=-1)
    return t * jnp.cos(angles) + half * jnp.sin(angles)


def routing(n, router, top_k: int):
    """(logits, probs, chosen (t, top_k)) of normed tokens n (t, d)."""
    logits = n @ router
    shifted = logits - jnp.max(logits, axis=-1, keepdims=True)
    probs = jnp.exp(shifted) / jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True)
    _, chosen = jax.lax.top_k(probs, top_k)
    return logits, probs, chosen


def _experts(n, probs, chosen, layer):
    """sum over the chosen experts of p_e * expert_e(n): a loop over all
    experts, each over every token, masked. The loop's body keeps its
    inputs and recomputes its (t, f) temporaries in the backward pass, so
    that 64 experts' worth of them is never alive at once beside 3X of
    parameters."""
    n_experts = probs.shape[-1]
    weight = jnp.sum(jax.nn.one_hot(chosen, n_experts, dtype=n.dtype), axis=1) * probs

    @jax.checkpoint
    def one(acc, expert):
        w_gate, w_up, w_down, w = expert
        gate = n @ w_gate
        y = (gate / (1.0 + jnp.exp(-gate)) * (n @ w_up)) @ w_down
        return acc + w[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(n),
                          (layer["w_gate"], layer["w_up"], layer["w_down"], weight.T))
    return out


def forward(params, batch, *, n_heads: int, top_k: int, eps: float,
            theta: float, aux_coef: float, z_coef: float):
    """-> (loss, the experts chosen (layers, tokens, top_k))."""
    tokens, targets = batch[:, :-1], batch[:, 1:]
    b, s = tokens.shape
    x = params["embed"][tokens]
    d = x.shape[-1]
    hd = d // n_heads
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def block(x, layer):
        h = _rms(x, layer["ln1_scale"], eps)
        q, k, v = jnp.split(h @ layer["wqkv"], 3, axis=-1)
        q = _rms(q, layer["q_norm_scale"], eps)
        k = _rms(k, layer["k_norm_scale"], eps)
        q, k, v = (t.reshape(b, s, n_heads, hd).transpose(0, 2, 1, 3)
                   for t in (q, k, v))
        q, k = _rope(q, theta), _rope(k, theta)
        scores = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(jnp.float32(hd))
        scores = jnp.where(causal, scores, -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        probs = jnp.exp(scores)
        probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
        ctx = (probs @ v).transpose(0, 2, 1, 3).reshape(b, s, d)
        x = x + ctx @ layer["wo"]

        n = _rms(x, layer["ln2_scale"], eps).reshape(b * s, d)
        logits, p, chosen = routing(n, layer["router"], top_k)
        x = x + _experts(n, p, chosen, layer).reshape(b, s, d)
        n_experts = p.shape[-1]
        share = jnp.sum(jax.nn.one_hot(chosen, n_experts, dtype=p.dtype),
                        axis=(0, 1)) / (b * s * top_k)
        balance = n_experts * jnp.sum(share * jnp.mean(p, axis=0))
        top = jnp.max(logits, axis=-1)
        lse = top + jnp.log(jnp.sum(jnp.exp(logits - top[:, None]), axis=-1))
        return x, (balance, jnp.mean(lse * lse), chosen)

    x, (balance, z, chosen) = jax.lax.scan(block, x, params["layers"])
    logits = _rms(x, params["ln_f_scale"], eps) @ params["lm_head"].T
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    total = -jnp.mean(picked) + aux_coef * jnp.mean(balance) + z_coef * jnp.mean(z)
    return total, chosen


def loss(params, batch, **hyper):
    return forward(params, batch, **hyper)[0]


def loss_and_grads(params, batch, **hyper):
    """Float32 throughout; on a TPU a float32 matmul runs in lower
    precision unless this is set."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(functools.partial(loss, **hyper)))(
            params, batch)


def chosen_experts(params, batch, **hyper):
    """(layers, tokens, top_k) expert ids the reference's router chooses:
    what the family counts the program's choices against."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(functools.partial(forward, **hyper))(params, batch)[1]
