"""Plain float32 reference of the transformer cell's loss, written from
the layer equations. It imports nothing from kungfu_tpu.models; it reads
the program's parameter tree (embed, pos_embed, ln_f_scale, layers.{ln1_scale,
ln2_scale, wqkv, wo, w_in, w_out}, the layers stacked on a leading axis).

    x_0   = E[tokens] + P[:S]
    a_l   = x_l + softmax(mask(q k^T / sqrt(hd))) v  W_o     q,k,v = rms(x_l) W_qkv
    x_l+1 = a_l + gelu_tanh(rms(a_l) W_in) W_out
    loss  = mean_t -log softmax(rms(x_L) E^T)[target_t]
    rms(x) = x / sqrt(mean(x^2) + 1e-6) * scale

Departures from BERT (causal mask, RMSNorm, tied head, tanh gelu) are the
program's, listed in configs/bert_base.json under "assumed".
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, scale):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) * scale


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3)))


def loss(params, batch, n_heads: int):
    tokens, targets = batch[:, :-1], batch[:, 1:]
    b, s = tokens.shape
    embed = params["embed"].astype(jnp.float32)
    d = embed.shape[1]
    hd = d // n_heads
    x = embed[tokens] + params["pos_embed"].astype(jnp.float32)[:s]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def block(x, layer):
        h = _rms(x, layer["ln1_scale"])
        q, k, v = jnp.split(h @ layer["wqkv"], 3, axis=-1)
        q, k, v = (t.reshape(b, s, n_heads, hd).transpose(0, 2, 1, 3)
                   for t in (q, k, v))
        scores = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(jnp.float32(hd))
        scores = jnp.where(causal, scores, -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        probs = jnp.exp(scores)
        probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
        ctx = (probs @ v).transpose(0, 2, 1, 3).reshape(b, s, d)
        x = x + ctx @ layer["wo"]
        h = _rms(x, layer["ln2_scale"])
        return x + _gelu_tanh(h @ layer["w_in"]) @ layer["w_out"], None

    # one block, scanned over the stacked layers: unrolled, the twelve
    # layers took the chip's compiler 68 s; scanned, 18 s
    x, _ = jax.lax.scan(block, x, params["layers"])
    logits = _rms(x, params["ln_f_scale"]) @ embed.T
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.mean(picked)


def loss_and_grads(params, batch, n_heads: int):
    """Float32 throughout; on a TPU a float32 matmul runs in lower
    precision unless this is set."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(loss), static_argnums=2)(
            params, batch, n_heads)
