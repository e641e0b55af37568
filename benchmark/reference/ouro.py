"""Plain float32 reference of the Ouro cell's loss, written from the layer
equations of ISSUE 48 (the published `modeling_ouro.py` and the paper,
"Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741, as
the issue's writer recalled them with no network to read them again: the
configuration file lists each such reading under `assumed`). It imports
nothing from kungfu_tpu; it reads the program's parameter tree: embed,
lm_head, ln_f_scale, exit_gate_w (D, 1), exit_gate_b (), and `layers`, the
L layers stacked on a leading axis: ln1_scale, ln1_post_scale, ln2_scale,
ln2_post_scale, wq, wk, wv, wo, w_gate, w_up, w_down.

    one layer, four norms:
    a     = h + N2(Attn(N1(h)))            N1 ln1_scale, N2 ln1_post_scale
    h'    = a + N4(FFN(N3(a)))             N3 ln2_scale, N4 ln2_post_scale
    Attn(x) = concat_h(softmax(causal(rope(q_h) rope(k_h)^T / sqrt(hd))) v_h) W_o,
              q, k, v = x W_q, x W_k, x W_v  as H heads of hd
    FFN(x)  = W_down (silu(x W_gate) * x W_up)
    N(x; s) = x / sqrt(mean(x^2) + eps) * s
    rope(t) = t cos(theta) + rotate_half(t) sin(theta),  theta_{s,i} = s / base^(2i/hd)

    the loop, T times over the same L layers:
    x_0   = E[tokens]
    u_t   = Layers(x_{t-1});  x_t = N_f(u_t)      the final norm, every loop step
    l_t   = -log softmax(x_t W_head^T)[target]    a position
    g_t   = x_t w_g + b_g;  lambda_t = sigmoid(g_t)
    p_t   = lambda_t prod_{j<t} (1 - lambda_j)  for t < T,   p_T = prod_{j<T} (1 - lambda_j)
    loss  = mean over positions of [ sum_t p_t l_t - beta H(p) ],  H(p) = -sum_t p_t log p_t

The loops over the loop steps and over the layers are Python's, so a loop
step may be given weights of its own (`loop_layers`: what the tests sum a
shared leaf's gradient from), and `loss_and_grads` takes the chain rule a
loop step at a time. The attention is dense, the mask written out,
computed a block of queries at a time (the scores of one layer, 16 heads at
4,096 positions, are 1.07 GB at once). A departure from ISSUE 48's "no
checkpoint": a block of queries, a layer application and a head pass each
keep their inputs and are run again in the backward pass, as the other
references' are: 32 layer applications' probabilities are 34 GB, and what is
kept changes no number.
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


def _attention(q, k, v, block: int):
    """q, k, v (b, H, s, hd) -> (b, H, s, hd), `block` queries at a time; a
    block keeps its inputs and recomputes its scores in the backward pass."""
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

    blocks = q.reshape(b, n_heads, s // block, block, hd)
    out = jax.lax.map(one, (blocks.transpose(2, 0, 1, 3, 4),
                            jnp.arange(0, s, block)))
    return out.transpose(1, 2, 0, 3, 4).reshape(b, n_heads, s, hd)


def layer(x, w, hyper: dict):
    """One layer; `w` its weights (no leading axis)."""
    b, s, d = x.shape
    n_heads, eps = hyper["n_heads"], hyper["eps"]
    hd = w["wq"].shape[-1] // n_heads

    def heads(t):
        return t.reshape(b, s, n_heads, hd).transpose(0, 2, 1, 3)

    h = _rms(x, w["ln1_scale"], eps)
    q = _rope(heads(h @ w["wq"]), hyper["theta"])
    k = _rope(heads(h @ w["wk"]), hyper["theta"])
    ctx = _attention(q, k, heads(h @ w["wv"]), hyper["query_block"])
    attn = ctx.transpose(0, 2, 1, 3).reshape(b, s, n_heads * hd) @ w["wo"]
    x = x + _rms(attn, w["ln1_post_scale"], eps)

    h = _rms(x, w["ln2_scale"], eps)
    gate = h @ w["w_gate"]
    ffn = (gate / (1.0 + jnp.exp(-gate)) * (h @ w["w_up"])) @ w["w_down"]
    return x + _rms(ffn, w["ln2_post_scale"], eps)


@jax.checkpoint
def _position_losses(x, head, targets):
    """-log softmax(x W_head^T)[target], a number a position."""
    logits = x @ head.T
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def exit_shares(gates):
    """p_1..p_T (T, ...) from the gates g_1..g_{T-1}: the last takes what
    the others left."""
    left, shares = 1.0, []
    for g in gates:
        lam = 1.0 / (1.0 + jnp.exp(-g))
        shares.append(lam * left)
        left = left * (1.0 - lam)
    return jnp.stack(shares + [left])


def loop_step(weights, x, hyper: dict):
    """One loop step: the layers of `weights["layers"]` (stacked) in order,
    then the model's final norm; the normed state is what the next loop step
    reads. Each layer application keeps its input and is run again in the
    backward pass."""
    one = jax.checkpoint(functools.partial(layer, hyper=hyper))
    stacked = weights["layers"]
    for at in range(stacked["wq"].shape[0]):
        x = one(x, jax.tree.map(lambda leaf: leaf[at], stacked))
    return _rms(x, weights["ln_f_scale"], hyper["eps"])


def exits(top, states, targets, beta: float):
    """The loss from the loop steps' normed states x_1..x_T and `top` =
    {lm_head, exit_gate_w, exit_gate_b} -> {"loss", "loop" (T,) the loop
    steps' mean cross-entropy, "exit_share" (T,) their mean shares,
    "exit_entropy"}."""
    losses = jnp.stack([_position_losses(x, top["lm_head"], targets)
                        for x in states])
    gates = [(x @ top["exit_gate_w"])[..., 0] + top["exit_gate_b"]
             for x in states[:-1]]  # the last loop step's is read by nothing
    p = exit_shares(gates)
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    return {"loss": jnp.mean(jnp.sum(p * losses, axis=0) - beta * entropy),
            "loop": jnp.mean(losses, axis=(1, 2)),
            "exit_share": jnp.mean(p, axis=(1, 2)),
            "exit_entropy": jnp.mean(entropy)}


def _split(params):
    """(what a loop step reads, what the loss reads of the states)."""
    return ({"layers": params["layers"], "ln_f_scale": params["ln_f_scale"]},
            {k: params[k] for k in ("lm_head", "exit_gate_w", "exit_gate_b")})


def forward(params, batch, *, loop_steps: int, beta: float, loop_layers=None,
            **hyper):
    """-> `exits`' parts and "logits" of the last loop step. `hyper`:
    n_heads, eps, theta, query_block. `loop_layers`: a stacked tree of layers
    a loop step, in the place of `params["layers"]` for all."""
    tokens, targets = batch[:, :-1], batch[:, 1:]
    weights, top = _split(params)
    stacks = loop_layers or [params["layers"]] * loop_steps
    assert len(stacks) == loop_steps
    states = [params["embed"][tokens]]
    for stacked in stacks:
        states.append(loop_step({**weights, "layers": stacked}, states[-1], hyper))
    return {**exits(top, states[1:], targets, beta),
            "logits": states[-1] @ params["lm_head"].T}


def loss(params, batch, **hyper):
    return forward(params, batch, **hyper)["loss"]


def loss_and_grads(params, batch, *, loop_steps: int, beta: float, **hyper):
    """`jax.value_and_grad(loss)`, the chain rule a loop step at a time and
    each piece a program of its own: the loop steps forward, keeping each
    one's input; the loss and its gradients in the states and in `top`; then
    back through the loop steps, last to first, a shared weight's gradient
    the sum over the steps. As one program the cell's 32 layer applications
    want 10.6 GiB beside the 4.6 the harness holds of the chip's 15.75; a
    loop step's 8 want a quarter (`benchmark/aot_check.py`-style compile, PR
    48). Float32 throughout; on a TPU a float32 matmul runs in lower
    precision unless this is set."""
    tokens, targets = batch[:, :-1], batch[:, 1:]
    weights, top = _split(params)
    step = functools.partial(loop_step, hyper=hyper)

    @jax.jit
    def back(weights, x, ct):
        return jax.vjp(step, weights, x)[1](ct)

    with jax.default_matmul_precision("highest"):
        states, ahead = [params["embed"][tokens]], jax.jit(step)
        for _ in range(loop_steps):
            states.append(ahead(weights, states[-1]))
        value, (d_top, d_states) = jax.jit(jax.value_and_grad(
            lambda top, states: exits(top, states, targets, beta)["loss"],
            argnums=(0, 1)))(top, states[1:])
        d_weights, ct = None, jnp.zeros_like(states[0])
        for t in reversed(range(loop_steps)):
            d_step, ct = back(weights, states[t], ct + d_states[t])
            d_weights = d_step if d_weights is None else jax.tree.map(
                jnp.add, d_weights, d_step)
        d_embed = jnp.zeros_like(params["embed"]).at[tokens].add(ct)
    return value, {**d_weights, **d_top, "embed": d_embed}
