"""Plain float32 reference of the Nemotron-H cell's loss, written from the
layer equations of ISSUE 43 (the source's `config.json`, `model_type`
nemotron_h, read with the Nemotron-H report, arXiv:2504.03624, and Mamba-2,
arXiv:2405.21060, where it is silent; the configuration file lists each such
reading under `assumed`). It imports nothing from kungfu_tpu; it reads the
program's parameter tree: embed, lm_head, ln_f_scale, and `layers`, a tuple
with one entry for each run of successive layers of one kind, the run's
layers stacked on a leading axis. Every layer is one residual branch behind
one norm, by the letter of `hybrid_override_pattern`:

    norm(x; w) = x / sqrt(mean(x^2) + eps) w;     x = x + f(norm(x; w))

`M`, the Mamba-2 mixer (ln1_scale, w_ssm_in, conv_w, conv_b, dt_bias, A_log,
D_skip, ssm_norm_scale, wo): H heads of P features, a state of N a feature, G
groups of H / G heads that share B and C:

    [z | xBC | dt] = h W_in                  (H P | H P + 2 G N | H columns)
    xBC = silu(conv(xBC) + b),   conv(x)_t = sum_{i<K} c_i x_{t-K+1+i}
    [x | B | C] = xBC            (H heads of P | G groups of N | G groups of N)
    Delta_t = softplus(dt_t + dt_bias);   A = -exp(A_log)     a number a head
    H_t = exp(Delta_t A) H_{t-1} + Delta_t x_t B_t^T          H_0 = 0, (P, N)
    y_t = H_t C_t + D x_t                    head j reads group j // (H / G)
    y = rms_G(y silu(z)) w_n;   out = y W_out

rms_G is over each of the G groups of H P / G features: the gate first, then
the norm. `*`, attention (ln1_scale, wq, wk, wv, wo), no position signal:

    a_h = softmax(causal(q_h k_{h // (H / Hkv)}^T / sqrt(hd))) v_{h // (H / Hkv)}

`E`, the expert layer (ln2_scale, router, router_bias, w_up, w_down of the
experts held, shared_up, shared_down):

    s = sigmoid(n W_r) over all E experts; e_1..e_k the k largest of s + b;
    w_j = scale s_{e_j} / sum_j s_{e_j}
    y = sum_{j: e_j held here} w_j expert_{e_j}(n) + expert_shared(n)
    expert(n) = W_down (relu(W_up n))^2
    loss = mean_t -log softmax(norm(x_L; w_f) W_head^T)[target_t]  over the rows held

The recurrence is run a position at a time (`lax.scan` inside blocks of
positions, a block keeping its first state and running again in the backward
pass). The attention is dense, a block of queries at a time. Every held
expert is run over every token, one expert after another, and masked; the
loss is taken a block of positions at a time. No chunk, no running sum of
decays, no sort, no groups of rows and no kernel to share a fault with the
program. What the experts on other chips would have added is left out, as in
the program: the share is the model here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def _norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _silu(x):
    return x * _sigmoid(x)


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def conv(x, taps, bias):
    """x (b, s, channels), taps (K, channels), bias (channels,): y_t = sum_i
    taps_i x_{t - K + 1 + i} + bias, zeros before the start; a plain loop
    over the taps."""
    K, s = taps.shape[0], x.shape[1]
    y = jnp.zeros_like(x) + bias
    for i in range(K):
        back = K - 1 - i
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :s - back]], axis=1)
        y = y + taps[i] * shifted
    return y


def ssm_recurrence(x, B, C, delta, A, block: int):
    """x (b, s, H, P), B and C (b, s, G, N), delta (b, s, H), A (H,) -> y (b,
    s, H, P): H_t = exp(delta_t A) H_{t-1} + delta_t x_t B_t^T, y_t = H_t
    C_t, a position at a time; head j reads group j // (H / G)."""
    b, s, H, P = x.shape
    G, N = B.shape[2:]
    block = min(block, s)
    assert s % block == 0 and H % G == 0, (s, block, H, G)
    group = jnp.arange(H) // (H // G)

    def position(state, at):
        x_t, B_t, C_t, d_t = at  # (b, H, P), (b, G, N), (b, G, N), (b, H)
        B_t, C_t = B_t[:, group], C_t[:, group]  # (b, H, N)
        state = (jnp.exp(d_t * A)[..., None, None] * state
                 + (d_t[..., None] * x_t)[..., :, None] * B_t[..., None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, C_t)

    @jax.checkpoint
    def some(state, xs):
        return jax.lax.scan(position, state, xs)

    def blocks(t):  # (b, s, ...) -> (s / block, block, b, ...)
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((s // block, block) + t.shape[1:])

    _, y = jax.lax.scan(some, jnp.zeros((b, H, P, N), jnp.float32),
                        tuple(blocks(t) for t in (x, B, C, delta)))
    return jnp.moveaxis(y.reshape((s,) + y.shape[2:]), 0, 1)


def mamba_mixer(h, w, hyper: dict):
    b, s, _ = h.shape
    H, P, N, G = (hyper["ssm_heads"], hyper["ssm_head_dim"], hyper["ssm_state"],
                  hyper["ssm_groups"])
    inner, bc = H * P, G * N
    zxbcdt = h @ w["w_ssm_in"]
    z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:2 * inner + 2 * bc],
                  zxbcdt[..., 2 * inner + 2 * bc:])
    xbc = _silu(conv(xbc, w["conv_w"], w["conv_b"]))
    x = xbc[..., :inner].reshape(b, s, H, P)
    B = xbc[..., inner:inner + bc].reshape(b, s, G, N)
    C = xbc[..., inner + bc:].reshape(b, s, G, N)
    delta = _softplus(dt + w["dt_bias"])
    y = ssm_recurrence(x, B, C, delta, -jnp.exp(w["A_log"]),
                       hyper["position_block"])
    y = (y + w["D_skip"][:, None] * x).reshape(b, s, inner) * _silu(z)
    y = y.reshape(b, s, G, inner // G)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + hyper["eps"])
    return (y.reshape(b, s, inner) * w["ssm_norm_scale"]) @ w["wo"]


def _attention(q, k, v, block: int):
    """q (b, H, s, hd), k and v (b, H / g, s, hd) -> (b, H, s, hd), causal,
    `block` queries at a time; a block keeps its inputs and recomputes its
    scores in the backward pass."""
    b, n_heads, s, hd = q.shape
    kv_heads = k.shape[1]
    group = n_heads // kv_heads
    block = min(block, s)
    assert s % block == 0, (s, block)

    @jax.checkpoint
    def one(args):
        qb, start = args  # (b, kv heads, group, block, hd)
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


def attention_mixer(h, w, hyper: dict):
    """No rotary pass and no position signal of any kind."""
    b, s, _ = h.shape
    H, Hkv, hd = hyper["heads"], hyper["kv_heads"], hyper["head_dim"]
    q = (h @ w["wq"]).reshape(b, s, H, hd).transpose(0, 2, 1, 3)
    k = (h @ w["wk"]).reshape(b, s, Hkv, hd).transpose(0, 2, 1, 3)
    v = (h @ w["wv"]).reshape(b, s, Hkv, hd).transpose(0, 2, 1, 3)
    ctx = _attention(q, k, v, hyper["query_block"])
    return ctx.transpose(0, 2, 1, 3).reshape(b, s, H * hd) @ w["wo"]


def _relu2(n, w_up, w_down):
    return jnp.square(jnp.maximum(n @ w_up, 0.0)) @ w_down


def routing(n, router, bias, top_k: int, scale: float):
    """(chosen (t, top_k), their weights (t, top_k)) of normed tokens n: the
    choice on the sigmoid scores plus the bias, the weights the chosen
    scores, without it, over their sum, times `scale`."""
    scores = _sigmoid(n @ router)
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, scale * top / jnp.sum(top, axis=-1, keepdims=True)


def experts(n, w, hyper: dict):
    """The expert layer on normed tokens n (t, d): -> (the held experts'
    part plus the shared expert, the experts chosen)."""
    chosen, weights = routing(n, w["router"], w["router_bias"], hyper["top_k"],
                              hyper["routed_scale"])
    y = _relu2(n, w["shared_up"], w["shared_down"])

    @jax.checkpoint
    def one(y, expert):  # keeps y, n and the expert's matrices
        e, w_up, w_down = expert
        mine = jnp.sum(jnp.where(chosen == hyper["first_held"] + e, weights, 0.0),
                       axis=-1)
        return y + mine[:, None] * _relu2(n, w_up, w_down), None

    held = w["w_up"].shape[0]  # the experts held here, one after another
    y, _ = jax.lax.scan(one, y, (jnp.arange(held), w["w_up"], w["w_down"]))
    return y, chosen


def _layer(x, w, kind: str, hyper: dict):
    """One layer, one branch; `w` its weights (no leading axis). -> (x, the
    experts chosen or None)."""
    b, s, d = x.shape
    if kind == EXPERTS:
        n = _norm(x, w["ln2_scale"], hyper["eps"]).reshape(b * s, d)
        y, chosen = experts(n, w, hyper)
        return x + y.reshape(b, s, d), chosen
    h = _norm(x, w["ln1_scale"], hyper["eps"])
    mix = mamba_mixer if kind == MAMBA else attention_mixer
    return x + mix(h, w, hyper), None


def _runs(kinds):
    """[(stack, index in it)] a layer: successive layers of one kind are one
    stack of the program's tree."""
    places, stack, at = [], -1, 0
    for i, kind in enumerate(kinds):
        if i and kind == kinds[i - 1]:
            at += 1
        else:
            stack, at = stack + 1, 0
        places.append((stack, at))
    return places


def forward(params, batch, *, layers, **hyper):
    """-> (loss, the final normed hidden states, [the experts chosen (tokens,
    top_k) of each expert layer]). `layers`: "M", "E" or "*" a layer;
    `hyper`: ssm_heads, ssm_head_dim, ssm_state, ssm_groups, heads, kv_heads,
    head_dim, eps, top_k, routed_scale, first_held, query_block,
    position_block. Each layer keeps its input and recomputes the rest in the
    backward pass."""
    tokens, targets = batch[:, :-1], batch[:, 1:]
    x = params["embed"][tokens]
    chosen = []
    for kind, (stack, at) in zip(layers, _runs(layers), strict=True):
        w = jax.tree.map(lambda leaf: leaf[at], params["layers"][stack])
        x, took = jax.checkpoint(
            functools.partial(_layer, kind=kind, hyper=hyper))(x, w)
        if took is not None:
            chosen.append(took)
    normed = _norm(x, params["ln_f_scale"], hyper["eps"])
    return _head_loss(normed, params["lm_head"], targets,
                      hyper["position_block"]), normed, chosen


def _head_loss(normed, head, targets, block: int):
    """mean_t -log softmax(normed_t head^T)[target_t], `block` positions at a
    time (8,192 x 16,384 float32 logits are 0.5 GB, and the softmax holds
    four such arrays)."""
    b, s, d = normed.shape
    block = min(block, s)
    assert s % block == 0, (s, block)

    @jax.checkpoint
    def some(total, xs):
        rows, picks = xs  # (b, block, d), (b, block)
        logits = rows @ head.T
        shifted = logits - jnp.max(logits, axis=-1, keepdims=True)
        logp = shifted - jnp.log(jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True))
        picked = jnp.take_along_axis(logp, picks[..., None], axis=-1)
        return total - jnp.sum(picked), None

    total, _ = jax.lax.scan(
        some, jnp.float32(0.0),
        (normed.reshape(b, s // block, block, d).transpose(1, 0, 2, 3),
         targets.reshape(b, s // block, block).transpose(1, 0, 2)))
    return total / (b * s)


def loss(params, batch, **hyper):
    return forward(params, batch, **hyper)[0]


def loss_and_grads(params, batch, **hyper):
    """Float32 throughout; on a TPU a float32 matmul runs in lower
    precision unless this is set."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(functools.partial(loss, **hyper)))(
            params, batch)


def logits(params, batch, **hyper):
    """(b, s, vocabulary) float32: for the tests' sizes."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, b: forward(p, b, **hyper)[1]
                       @ p["lm_head"].T)(params, batch)


def chosen_experts(params, batch, **hyper):
    """(expert layers, tokens, top_k) expert ids the reference's router
    chooses: what the family counts the program's choices against."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack(jax.jit(lambda p, b: forward(p, b, **hyper)[2])(
            params, batch))
