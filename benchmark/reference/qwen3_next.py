"""Plain float32 reference of the Qwen3-Next cell's loss, written from the
layer equations of ISSUE 36 (the source's `config.json`, `model_type`
qwen3_next, read with the transformers library's conventions where it is
silent; the configuration file lists each such reading under `assumed`). It
imports nothing from kungfu_tpu; it reads the program's parameter tree:
embed, lm_head, ln_f_scale, and `layers`, a tuple with one entry for each run
of successive layers of one kind, the run's layers stacked on a leading axis.
A Gated DeltaNet layer holds ln1_scale, w_qkvz, w_ba, conv_w, A_log, dt_bias,
gdn_norm_scale, wo; a gated attention layer ln1_scale, wq, wk, wv,
q_norm_scale, k_norm_scale, wo; both ln2_scale, router, w_gate, w_up, w_down
(the experts held, on the next axis), shared_gate, shared_up, shared_down,
w_shared_gate.

    norm(x; w) = x / sqrt(mean(x^2) + eps) (1 + w)
    x     = x + mixer(norm(x; w1));   x = x + experts(norm(x; w2))

Gated DeltaNet mixer, Hk key heads and Hv = r Hk value heads of size d:

    [q | k | v | z] = h W_qkvz;   [b | a] = h W_ba     (the columns a key head
                                  at a time: its q, k, r heads of v, r of z; r b, r a)
    [q | k | v] = silu(conv([q | k | v])),   conv(x)_t = sum_{i<K} c_i x_{t-K+1+i}
    value head j reads key head j // r
    q_t = q_t / sqrt(|q_t|^2 + 1e-6) / sqrt(d);   k_t = k_t / sqrt(|k_t|^2 + 1e-6)
    beta_t = sigmoid(b_t);   g_t = -exp(A_log) softplus(a_t + dt_bias)
    S'_t = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S'_t^T k_t)
    S_t  = S'_t + k_t u_t^T;  o_t = S_t^T q_t                      S_0 = 0
    y_t  = o_t / sqrt(mean(o_t^2) + eps) w_n silu(z_t);   out = y W_o

Gated attention mixer, H query heads on Hkv key/value heads of size hd:

    [q | gate] = h W_q a head;  k = h W_k;  v = h W_v
    q, k = rope(norm(q; w_q)), rope(norm(k; w_k))      norms over the head
    a_h  = softmax(causal(q_h k_{h // (H / Hkv)}^T / sqrt(hd))) v_{h // (H / Hkv)}
    out  = (a sigmoid(gate)) W_o

rope turns the first `rotary` features of each head by rotate-half, angles s
theta^(-2i / rotary), and passes the rest. Experts:

    p = softmax(n W_r) over all E experts; e_1..e_k the k largest;
    w_j = p_{e_j} / sum_j p_{e_j}
    y = sum_{j: e_j held here} w_j expert_{e_j}(n) + sigmoid(n w_s) expert_shared(n)
    expert(n) = W_down (silu(W_gate n) * W_up n)
    loss = mean_t -log softmax(norm(x_L; w_f) W_head^T)[target_t]  over the rows held

The recurrence is run a position at a time (`lax.scan` inside blocks of
positions, a block keeping its first state and running again in the
backward pass: a state a position of 32 heads at 16,384 positions is 34
GB), a few heads at a time. The attention is dense, a block of queries at a
time. Every held expert is run over every token, one expert after another,
and masked; the loss is taken a block of positions at a time. No chunk, no
triangular system, no sort, no groups and no kernel to share a fault with
the program. What the experts on other chips would have added is left out,
as in the program: the share is the model here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _silu(x):
    return x * _sigmoid(x)


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def _conv(x, taps):
    """x (b, s, channels), taps (K, channels): y_t = sum_i taps_i
    x_{t - K + 1 + i}, zeros before the start; a plain loop over the taps."""
    K, s = taps.shape[0], x.shape[1]
    y = jnp.zeros_like(x)
    for i in range(K):
        back = K - 1 - i
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :s - back]], axis=1)
        y = y + taps[i] * shifted
    return y


def delta_rule(q, k, v, g, beta, block: int):
    """q, k (b, H, s, dk), v (b, H, s, dv), g, beta (b, H, s) -> o (b, H, s,
    dv): the recurrence, a position at a time."""
    b, H, s, dk = q.shape
    block = min(block, s)
    assert s % block == 0, (s, block)

    def position(S, x):
        q_t, k_t, v_t, g_t, beta_t = x  # (b, H, d), (b, H)
        S = jnp.exp(g_t)[..., None, None] * S
        u = beta_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    @jax.checkpoint
    def some(S, xs):
        return jax.lax.scan(position, S, xs)

    def blocks(x):  # (b, H, s, ...) -> (s / block, block, b, H, ...)
        x = jnp.moveaxis(x, 2, 0)
        return x.reshape((s // block, block) + x.shape[1:])

    S0 = jnp.zeros((b, H, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(some, S0, tuple(blocks(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((s,) + o.shape[2:]), 0, 2)


def _delta_heads(h, w, hyper: dict):
    """Some key heads of the mixer with their value heads: `w` holds their
    columns of W_qkvz, W_ba and the taps, their A_log and dt_bias and
    their rows of W_o. -> their part of the mixer's output."""
    b, s, _ = h.shape
    d, eps = hyper["linear_head_dim"], hyper["eps"]
    r = hyper["value_heads"] // hyper["key_heads"]
    Hv = w["A_log"].shape[0]
    Hk = Hv // r
    # the columns lie a key head at a time: q, k, r heads of v, r heads of z
    qkvz = (h @ w["w_qkvz"]).reshape(b, s, Hk, (2 + 2 * r) * d)
    ba = (h @ w["w_ba"]).reshape(b, s, Hk, 2 * r)  # a key head: r of b, r of a
    taps = w["conv_w"].reshape(-1, Hk, (2 + r) * d)
    qkv = jnp.stack([_silu(_conv(qkvz[:, :, j, :(2 + r) * d], taps[:, j]))
                     for j in range(Hk)], axis=2)
    z = qkvz[..., (2 + r) * d:].reshape(b, s, Hv, d)
    q = jnp.repeat(qkv[..., :d], r, axis=2).transpose(0, 2, 1, 3)
    k = jnp.repeat(qkv[..., d:2 * d], r, axis=2).transpose(0, 2, 1, 3)
    v = qkv[..., 2 * d:].reshape(b, s, Hv, d).transpose(0, 2, 1, 3)
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) / jnp.sqrt(
        jnp.float32(d))
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    beta = _sigmoid(ba[..., :r].reshape(b, s, Hv)).transpose(0, 2, 1)
    g = (-jnp.exp(w["A_log"]) * _softplus(ba[..., r:].reshape(b, s, Hv)
                                          + w["dt_bias"])).transpose(0, 2, 1)
    o = delta_rule(q, k, v, g, beta, hyper["position_block"])
    o = o.transpose(0, 2, 1, 3)  # (b, s, Hv, d)
    y = (o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
         * w["gdn_norm_scale"] * _silu(z))
    return y.reshape(b, s, Hv * d) @ w["wo"]


def _delta_mixer(h, w, hyper: dict):
    """The heads are independent until W_o adds them up: `head_block` key
    heads at a time, one block after another, a block keeping its
    arguments and running again in the backward pass (32 heads of
    float32 at 16,384 positions are 0.27 GB an array)."""
    Hk = hyper["key_heads"]
    n = Hk // min(hyper["head_block"], Hk)

    def blocks(leaf, axis):
        shape = leaf.shape[:axis] + (n, -1) + leaf.shape[axis + 1:]
        return jnp.moveaxis(leaf.reshape(shape), axis, 0)

    axes = {"w_qkvz": 1, "w_ba": 1, "conv_w": 1, "A_log": 0, "dt_bias": 0, "wo": 0}
    parts = {name: blocks(w[name], axis) for name, axis in axes.items()}

    @jax.checkpoint
    def one(out, part):
        part = {**part, "gdn_norm_scale": w["gdn_norm_scale"]}
        return out + _delta_heads(h, part, hyper), None

    return jax.lax.scan(one, jnp.zeros_like(h), parts)[0]


def _rope(t, theta: float, rotary: int):
    """t (b, heads, s, hd): rotate-half over the leading `rotary` features,
    the rest as they are."""
    s = t.shape[2]
    freq = theta ** (-jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    turn, rest = t[..., :rotary], t[..., rotary:]
    half = jnp.concatenate([-turn[..., rotary // 2:], turn[..., :rotary // 2]],
                           axis=-1)
    return jnp.concatenate(
        [turn * jnp.cos(angles) + half * jnp.sin(angles), rest], axis=-1)


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


def _attention_mixer(h, w, hyper: dict):
    b, s, _ = h.shape
    H, Hkv, hd, eps = (hyper["heads"], hyper["kv_heads"], hyper["head_dim"],
                       hyper["eps"])
    qg = (h @ w["wq"]).reshape(b, s, H, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = (h @ w["wk"]).reshape(b, s, Hkv, hd)
    v = (h @ w["wv"]).reshape(b, s, Hkv, hd)
    q = _norm(q, w["q_norm_scale"], eps).transpose(0, 2, 1, 3)
    k = _norm(k, w["k_norm_scale"], eps).transpose(0, 2, 1, 3)
    q = _rope(q, hyper["rope_theta"], hyper["rotary"])
    k = _rope(k, hyper["rope_theta"], hyper["rotary"])
    ctx = _attention(q, k, v.transpose(0, 2, 1, 3), hyper["query_block"])
    ctx = ctx.transpose(0, 2, 1, 3) * _sigmoid(gate)
    return ctx.reshape(b, s, H * hd) @ w["wo"]


def _swiglu(n, w_gate, w_up, w_down):
    return (_silu(n @ w_gate) * (n @ w_up)) @ w_down


def routing(n, router, top_k: int):
    """(chosen (t, top_k), their weights (t, top_k), renormalised) of normed
    tokens n."""
    logits = n @ router
    shifted = logits - jnp.max(logits, axis=-1, keepdims=True)
    probs = jnp.exp(shifted) / jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True)
    top, chosen = jax.lax.top_k(probs, top_k)
    return chosen, top / jnp.sum(top, axis=-1, keepdims=True)


def experts(n, w, hyper: dict):
    """The expert layer on normed tokens n (t, d): -> (the held experts'
    part plus the gated shared expert, the experts chosen)."""
    chosen, weights = routing(n, w["router"], hyper["top_k"])
    y = (_sigmoid(n @ w["w_shared_gate"])
         * _swiglu(n, w["shared_gate"], w["shared_up"], w["shared_down"]))

    @jax.checkpoint
    def one(y, expert):  # keeps y, n and the expert's matrices
        e, w_gate, w_up, w_down = expert
        mine = jnp.sum(jnp.where(chosen == hyper["first_held"] + e, weights, 0.0),
                       axis=-1)
        return y + mine[:, None] * _swiglu(n, w_gate, w_up, w_down), None

    held = w["w_gate"].shape[0]  # the experts held here, one after another
    y, _ = jax.lax.scan(one, y, (jnp.arange(held), w["w_gate"], w["w_up"],
                                 w["w_down"]))
    return y, chosen


def _layer(x, w, mixer: str, hyper: dict):
    """One layer; `w` its weights (no leading axis). -> (x, chosen)."""
    b, s, d = x.shape
    h = _norm(x, w["ln1_scale"], hyper["eps"])
    mix = _delta_mixer if mixer == "linear_attention" else _attention_mixer
    x = x + mix(h, w, hyper)
    n = _norm(x, w["ln2_scale"], hyper["eps"]).reshape(b * s, d)
    y, chosen = experts(n, w, hyper)
    return x + y.reshape(b, s, d), chosen


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
    top_k) of each layer]).
    `layers`: "linear_attention" or "full_attention" a layer; `hyper`:
    key_heads, value_heads, linear_head_dim, heads, kv_heads, head_dim,
    rope_theta, rotary, eps, top_k, first_held, query_block,
    position_block, head_block. Each layer keeps its input and recomputes the rest in
    the backward pass."""
    tokens, targets = batch[:, :-1], batch[:, 1:]
    x = params["embed"][tokens]
    chosen = []
    for kind, (stack, at) in zip(layers, _runs(layers), strict=True):
        w = jax.tree.map(lambda leaf: leaf[at], params["layers"][stack])
        x, took = jax.checkpoint(
            functools.partial(_layer, mixer=kind, hyper=hyper))(x, w)
        chosen.append(took)
    normed = _norm(x, params["ln_f_scale"], hyper["eps"])
    return _head_loss(normed, params["lm_head"], targets,
                      hyper["position_block"]), normed, chosen


def _head_loss(normed, head, targets, block: int):
    """mean_t -log softmax(normed_t head^T)[target_t], `block` positions at a
    time (16,384 x 18,992 float32 logits are 1.2 GB, and the softmax holds
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
    """(layers, tokens, top_k) expert ids the reference's router chooses:
    what the family counts the program's choices against."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack(jax.jit(lambda p, b: forward(p, b, **hyper)[2])(
            params, batch))
