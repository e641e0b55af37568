"""Plain float32 reference of the Kimi-Linear cell's loss, written from the
layer equations of ISSUE 69 (the source's `config.json`, `model_type`
kimi_linear, and the Kimi Linear report, arXiv:2510.26692; the configuration
file lists each reading the two leave open under `assumed`). It imports
nothing from kungfu_tpu; it reads the program's parameter tree: embed,
lm_head, ln_f_scale and `layers`, a tuple with one entry for each run of
successive layers of one kind, the run's layers stacked on a leading axis. A
layer holds ln1_scale, ln2_scale; a KDA mixer's w_q, w_k, w_v, conv_q, conv_k,
conv_v, w_f_a, w_f_b, A_log, dt_bias, w_beta, kda_norm_scale, w_g_a, w_g_b,
wo, or a latent mixer's w_q_up, w_kv_down, kv_latent_norm, w_kv_up, wo; the
dense feed-forward's w_gate, w_up, w_down, or an expert layer's router,
router_bias, w_gate, w_up, w_down (the experts held, on the next axis),
shared_gate, shared_up, shared_down.

    rms(x; s) = x / sqrt(mean(x^2) + eps) * s
    x = x + mixer(rms(x; s1));   x = x + ffn(rms(x; s2))

KDA mixer, H heads of d features for q, k and v alike:

    q = l2(silu(conv(h W_q))) / sqrt(d);  k = l2(silu(conv(h W_k)));  v = silu(conv(h W_v))
    conv(x)_t = sum_{i<K} c_i x_{t-K+1+i}         l2(t) = t / sqrt(|t|^2 + 1e-6), a head
    g_t = -exp(A_log) softplus((h W_fa) W_fb + dt_bias)     a number a key feature
    beta_t = sigmoid(h W_beta)                               a number a head
    S'_t = Diag(exp(g_t)) S_{t-1};  u_t = beta_t (v_t - S'_t^T k_t)
    S_t  = S'_t + k_t u_t^T;        o_t = S_t^T q_t                 S_0 = 0
    y_t  = o_t / sqrt(mean(o_t^2) + eps) w_n sigmoid((h W_ga) W_gb);  out = y W_o

Latent mixer, H heads, no q latent and nothing turned by position:

    [q_nope | q_pe]_head = h W_q                a head at a time, nope + pe features
    [c_kv | k_pe] = h W_kva;   c_kv = rms(c_kv; s_kv)
    [k_nope | v]_head = c_kv W_kvb              a head at a time, nope + value features
    k_head = [k_nope | k_pe]                    the one k_pe for all heads
    a_head = softmax(causal(q_head k_head^T / sqrt(nope + pe))) v_head;  out = a W_o

Feed-forward: the dense W_down (silu(W_gate n) * W_up n), or

    s = sigmoid(n W_r) over all E experts; e_1..e_k the k largest of s + b;
    w_j = scale * s_{e_j} / sum_j s_{e_j}
    y = sum_{j: e_j held here} w_j expert_{e_j}(n) + expert_shared(n)

    loss = mean_t -log softmax(rms(x_L; s_f) W_head^T)[target_t]   over the rows held

The recurrence is run a position at a time (`lax.scan` inside blocks of
positions, a block keeping its first state and running again in the backward
pass), a few heads at a time. The attention is dense, a block of queries at a
time. Every held expert is run over every token, one after another, and
masked; the loss is taken a block of positions at a time. No chunk, no
sub-block, no triangular system, no sort, no groups and no kernel to share a
fault with the program. What the experts on other chips would have added is
left out, as in the program: the share is the model here. The bias b is a
constant: only the choice reads it, and the choice has no derivative.

Departures from the report: none in the equations above. The report trains
with a balancing step on b that the config does not state; b stands still
here (the configuration's `assumed`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


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
    """q, k, g (b, H, s, dk), v (b, H, s, dv), beta (b, H, s) -> o (b, H, s,
    dv): the recurrence, a position at a time, the state's rows decayed each
    by its own feature's exp(g)."""
    b, H, s, dk = q.shape
    block = min(block, s)
    assert s % block == 0, (s, block)

    def position(S, x):
        q_t, k_t, v_t, g_t, beta_t = x  # (b, H, d), (b, H)
        S = jnp.exp(g_t)[..., None] * S
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


def _kda_heads(h, w, hyper: dict):
    """Some heads of the mixer: `w` holds their columns of W_q, W_k, W_v,
    the taps, W_fb, W_gb and W_beta, their A_log and dt_bias and their rows
    of W_o, with W_fa and W_ga whole. -> their part of the mixer's output."""
    b, s, _ = h.shape
    d, eps = hyper["kda_head_dim"], hyper["eps"]
    H = w["A_log"].shape[0]

    def heads(t):  # (b, s, H d) -> (b, H, s, d)
        return t.reshape(b, s, H, d).transpose(0, 2, 1, 3)

    q, k, v = (heads(_silu(_conv(h @ w["w_" + n], w["conv_" + n])))
               for n in "qkv")
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) / jnp.sqrt(
        jnp.float32(d))
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    g = heads((h @ w["w_f_a"]) @ w["w_f_b"] + w["dt_bias"])
    g = -jnp.exp(w["A_log"])[None, :, None, None] * _softplus(g)
    beta = _sigmoid(h @ w["w_beta"]).transpose(0, 2, 1)
    o = delta_rule(q, k, v, g, beta, hyper["position_block"])
    o = o.transpose(0, 2, 1, 3)  # (b, s, H, d)
    gate = _sigmoid((h @ w["w_g_a"]) @ w["w_g_b"]).reshape(b, s, H, d)
    y = (o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
         * w["kda_norm_scale"] * gate)
    return y.reshape(b, s, H * d) @ w["wo"]


def kda_mixer(h, w, hyper: dict):
    """The heads are independent until W_o adds them up: `head_block` heads
    at a time, one block after another, a block keeping its arguments and
    running again in the backward pass."""
    H = w["A_log"].shape[0]
    n = H // min(hyper["head_block"], H)

    def blocks(leaf, axis):
        shape = leaf.shape[:axis] + (n, -1) + leaf.shape[axis + 1:]
        return jnp.moveaxis(leaf.reshape(shape), axis, 0)

    axes = {"w_q": 1, "w_k": 1, "w_v": 1, "conv_q": 1, "conv_k": 1,
            "conv_v": 1, "w_f_b": 1, "w_g_b": 1, "w_beta": 1, "A_log": 0,
            "dt_bias": 0, "wo": 0}
    parts = {name: blocks(w[name], axis) for name, axis in axes.items()}
    whole = {name: w[name] for name in ("w_f_a", "w_g_a", "kda_norm_scale")}

    @jax.checkpoint
    def one(out, part):
        return out + _kda_heads(h, {**part, **whole}, hyper), None

    return jax.lax.scan(one, jnp.zeros_like(h), parts)[0]


def attention(q, k, v, block: int):
    """Causal softmax attention, q and k (b, H, s, hd), v (b, H, s, vd) ->
    (b, H, s, vd), the scores over sqrt(hd), `block` queries at a time; a
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

    blocks = q.reshape(b, n_heads, s // block, block, hd).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(one, (blocks, jnp.arange(0, s, block)))
    return out.transpose(1, 2, 0, 3, 4).reshape(b, n_heads, s, v.shape[-1])


def latent_attention(h, w, hyper: dict):
    """The mixer on normed hidden states h (b, s, d) -> (b, s, d)."""
    b, s, _ = h.shape
    heads, nope, pe, value = (hyper[k] for k in ("heads", "nope", "pe", "value"))
    rank, eps = hyper["kv_rank"], hyper["eps"]
    q = (h @ w["w_q_up"]).reshape(b, s, heads, nope + pe).transpose(0, 2, 1, 3)
    down = h @ w["w_kv_down"]
    c_kv, k_pe = _rms(down[..., :rank], w["kv_latent_norm"], eps), down[..., rank:]
    kv = (c_kv @ w["w_kv_up"]).reshape(b, s, heads, nope + value).transpose(0, 2, 1, 3)
    k_pe = jnp.broadcast_to(k_pe[:, None], (b, heads, s, pe))
    k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
    ctx = attention(q, k, kv[..., nope:], hyper["query_block"])
    return ctx.transpose(0, 2, 1, 3).reshape(b, s, heads * value) @ w["wo"]


def _swiglu(n, w_gate, w_up, w_down):
    return (_silu(n @ w_gate) * (n @ w_up)) @ w_down


def routing(n, router, bias, top_k: int, scale: float):
    """(chosen (t, top_k), their weights (t, top_k)) of normed tokens n: the
    choice on sigmoid scores + bias, the weights from the scores alone."""
    scores = _sigmoid(n @ router)
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, scale * top / jnp.sum(top, axis=-1, keepdims=True)


def experts(n, w, hyper: dict):
    """The expert layer on normed tokens n (t, d) -> (y (t, d), chosen):
    the held experts' part and the shared expert."""
    chosen, weights = routing(n, w["router"], w["router_bias"], hyper["top_k"],
                              hyper["routed_scale"])
    y = _swiglu(n, w["shared_gate"], w["shared_up"], w["shared_down"])

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


def _layer(x, w, hyper: dict):
    """One layer; `w` its weights (no leading axis): a KDA mixer where it has
    W_fa, else the latent one; an expert layer where it has a router, else
    the dense feed-forward. -> (x, chosen or None)."""
    b, s, d = x.shape
    eps = hyper["eps"]
    mixer = kda_mixer if "w_f_a" in w else latent_attention
    x = x + mixer(_rms(x, w["ln1_scale"], eps), w, hyper)
    n = _rms(x, w["ln2_scale"], eps)
    if "router" not in w:
        return x + _swiglu(n, w["w_gate"], w["w_up"], w["w_down"]), None
    y, chosen = experts(n.reshape(b * s, d), w, hyper)
    return x + y.reshape(b, s, d), chosen


def forward(params, batch, **hyper):
    """-> (loss, the final normed hidden states, [the experts chosen (tokens,
    top_k) of each expert layer]). `hyper`: kda_head_dim, heads, nope, pe,
    value, kv_rank, eps, top_k, routed_scale, first_held, query_block,
    position_block, head_block. Each layer keeps its input and recomputes
    the rest in the backward pass."""
    tokens, targets = batch[:, :-1], batch[:, 1:]
    layer = jax.checkpoint(functools.partial(_layer, hyper=hyper))
    x = params["embed"][tokens]
    chosen = []
    for stack in params["layers"]:
        for at in range(stack["ln1_scale"].shape[0]):
            x, took = layer(x, jax.tree.map(lambda leaf: leaf[at], stack))
            if took is not None:
                chosen.append(took)
    normed = _rms(x, params["ln_f_scale"], hyper["eps"])
    return _head_loss(normed, params["lm_head"], targets,
                      hyper["position_block"]), normed, chosen


def _head_loss(normed, head, targets, block: int):
    """mean_t -log softmax(normed_t head^T)[target_t], `block` positions at a
    time (16,384 x 20,480 float32 logits are 1.3 GB, and the softmax holds
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


def chosen_experts(params, batch, **hyper):
    """(expert layers, tokens, top_k) expert ids the reference's router
    chooses: what the family counts the program's choices against."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack(jax.jit(lambda p, b: forward(p, b, **hyper)[2])(
            params, batch))
