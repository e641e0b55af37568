"""Plain float32 reference of the Xing4.0-29B-A4B cell's loss, written from
the layer equations of ISSUE 71: the source's `config.json` (`model_type`
xing4_0) read with DeepSeek-V3's layout for the layer and its rule for YaRN,
and with the mHC paper (arXiv:2512.24880, on hyper-connections,
arXiv:2409.19606) for the residual path; the configuration file lists each
such reading under `assumed`. It imports nothing from kungfu_tpu and nothing
from another reference; it reads the program's parameter tree: embed,
lm_head, ln_f_scale, `layers` (a tuple with one entry for each run of
successive layers of one kind, the run's layers stacked on a leading axis)
and, where the configuration has the module, `mtp` (enorm_scale, hnorm_scale,
eh_proj, ln_f_scale and `layer`, one layer's leaves with no leading axis). A
layer's leaves: ln1_scale, ln2_scale, w_q_down, q_latent_norm, w_q_up,
w_kv_down, kv_latent_norm, w_kv_up, wo, then w_gate, w_up, w_down of the
dense feed-forward, or router, router_bias, w_gate, w_up, w_down (the experts
held, on the next axis), shared_gate, shared_up, shared_down of an expert
layer; and the maps of its two branches, hc1_phi (n C, 2 n + n^2), hc1_a (3),
hc1_b (2 n + n^2) of the mixer's and hc2_* of the feed-forward's.

A position's state is X in R^(n x C), n streams. Entry X[j] = E(t) for every
j. Around a branch F (the branch with its norm):

    r      = vec(X) / sqrt(mean(vec(X)^2) + 1e-6)          vec: stream j at j C .. (j + 1) C
    t      = r Phi                                          2 n + n^2 numbers a position
    H_pre  = sigmoid(a_pre t[:n] + b[:n])
    H_post = 2 sigmoid(a_post t[n:2n] + b[n:2n])
    M      = exp(clip(a_res t[2n:] + b[2n:], lo, hi))       (n, n), row i column j
    20 x:    M <- M / (column sums + eps);  M <- M / (row sums + eps);   H_res = M
    u      = sum_j H_pre[j] X[j];   y = F(u);   X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

    mixer:   F(u) = latent_attention(rms(u; s1)):
    c_q    = rms(h W_qa; s_q)                               (rank 768)
    [q_nope | q_rope]_head = c_q W_qb        a head at a time, 128 + 64 features
    [c_kv | k_r] = h W_kva                   512 + 64 features;  c_kv = rms(c_kv; s_kv)
    [k_nope | v]_head = c_kv W_kvb           a head at a time, 128 + 128 features
    q_head = [q_nope | rot(q_rope)],   k_head = [k_nope | rot(k_r)]   (one k_r for all heads)
    a_head = softmax(causal(q_head k_head^T * mscale^2 / sqrt(192))) v_head;  concat_head(a_head) W_o
    dense:   F(u) = W_down (silu(W_gate n) * W_up n),  n = rms(u; s2)
    experts: s = sigmoid(n W_r) over all E experts; e_1..e_k the k largest of s + b;
             w_j = scale * s_{e_j} / sum_j s_{e_j}
             F(u) = sum_{j: e_j held here} w_j expert_{e_j}(n) + expert_shared(n)
    x      = sum_j X[j]                                     exit
    main   = mean_i -log softmax(rms(x; s_f) W_head^T)_i [t_{i+1}]        over the rows held
    module (where the tree has `mtp`): h'_i = [rms(E(t_{i+1}); s_e) | rms(x_i; s_h)] W_eh,
             n streams of h' through one expert block with maps of its own, their sum z,
             mtp = mean_i -log softmax(rms(z; s_f') W_head^T)_i [t_{i+2}];  loss = main + weight * mtp
    rms(x; s) = x / sqrt(mean(x^2) + eps) * s
    rot(t)    = (t cos(theta) + rotate_half(t) sin(theta)) * attention factor,
    theta_{p,i} = p f_i,  f_i = base^(-2i/64) (1 - g_i) + base^(-2i/64) / factor * g_i,
    g the linear ramp between the features that turn beta_fast and beta_slow
    times over the original positions (YaRN, arXiv:2309.00071)
    mscale    = 0.1 mscale_all_dim ln(factor) + 1;  attention factor =
    (0.1 mscale ln(factor) + 1) / (0.1 mscale_all_dim ln(factor) + 1)

over positions p = 0..S-1. The maps are made a position at a time, the
Sinkhorn loop written out; the attention is dense, the mask written out,
computed a block of queries at a time; every held expert is run over every
token in a Python loop and masked: no sort, no groups, no kernel and no
layout to share a fault with the program. What the experts on other chips
would have added is left out, as in the program: the share is the model here.

Departures from the papers, each the configuration's and noted in its file:
the mHC paper starts the gains a at 0.01 and this tree's are what the state
holds; r carries no learned weight; the streams enter as copies and leave by
their sum (hyper-connections, section 3), and the module's block runs under
streams of its own; H_post carries the factor 2; `eps` stands beside each sum
of a Sinkhorn pass, columns first; DeepSeek-V3 pairs neighbouring rotary
features and this reads rotate-half, as the GLM-4.7-Flash file does.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


# --- the residual path -------------------------------------------------------

def maps_of(X, phi, a, b, hyper: dict):
    """One position's three maps from its streams X (n, c): (H_pre (n,),
    H_post (n,), H_res (n, n))."""
    n = X.shape[0]
    v = X.reshape(-1)
    t = (v / jnp.sqrt(jnp.mean(v * v) + 1e-6)) @ phi
    pre = _sigmoid(a[0] * t[:n] + b[:n])
    post = 2.0 * _sigmoid(a[1] * t[n:2 * n] + b[n:2 * n])
    lo, hi = hyper["clamp"]
    M = jnp.exp(jnp.clip((a[2] * t[2 * n:] + b[2 * n:]).reshape(n, n), lo, hi))
    for _ in range(hyper["sinkhorn_iters"]):
        M = M / (jnp.sum(M, axis=0, keepdims=True) + hyper["hc_eps"])  # columns
        M = M / (jnp.sum(M, axis=1, keepdims=True) + hyper["hc_eps"])  # rows
    return pre, post, M


def around(X, w, branch: str, F, hyper: dict):
    """The streams X (b, s, n, c) around the branch F -> (the streams, what
    F handed back beside its output)."""
    pre, post, res = jax.vmap(jax.vmap(lambda x: maps_of(
        x, w[branch + "_phi"], w[branch + "_a"], w[branch + "_b"], hyper)))(X)
    y, beside = F(jnp.einsum("bsj,bsjc->bsc", pre, X))
    return (jnp.einsum("bsij,bsjc->bsic", res, X)
            + post[..., None] * y[:, :, None, :]), beside


# --- the mixer ---------------------------------------------------------------

def yarn_frequencies(r: int, base: float, yarn):
    """The r / 2 frequencies of the rotated features under YaRN and the
    factor on cos and sin; `yarn` = (factor, original positions, beta_fast,
    beta_slow, mscale, mscale_all_dim) or None: the plain ones and 1."""
    plain = base ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    if yarn is None:
        return plain, 1.0
    factor, original, fast, slow, mscale, mscale_all = yarn

    def feature_that_turns(times):
        return r * math.log(original / (times * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(feature_that_turns(fast)), 0)
    high = min(math.ceil(feature_that_turns(slow)), r - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(r // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    on = lambda m: 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0
    return plain * (1 - ramp) + plain / factor * ramp, on(mscale) / on(mscale_all)


def softmax_scale(hd: int, yarn) -> float:
    """1 / sqrt(hd), under YaRN with `mscale_all_dim` times mscale^2
    (DeepSeek-V2's and V3's published rule)."""
    if yarn is None or not yarn[5] or yarn[0] <= 1:
        return hd ** -0.5
    return (0.1 * yarn[5] * math.log(yarn[0]) + 1.0) ** 2 * hd ** -0.5


def _rot(t, base: float, yarn):
    """t (..., s, r): rotate-half over all r features at positions 0..s-1."""
    s, r = t.shape[-2], t.shape[-1]
    freq, factor = yarn_frequencies(r, base, yarn)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    half = jnp.concatenate([-t[..., r // 2:], t[..., :r // 2]], axis=-1)
    return (t * jnp.cos(angles) + half * jnp.sin(angles)) * factor


def attention(q, k, v, block: int, scale: float):
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
        scores = jnp.einsum("bhqd,bhsd->bhqs", qb, k) * scale
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
    rank, eps, base, yarn = (hyper[k] for k in ("kv_rank", "eps", "rope_theta", "yarn"))
    c_q = _rms(h @ w["w_q_down"], w["q_latent_norm"], eps)
    q = (c_q @ w["w_q_up"]).reshape(b, s, heads, nope + rope).transpose(0, 2, 1, 3)
    down = h @ w["w_kv_down"]
    c_kv, k_r = _rms(down[..., :rank], w["kv_latent_norm"], eps), down[..., rank:]
    kv = (c_kv @ w["w_kv_up"]).reshape(b, s, heads, nope + value).transpose(0, 2, 1, 3)
    q = jnp.concatenate([q[..., :nope], _rot(q[..., nope:], base, yarn)], axis=-1)
    k_r = jnp.broadcast_to(_rot(k_r, base, yarn)[:, None], (b, heads, s, rope))
    k = jnp.concatenate([kv[..., :nope], k_r], axis=-1)
    ctx = attention(q, k, kv[..., nope:], hyper["query_block"],
                    softmax_scale(nope + rope, yarn))
    return ctx.transpose(0, 2, 1, 3).reshape(b, s, heads * value) @ w["wo"]


# --- the feed-forward --------------------------------------------------------

def _swiglu(n, w_gate, w_up, w_down):
    gate = n @ w_gate
    return (gate * _sigmoid(gate) * (n @ w_up)) @ w_down


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
    for e in range(w["w_gate"].shape[0]):  # the experts held here
        mine = jnp.sum(jnp.where(chosen == hyper["first_held"] + e, weights, 0.0),
                       axis=-1)
        y = y + mine[:, None] * _swiglu(n, w["w_gate"][e], w["w_up"][e],
                                        w["w_down"][e])
    return y, chosen


def _block(X, w, hyper: dict):
    """One layer on the streams X (b, s, n, c); `w` its weights (no leading
    axis): an expert layer where it has a router, else the dense
    feed-forward. -> (X, chosen or None)."""
    eps = hyper["eps"]

    def mixer(u):
        return latent_attention(_rms(u, w["ln1_scale"], eps), w, hyper), None

    def feed_forward(u):
        b, s, d = u.shape
        n = _rms(u, w["ln2_scale"], eps)
        if "router" not in w:
            return _swiglu(n, w["w_gate"], w["w_up"], w["w_down"]), None
        y, chosen = experts(n.reshape(b * s, d), w, hyper)
        return y.reshape(b, s, d), chosen

    X, _ = around(X, w, "hc1", mixer, hyper)
    return around(X, w, "hc2", feed_forward, hyper)


def _enter(x, n: int):
    return jnp.broadcast_to(x[:, :, None, :], (*x.shape[:2], n, x.shape[-1]))


@functools.partial(jax.checkpoint, static_argnums=(4,))
def _head_loss(x, scale, head, targets, eps):
    logits = _rms(x, scale, eps) @ head.T
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def forward(params, batch, **hyper):
    """-> ((main loss, the module's loss or None), [the experts chosen
    (tokens, top_k) of each expert layer, the module's last]). batch: ids (b,
    S + 1), or (b, S + 2) where the tree has the module. `hyper`: heads,
    nope, rope, value, kv_rank, rope_theta, yarn, eps, top_k, routed_scale,
    first_held, query_block, streams, sinkhorn_iters, hc_eps, clamp. Each
    layer keeps its input and recomputes the rest in the backward pass."""
    module = "mtp" in params
    s = batch.shape[1] - 1 - module
    tokens, targets = batch[:, :s], batch[:, 1:s + 1]
    block = jax.checkpoint(functools.partial(_block, hyper=hyper))
    X = _enter(params["embed"][tokens], hyper["streams"])
    chosen = []
    for stack in params["layers"]:
        for at in range(stack["ln1_scale"].shape[0]):
            X, took = block(X, jax.tree.map(lambda leaf: leaf[at], stack))
            if took is not None:
                chosen.append(took)
    eps = hyper["eps"]
    x = jnp.sum(X, axis=2)
    main = _head_loss(x, params["ln_f_scale"], params["lm_head"], targets, eps)
    if not module:
        return (main, None), chosen
    mtp = params["mtp"]
    both = jnp.concatenate([_rms(params["embed"][targets], mtp["enorm_scale"], eps),
                            _rms(x, mtp["hnorm_scale"], eps)], axis=-1)
    Z, took = block(_enter(both @ mtp["eh_proj"], hyper["streams"]), mtp["layer"])
    chosen.append(took)
    return (main, _head_loss(jnp.sum(Z, axis=2), mtp["ln_f_scale"],
                             params["lm_head"], batch[:, 2:], eps)), chosen


def loss(params, batch, *, mtp_weight: float, **hyper):
    main, mtp = forward(params, batch, **hyper)[0]
    return main if mtp is None else main + mtp_weight * mtp


def loss_and_grads(params, batch, **hyper):
    """Float32 throughout; on a TPU a float32 matmul runs in lower
    precision unless this is set."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(functools.partial(loss, **hyper)))(
            params, batch)


def chosen_experts(params, batch, *, mtp_weight: float = 0.0, **hyper):
    """(expert layers, tokens, top_k) expert ids the reference's router
    chooses, the module's layer last: what the family counts the program's
    choices against."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack(jax.jit(lambda p, b: forward(p, b, **hyper)[1])(
            params, batch))


def residual_maps(X, w, branch: str, **hyper):
    """(H_pre (b, s, n), H_post (b, s, n), H_res (b, s, n, n)) of one branch
    on streams X (b, s, n, c): what the tests hold the program's op to."""
    with jax.default_matmul_precision("highest"):
        return jax.vmap(jax.vmap(lambda x: maps_of(
            x, w[branch + "_phi"], w[branch + "_a"], w[branch + "_b"], hyper)))(X)
