"""Plain float32 reference of the Keye-VL-2.0-30B-A3B cell's loss (the
language model), written from the layer equations of ISSUE 61 (the source's
`config.json`, `model_type` KeyeVL2, read with DeepSeek-V3.2-Exp's report
"DeepSeek Sparse Attention" where the config names a mechanism and not its
equations; the configuration file lists each such reading under `assumed`).
It imports nothing from kungfu_tpu and nothing from the other references; it
reads the program's parameter tree: embed, lm_head, ln_f_scale, and `layers`,
the layers stacked on a leading axis: ln1_scale, ln2_scale, wq, wk, wv, wo,
q_norm_scale, k_norm_scale, index_wq, index_wk, index_w, index_ln_scale,
index_ln_bias, router, w_gate, w_up, w_down (the experts held, on the next
axis).

    every layer:  u = rms(h; w_1);  h = h + mix(u);  h = h + moe(rms(h; w_2))
    rms(x; w) = x / sqrt(mean(x^2) + eps) w

    projections:  q = u W_q (H heads of hd), k = u W_k, v = u W_v (Hkv heads)
                  q = rot(rms(q; w_qn)), k = rot(rms(k; w_kn))     the norm over a head's hd
                  rot(t) = t cos(theta) + rotate_half(t) sin(theta), theta_{p,i} = p base^(-2i / width)
    indexer:      ub = stop_gradient(u)
                  qI = rot(ub W_qI) as (S, Hi, di);  kI = rot(LN(ub W_kI)) (S, di)
                  LN(x) = (x - mean) / sqrt(var + eps) g + b
                  w = ub W_w / sqrt(Hi) / sqrt(di)
                  I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])
    choice:       C_t = the min(t + 1, K) positions s <= t of the largest I[t, s]
                  (`lax.top_k` of the row with later keys at -inf: a tie to the lower position)
    core:         a[t, j, s] = softmax over s in C_t of q[t, j] . k[s, j // (H / Hkv)] / sqrt(hd)
                  o[t, j] = sum over s in C_t of a[t, j, s] v[s, j // (H / Hkv)];  mix = concat_j(o) W_o
    indexer loss: p[t, s] = stop_gradient(mean_j a[t, j, s])
                  LI = mean_t sum over s in C_t of p log p - p log_softmax over C_t of I[t, .]
    experts:      s = softmax(n W_r) over all E experts; e_1..e_k the k largest;
                  w_j = s_{e_j} / sum_j s_{e_j};  moe = sum_{j: e_j held here} w_j expert_{e_j}(n)
                  expert(n) = W_down (silu(W_gate n) * W_up n)
    loss = mean_t -log softmax(rms(h_L; w_f) W_head^T)[id_{t+1}] + weight * sum_l LI_l

over positions 0..S-1 of a batch of S + 1 ids, the rows of the vocabulary
held here. Where this departs from the published description, a line each:
- the indexer reads the layer's normed input (the report takes q from its q
  latent, which this model has not);
- LN on the indexer's key, 1 / sqrt(Hi) / sqrt(di) on its weights, and its
  rotation over all di features are the published implementation's, which
  rotates the leading 64 of its 128 and this model's indexer head is 64;
- `indexer_num_kv_heads` 1 is the one key of all Hi heads;
- zeros of either sign are one value in the choice (`lax.top_k` orders them
  by position);
- the three position streams of `mrope_section` are one on text: the plain
  rotation;
- what the experts on other chips would have added is left out, as in the
  program: the share is the model here.

The indexer's scores, the choice, the core and the indexer's loss are
computed a block of rows at a time, each block through the (H, rows, S)
probabilities, dense under the mask: no kernel, no search over bits and no
online softmax to share a fault with the program. A block keeps its inputs
and runs again in the backward pass; every held expert is run over every
token in a Python loop and masked.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _layer_norm(x, scale, bias, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale + bias


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _rot(t, base: float, axis: int = -2):
    """Rotate-half over all of t's last axis at positions 0..s-1 along `axis`."""
    s, r = t.shape[axis], t.shape[-1]
    freq = base ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    if axis != -2:  # (.., s, heads, r): the same angles for every head
        angles = angles[:, None, :]
    half = jnp.concatenate([-t[..., r // 2:], t[..., :r // 2]], axis=-1)
    return t * jnp.cos(angles) + half * jnp.sin(angles)


def index_inputs(u, w, hyper: dict):
    """(qI (b, s, Hi, di), kI (b, s, di), w (b, s, Hi)) of normed states u."""
    b, s, _ = u.shape
    Hi, di = hyper["index_heads"], hyper["index_dim"]
    ub = jax.lax.stop_gradient(u)
    qI = _rot((ub @ w["index_wq"]).reshape(b, s, Hi, di), hyper["rope_theta"], 1)
    kI = _rot(_layer_norm(ub @ w["index_wk"], w["index_ln_scale"],
                          w["index_ln_bias"], hyper["eps"]), hyper["rope_theta"])
    return qI, kI, (ub @ w["index_w"]) / jnp.sqrt(jnp.float32(Hi * di))


def _chosen(scores, start, keys: int):
    """The choice (b, r, s) bool of a block of rows' scores (b, r, s), row i
    the query at position start + i: `lax.top_k` over the keys at or before
    it."""
    b, r, s = scores.shape
    valid = jnp.arange(s)[None, :] <= (start + jnp.arange(r))[:, None]
    clean = jnp.where(scores == 0.0, 0.0, scores)  # -0.0 is 0.0
    _, taken = jax.lax.top_k(jnp.where(valid, clean, -jnp.inf), min(keys, s))
    marked = jnp.zeros((b, r, s), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(r)[None, :, None], taken].set(True)
    return marked & valid


def sparse_mixer(u, w, hyper: dict):
    """-> (the mixer's output (b, s, d), the indexer's loss, the choice (b, s,
    s) bool), `row_block` queries at a time."""
    b, s, _ = u.shape
    H, Hkv, hd = hyper["heads"], hyper["kv_heads"], hyper["head_dim"]
    eps, base, keys = hyper["eps"], hyper["rope_theta"], hyper["keys"]
    block = min(hyper["row_block"], s)
    assert s % block == 0 and H % Hkv == 0, (s, block, H, Hkv)

    def heads(t, n):
        return t.reshape(b, s, n, hd).transpose(0, 2, 1, 3)

    q = _rot(_rms(heads(u @ w["wq"], H), w["q_norm_scale"], eps), base)
    k = _rot(_rms(heads(u @ w["wk"], Hkv), w["k_norm_scale"], eps), base)
    v = heads(u @ w["wv"], Hkv)
    k, v = (jnp.repeat(t, H // Hkv, axis=1) for t in (k, v))
    qI, kI, weight = index_inputs(u, w, hyper)

    @jax.checkpoint
    def rows(q, qI, weight, k, v, kI, start):
        scores = jnp.einsum("brj,bjrs->brs", weight, jax.nn.relu(
            jnp.einsum("brjd,bsd->bjrs", qI, kI)))
        chosen = _chosen(jax.lax.stop_gradient(scores), start, keys)
        logits = jnp.einsum("bhrd,bhsd->bhrs", q, k) / jnp.sqrt(jnp.float32(hd))
        logits = jnp.where(chosen[:, None], logits, -jnp.inf)
        a = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
        a = a / jnp.sum(a, axis=-1, keepdims=True)  # (b, H, r, s)
        out = jnp.einsum("bhrs,bhsd->bhrd", a, v)
        p = jax.lax.stop_gradient(jnp.mean(a, axis=1))
        masked = jnp.where(chosen, scores, -jnp.inf)
        shifted = masked - jnp.max(masked, axis=-1, keepdims=True)
        log_soft = shifted - jnp.log(jnp.sum(jnp.exp(shifted), axis=-1,
                                             keepdims=True))
        kl = jnp.sum(jnp.where(chosen, jax.scipy.special.xlogy(p, p)
                               - p * jnp.where(chosen, log_soft, 0.0), 0.0))
        return out, kl, chosen

    def one(xs):
        qb, qIb, wb, start = xs
        return rows(qb, qIb, wb, k, v, kI, start)

    n = s // block
    out, kl, chosen = jax.lax.map(one, (
        q.reshape(b, H, n, block, hd).transpose(2, 0, 1, 3, 4),
        qI.reshape(b, n, block, *qI.shape[2:]).swapaxes(0, 1),
        weight.reshape(b, n, block, -1).swapaxes(0, 1),
        jnp.arange(0, s, block)))
    out = out.transpose(1, 2, 0, 3, 4).reshape(b, H, s, hd)
    chosen = chosen.swapaxes(0, 1).reshape(b, s, s)
    mixed = out.transpose(0, 2, 1, 3).reshape(b, s, H * hd) @ w["wo"]
    return mixed, jnp.sum(kl) / (b * s), chosen


def _swiglu(n, w_gate, w_up, w_down):
    return (_silu(n @ w_gate) * (n @ w_up)) @ w_down


def routing(n, router, top_k: int):
    """(chosen (t, top_k), their weights (t, top_k)) of normed tokens n:
    softmax scores over all experts, the chosen over their sum."""
    logits = n @ router
    shifted = logits - jnp.max(logits, axis=-1, keepdims=True)
    scores = jnp.exp(shifted) / jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True)
    top, chosen = jax.lax.top_k(scores, top_k)
    return chosen, top / jnp.sum(top, axis=-1, keepdims=True)


def experts(n, w, hyper: dict):
    """The expert layer on normed tokens n (t, d) -> (y (t, d), chosen): the
    part of the experts held here, `first_held` and as many as `w` stacks,
    each run over every token and masked."""
    router = w["router"] if hyper["routers_trained"] else jax.lax.stop_gradient(
        w["router"])
    chosen, weights = routing(n, router, hyper["top_k"])
    one = jax.checkpoint(_swiglu)
    y = jnp.zeros_like(n)
    for e in range(w["w_gate"].shape[0]):
        mine = jnp.sum(jnp.where(chosen == hyper["first_held"] + e, weights, 0.0),
                       axis=-1)
        y = y + mine[:, None] * one(n, w["w_gate"][e], w["w_up"][e],
                                    w["w_down"][e])
    return y, chosen


def _layer(x, w, hyper: dict):
    """One layer; `w` its weights (no leading axis) -> (x, the indexer's
    loss, the experts chosen, the keys chosen)."""
    b, s, d = x.shape
    mixed, kl, keys = sparse_mixer(_rms(x, w["ln1_scale"], hyper["eps"]), w, hyper)
    x = x + mixed
    y, chosen = experts(_rms(x, w["ln2_scale"], hyper["eps"]).reshape(b * s, d),
                        w, hyper)
    return x + y.reshape(b, s, d), kl, chosen, keys


def forward(params, tokens, **hyper):
    """-> (the final normed hidden states (b, s, d), the indexers' losses
    (layers,), [the experts chosen of each layer], [the keys chosen]).
    `hyper`: heads, kv_heads, head_dim, rope_theta, eps, index_heads,
    index_dim, keys, top_k, first_held, routers_trained, row_block,
    position_block. Each layer keeps its input and recomputes the rest in
    the backward pass."""
    layer = jax.checkpoint(functools.partial(_layer, hyper=hyper))
    x = params["embed"][tokens]
    kls, chosen, keys = [], [], []
    stack = params["layers"]
    for at in range(stack["ln1_scale"].shape[0]):
        x, kl, took, seen = layer(x, jax.tree.map(lambda leaf: leaf[at], stack))
        kls.append(kl)
        chosen.append(took)
        keys.append(seen)
    return (_rms(x, params["ln_f_scale"], hyper["eps"]), jnp.stack(kls), chosen,
            keys)


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


def loss(params, batch, *, indexer_loss_weight: float, **hyper):
    """-> (loss, (the cross-entropy, the indexers' losses a layer, the
    experts chosen (layers, tokens, top_k), the keys chosen (layers, b, s, s)
    bool))."""
    normed, kls, chosen, keys = forward(params, batch[:, :-1], **hyper)
    main = _head_loss(normed, params["lm_head"], batch[:, 1:],
                      hyper["position_block"])
    return main + indexer_loss_weight * jnp.sum(kls), (
        main, kls, jnp.stack(chosen), jnp.stack(keys))


@functools.lru_cache(maxsize=None)
def _jitted(hyper: tuple):
    """The jitted loss and gradients for one set of sizes: a second call with
    the same sizes does not compile again."""
    return jax.jit(jax.value_and_grad(functools.partial(loss, **dict(hyper)),
                                      has_aux=True))


def loss_and_grads(params, batch, **hyper):
    """-> ((loss, (cross-entropy, the indexers' losses a layer, the experts
    chosen, the keys chosen)), gradients): the choices come with the loss, so
    that what counts them compiles no program of its own. Float32 throughout;
    on a TPU a float32 matmul runs in lower precision unless this is set."""
    with jax.default_matmul_precision("highest"):
        return _jitted(tuple(sorted(hyper.items())))(params, batch)
