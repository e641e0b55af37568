"""Plain float32 reference of the Granite 4.0-H cell's loss on packed
documents, written from the layer equations of ISSUE 52 (the source's
`config.json`, `model_type` granitemoehybrid, read with Mamba-2,
arXiv:2405.21060, where it is silent; the configuration file lists each such
reading under `assumed`). It imports nothing from kungfu_tpu and nothing from
the other references; it reads the program's parameter tree: embed,
ln_f_scale, and `layers`, a tuple with one entry for each run of successive
layers of one kind, the run's layers stacked on a leading axis.

A row of ids is several documents laid end to end. `end` is the id that is a
document's last position; position t is of the document that its id ends or
continues, so document numbers rise by one behind every `end`, and the row's
head is a document of its own:

    doc_t = #{u < t : id_u = end};      first_t = (t = 0) or (id_{t-1} = end)

    h_0 = m_e E[ids]
    every layer:   h = h + m_r mixer(norm(h; w_1));   h = h + m_r mlp(norm(h; w_2))
    norm(x; w) = x / sqrt(mean(x^2) + eps) w
    mlp(x) = W_down (silu(x W_gate) * (x W_up))
    loss = mean_t -log softmax(norm(h_L; w_f) E^T / m_l)[id_{t+1}]

over every position, the one behind an `end` included, on the tied embedding.
`mamba`, the Mamba-2 mixer (w_ssm_in, conv_w, conv_b, dt_bias, A_log, D_skip,
ssm_norm_scale, wo): H heads of P features, a state of N a feature, G groups of
H / G heads that share B and C:

    [z | xBC | dt] = x W_in                  (H P | H P + 2 G N | H columns)
    conv(u)_t = b + sum_{i<K} c_i u_{t-K+1+i} [doc_{t-K+1+i} = doc_t], 0 before the row
    [x | B | C] = silu(conv(xBC))
    Delta_t = softplus(dt_t + dt_bias);   A = -exp(A_log)     a number a head
    S_t = exp(Delta_t A) S'_{t-1} + Delta_t x_t B_t^T,   S'_{t-1} = 0 if first_t
    y_t = S_t C_t + D x_t                    head j reads group j // (H / G)
    out = (rms_G(y silu(z)) w_n) W_out       the gate first, then the norm

rms_G is over each of the G groups of H P / G features (one group: over all of
them). `attention` (wq, wk, wv, wo), no position signal, the scores times m_a
in the place of 1 / sqrt(head size):

    a_h = softmax(m_a q_h k_{h // (H / Hkv)}^T over {j <= i, doc_j = doc_i}) v_{h // (H / Hkv)}

Departures from the published implementation, each a matter of layout and not
of the function: the feed-forward's input projection is the program's two
matrices W_gate and W_up where the checkpoint has one of twice the width, [g |
u]; q, k and v are three matrices as published. The recurrence is run a
position at a time with the state set to zero, exactly, at a document's first
position (`lax.scan` inside blocks of positions, a block keeping its first
state and running again in the backward pass); the convolution is K shifted
products, each with the boundary's zeros; the attention is dense scores under
the mask, a block of queries at a time; the loss is taken a block of positions
at a time. No chunk, no running sum of decays, no segment number handed to a
kernel: nothing to share a fault with the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

MAMBA, ATTENTION = "mamba", "attention"


def documents(tokens, end: int):
    """tokens (b, s) -> (doc (b, s) int32, first (b, s) bool)."""
    behind_an_end = jnp.concatenate(
        [jnp.zeros_like(tokens[:, :1], bool), tokens[:, :-1] == end], axis=1)
    first = behind_an_end.at[:, 0].set(True)
    return jnp.cumsum(behind_an_end.astype(jnp.int32), axis=1), first


def _norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def conv(u, taps, bias, doc):
    """u (b, s, channels), taps (K, channels), bias (channels,), doc (b, s):
    K shifted products; a tap that would read before the row or into
    another document reads zero."""
    K, s = taps.shape[0], u.shape[1]
    y = jnp.zeros_like(u) + bias
    for i in range(K):
        back = K - 1 - i
        moved = jnp.concatenate([jnp.zeros_like(u[:, :back]), u[:, :s - back]],
                                axis=1)
        theirs = jnp.concatenate(
            [jnp.full_like(doc[:, :back], -1), doc[:, :s - back]], axis=1)
        y = y + taps[i] * jnp.where((theirs == doc)[..., None], moved, 0.0)
    return y


def recurrence(x, B, C, delta, A, first, block: int):
    """x (b, s, H, P), B and C (b, s, G, N), delta (b, s, H), A (H,), first
    (b, s) bool -> y (b, s, H, P): S_t = exp(delta_t A) S'_{t-1} + delta_t
    x_t B_t^T with S' zero where `first`, y_t = S_t C_t, a position at a
    time; head j reads group j // (H / G)."""
    b, s, H, P = x.shape
    G, N = B.shape[2:]
    block = min(block, s)
    assert s % block == 0 and H % G == 0, (s, block, H, G)
    group = jnp.arange(H) // (H // G)

    def position(state, at):
        x_t, B_t, C_t, d_t, first_t = at
        B_t, C_t = B_t[:, group], C_t[:, group]  # (b, H, N)
        state = jnp.where(first_t[:, None, None, None], 0.0, state)
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
                        tuple(blocks(t) for t in (x, B, C, delta, first)))
    return jnp.moveaxis(y.reshape((s,) + y.shape[2:]), 0, 1)


def mamba_mixer(h, w, doc, first, hyper: dict):
    b, s, _ = h.shape
    H, P, N, G = (hyper["ssm_heads"], hyper["ssm_head_dim"], hyper["ssm_state"],
                  hyper["ssm_groups"])
    inner, bc = H * P, G * N
    zxbcdt = h @ w["w_ssm_in"]
    z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:2 * inner + 2 * bc],
                  zxbcdt[..., 2 * inner + 2 * bc:])
    xbc = _silu(conv(xbc, w["conv_w"], w["conv_b"], doc))
    x = xbc[..., :inner].reshape(b, s, H, P)
    B = xbc[..., inner:inner + bc].reshape(b, s, G, N)
    C = xbc[..., inner + bc:].reshape(b, s, G, N)
    delta = _softplus(dt + w["dt_bias"])
    y = recurrence(x, B, C, delta, -jnp.exp(w["A_log"]), first,
                   hyper["position_block"])
    y = (y + w["D_skip"][:, None] * x).reshape(b, s, inner) * _silu(z)
    y = y.reshape(b, s, G, inner // G)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + hyper["eps"])
    return (y.reshape(b, s, inner) * w["ssm_norm_scale"]) @ w["wo"]


def _attention(q, k, v, doc, scale: float, block: int):
    """q (b, H, s, hd), k and v (b, H / g, s, hd), doc (b, s) -> (b, H, s,
    hd): a query sees the keys no later than it and of its document, `block`
    queries at a time; a block keeps its inputs and recomputes its scores in
    the backward pass."""
    b, n_heads, s, hd = q.shape
    kv_heads = k.shape[1]
    group = n_heads // kv_heads
    block = min(block, s)
    assert s % block == 0, (s, block)

    @jax.checkpoint
    def one(args):
        qb, start = args  # (b, kv heads, group, block, hd)
        at = start + jnp.arange(block)
        mine = jax.lax.dynamic_slice_in_dim(doc, start, block, axis=1)
        seen = ((at[:, None] >= jnp.arange(s)[None, :])[None]
                & (mine[:, :, None] == doc[:, None, :]))  # (b, block, s)
        scores = scale * jnp.einsum("bkgqd,bksd->bkgqs", qb, k)
        scores = jnp.where(seen[:, None, None], scores, -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        probs = jnp.exp(scores)
        probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
        return jnp.einsum("bkgqs,bksd->bkgqd", probs, v)

    # query head h = key/value head h // group, and place h % group in it
    blocks = q.reshape(b, kv_heads, group, s // block, block, hd)
    out = jax.lax.map(one, (blocks.transpose(3, 0, 1, 2, 4, 5),
                            jnp.arange(0, s, block)))
    return out.transpose(1, 2, 3, 0, 4, 5).reshape(b, n_heads, s, hd)


def attention_mixer(h, w, doc, first, hyper: dict):
    """No rotary pass and no position signal of any kind; the scores times
    `attention_multiplier`."""
    b, s, _ = h.shape
    H, Hkv, hd = hyper["heads"], hyper["kv_heads"], hyper["head_dim"]
    q = (h @ w["wq"]).reshape(b, s, H, hd).transpose(0, 2, 1, 3)
    k = (h @ w["wk"]).reshape(b, s, Hkv, hd).transpose(0, 2, 1, 3)
    v = (h @ w["wv"]).reshape(b, s, Hkv, hd).transpose(0, 2, 1, 3)
    ctx = _attention(q, k, v, doc, hyper["attention_multiplier"],
                     hyper["query_block"])
    return ctx.transpose(0, 2, 1, 3).reshape(b, s, H * hd) @ w["wo"]


def mlp(x, w, block: int):
    """W_down (silu(x W_gate) * (x W_up)) on x (b, s, d), `block` positions
    at a time (it looks at no other position; 8,192 x 8,192 float32 gates
    are 0.27 GB, and the backward pass holds six such arrays); a block keeps
    its rows and runs again in the backward pass."""
    b, s, d = x.shape
    block = min(block, s)
    assert s % block == 0, (s, block)

    @jax.checkpoint
    def some(rows):
        return (_silu(rows @ w["w_gate"]) * (rows @ w["w_up"])) @ w["w_down"]

    out = jax.lax.map(some, x.reshape(b, s // block, block, d).swapaxes(0, 1))
    return out.swapaxes(0, 1).reshape(b, s, d)


def _layer(x, w, doc, first, kind: str, hyper: dict):
    """One layer, both branches; `w` its weights (no leading axis). Each
    branch keeps its input and runs again in the backward pass."""
    mix = mamba_mixer if kind == MAMBA else attention_mixer
    m_r = hyper["residual_multiplier"]
    mixed = jax.checkpoint(functools.partial(mix, hyper=hyper))(
        _norm(x, w["ln1_scale"], hyper["eps"]), w, doc, first)
    x = x + m_r * mixed
    return x + m_r * mlp(_norm(x, w["ln2_scale"], hyper["eps"]), w,
                         hyper["position_block"] * 8)


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


def hidden(params, tokens, *, layers, **hyper):
    """-> the final normed hidden states (b, s, d). `layers`: "mamba" or
    "attention" a layer; `hyper`: ssm_heads, ssm_head_dim, ssm_state,
    ssm_groups, heads, kv_heads, head_dim, eps, embedding_multiplier,
    attention_multiplier, residual_multiplier, logits_scaling,
    end_of_document, query_block, position_block. Each layer keeps its input
    and recomputes the rest in the backward pass."""
    doc, first = documents(tokens, hyper["end_of_document"])
    x = hyper["embedding_multiplier"] * params["embed"][tokens]
    for kind, (stack, at) in zip(layers, _runs(layers), strict=True):
        w = jax.tree.map(lambda leaf: leaf[at], params["layers"][stack])
        x = jax.checkpoint(functools.partial(_layer, kind=kind, hyper=hyper))(
            x, w, doc, first)
    return _norm(x, params["ln_f_scale"], hyper["eps"])


def _head_loss(normed, head, targets, scaling: float, block: int):
    """mean_t -log softmax(normed_t head^T / scaling)[target_t], `block`
    positions at a time (8,192 x 12,544 float32 logits are 0.41 GB, and the
    softmax holds four such arrays)."""
    b, s, d = normed.shape
    block = min(block, s)
    assert s % block == 0, (s, block)

    @jax.checkpoint
    def some(total, xs):
        rows, picks = xs  # (b, block, d), (b, block)
        logits = rows @ head.T / scaling
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
    """Every position's next id is a target, the one behind an end of
    document too: concatenate-and-chunk pre-training masks none."""
    normed = hidden(params, batch[:, :-1], **hyper)
    return _head_loss(normed, params["embed"], batch[:, 1:],
                      hyper["logits_scaling"], hyper["position_block"])


def loss_and_grads(params, batch, **hyper):
    """Float32 throughout; on a TPU a float32 matmul runs in lower
    precision unless this is set."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(functools.partial(loss, **hyper)))(
            params, batch)


def logits(params, batch, **hyper):
    """(b, s, vocabulary) float32: for the tests' sizes."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t: hidden(p, t, **hyper) @ p["embed"].T
                       / hyper["logits_scaling"])(params, batch[:, :-1])
