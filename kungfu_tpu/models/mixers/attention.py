"""Softmax attention as a layer's token mixer (`mixer="attention"`), and the
same mixer under a learned sparse index (`sparse_index`).

Attention: a fused (D, 3D) projection `wqkv`, or `wq`, `wk`, `wv` of their own
widths where the configuration has a head size or key/value heads of its own
(`split_qkv`: query head h reads key/value head h // (n_heads // n_kv_heads));
q and k normed over all their features or, with split projections, a head
(`qk_norm`, leaves `q_norm_scale`, `k_norm_scale`); rotary positions over the
leading share of the head, with YaRN's frequencies where the configuration
names them; a causal core plugged from outside (the ring's) or the
configuration's own, XLA's dense one or `ops.flash_attention` with its band
mask (`window`), its scale (`attention_multiplier`) and the documents of
packed rows; a sigmoid gate a head on the core's output (`head_gate`, leaf
`w_head_gate`) or a gate a feature from a q projection of twice the width
(`q_gate`); `wo`. Packed documents are kept apart on the flash core alone.

Learned sparse attention (DeepSeek Sparse Attention on grouped heads):
`sparse_index` = (indexer heads, indexer head size, keys a query) gives the
layer a lightning indexer (leaves `index_wq`, `index_wk`, `index_w`,
`index_ln_scale`, `index_ln_bias`) on the layer's normed input with its
gradient stopped, the choice of each query's best-scored keys at or before it,
the softmax core over the chosen keys alone and the indexer's own loss, the KL
divergence of its distribution from the head-mean of the core's probabilities
(`_sparse_attention`, `ops.sparse_attention`). The cross-entropy reaches no
leaf of the indexer and the indexer's loss no other leaf. `()` is every other
configuration's program, text for text.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from kungfu_tpu.models.blocks import (_core_kind_scope, _layer_keys,
                                      _mixer_input, _recompute, _rmsnorm,
                                      _rope, _rotary_tables, _scale,
                                      attention_core_of)


def on_the_flash_core(cfg) -> bool:
    """Whether the mixer keeps packed documents apart: a query sees the keys
    of its own document on the flash core, and every earlier key on the
    dense one."""
    return cfg.attn_core == "flash"


def init(key, cfg, dense, unit):
    """wq (or wqkv) from [0] and wo from [1] of the layer's first split; wk,
    wv and the head gate from [0], [1] and [2] of the split of fold 1, which
    the shared expert draws its leaves' from too. The numbers are fixed
    because the states of the cells are."""
    D = cfg.d_model
    lk = _layer_keys(key, cfg)
    if cfg.split_qkv or cfg.head_gate:
        xk = jax.random.split(jax.random.fold_in(key, 1), 6)
    layer = {}
    if cfg.split_qkv:
        q_width, kv_width = (h * cfg.head_dim
                             for h in (cfg.n_heads, cfg.kv_heads))
        layer["wq"] = dense(lk[0], (D, q_width * (2 if cfg.q_gate else 1)))
        layer["wk"] = dense(xk[0], (D, kv_width))
        layer["wv"] = dense(xk[1], (D, kv_width))
        layer["wo"] = dense(lk[1], (q_width, D))
    else:
        layer["wqkv"] = dense(lk[0], (D, 3 * D))
        layer["wo"] = dense(lk[1], (D, D))
    if cfg.head_gate:
        layer["w_head_gate"] = dense(xk[2], (D, cfg.n_heads))
    if cfg.qk_norm:
        width = cfg.head_dim if cfg.split_qkv else D
        layer["q_norm_scale"] = unit(cfg, (width,))
        layer["k_norm_scale"] = unit(cfg, (width,))
    return layer


def pspecs(cfg, t):
    """Heads over tp: wqkv's columns, or wq's, wk's, wv's and the head
    gate's, and wo's rows. The q/k norms' scales span all of q's features,
    which tp splits, and are sharded like them; a head's own (split
    projections) are whole."""
    specs = {"wo": P(None, t, None)}
    if cfg.split_qkv:
        specs.update(wq=P(None, None, t), wk=P(None, None, t),
                     wv=P(None, None, t))
    else:
        specs.update(wqkv=P(None, None, t))
    if cfg.head_gate:
        specs.update(w_head_gate=P(None, None, t))
    if cfg.qk_norm:
        spec = P(None, None) if cfg.split_qkv else P(None, t)
        specs.update(q_norm_scale=spec, k_norm_scale=spec)
    return specs


def _qk_scales(layer, cfg):
    """(q's norm's scale, k's) where the configuration norms q and k. Made
    before the layer's own norm, where they have always been made."""
    return ((_scale(layer["q_norm_scale"], cfg),
             _scale(layer["k_norm_scale"], cfg)) if cfg.qk_norm else None)


def apply(x, layer, cfg, core, segments, marks):
    dt = cfg.dtype
    scales = _qk_scales(layer, cfg)
    h = _mixer_input(x, layer, cfg)
    wqkv = (tuple(layer[w].astype(dt) for w in ("wq", "wk", "wv"))
            if cfg.split_qkv else layer["wqkv"].astype(dt))
    return _attention(h, wqkv, layer["wo"].astype(dt), cfg, core=core,
                      qk_scales=scales,
                      w_head_gate=(layer["w_head_gate"].astype(dt)
                                   if cfg.head_gate else None),
                      segments=segments), None


def _gated_out(ctx, pre, wo):
    """(ctx (B, H, S, hd) times sigmoid(pre (B, S, H)), a scalar a head and
    position, the sigmoid in float32) as (B, S, H * hd) @ wo. Under its
    checkpoint (`_gated_out_kept`) it keeps ctx, which the core keeps
    anyway, pre and wo; the gated copy of ctx, the matmul's operand, is made
    again, as the feed-forward's `_gelu_out` makes its gelu again."""
    B, H, S, hd = ctx.shape
    gate = jax.nn.sigmoid(pre.astype(jnp.float32)).astype(ctx.dtype)
    ctx = ctx * gate.transpose(0, 2, 1)[..., None]
    return ctx.transpose(0, 2, 1, 3).reshape(B, S, H * hd) @ wo


def _split_heads(x, wqkv, cfg, qk_scales=None):
    """x @ (wq, wk, wv) as (B, heads, S, hd) q, k, v with rotary positions,
    and the (B, S, H, hd) gate that a doubled wq carries behind each head's
    q (`q_gate`; None without). q and k are normed a head where the
    configuration says so (`qk_scales`). Without a norm the backward pass
    wants x and the matrices and nothing else: the rotation keeps nothing,
    so there is no checkpoint to say so."""
    B, S, _ = x.shape
    hd = cfg.head_dim

    def heads(t):
        return t.transpose(0, 2, 1, 3)

    gate = None
    if cfg.q_gate:
        q = (x @ wqkv[0]).reshape(B, S, -1, 2 * hd)
        q, gate = q[..., :hd], q[..., hd:]
    else:
        q = (x @ wqkv[0]).reshape(B, S, -1, hd)
    if not cfg.qk_norm:
        q = heads(q)
    k = (x @ wqkv[1]).reshape(B, S, -1, hd)
    if cfg.qk_norm:
        with jax.named_scope("qk_norm"):
            q = heads(_rmsnorm(q, qk_scales[0], cfg.norm_eps))
            k = _rmsnorm(k, qk_scales[1], cfg.norm_eps)
    k = heads(k)
    v = heads((x @ wqkv[2]).reshape(B, S, -1, hd))
    if cfg.positions == "rope":
        with jax.named_scope("rope"):
            q, k = _rope(q, k, cfg.rope_theta, cfg.rotary_share, cfg.yarn)
    return q, k, v, gate


def _feature_gated_out(ctx, gate, wo):
    """(ctx (B, H, S, hd) times sigmoid(gate (B, S, H, hd)), one a feature,
    the sigmoid in float32) as (B, S, H * hd) @ wo. Under its checkpoint
    (`_feature_gated_out_kept`) it keeps ctx, gate and wo and makes the
    gated copy again, as `_gated_out` does."""
    B, H, S, hd = ctx.shape
    ctx = ctx.transpose(0, 2, 1, 3) * jax.nn.sigmoid(
        gate.astype(jnp.float32)).astype(ctx.dtype)
    return ctx.reshape(B, S, H * hd) @ wo


# in a layer that is run again whole (`layer_remat`) the piece as it is: a
# checkpoint inside would run it a third time
_gated_out_kept = _recompute(_gated_out)
_feature_gated_out_kept = _recompute(_feature_gated_out)


def _attention(x, wqkv, wo, cfg, core=None, qk_scales=None, w_head_gate=None,
               segments=()):
    """QKV projection + head reshape around a pluggable (q,k,v)->ctx core
    (the configuration's by default, the ring core for sequence parallelism
    — ONE copy of the projection plumbing for every path). `wqkv` is the
    fused (D, 3D) matrix, or (wq, wk, wv) where q's width and k's, v's are
    the configuration's own (`split_qkv`), wq twice as wide where it carries
    a gate a feature (`q_gate`). `qk_scales` = (q_norm_scale, k_norm_scale)
    where the configuration norms q and k, over all of their features or,
    with split projections, a head; `w_head_gate` (D, H) where it gates each
    head's output; `segments`, (the documents' numbers,) of packed rows, go
    to the core."""
    B, S, _ = x.shape
    H, hd = cfg.n_heads, cfg.head_dim
    gate = None
    if cfg.split_qkv:
        q, k, v, gate = _split_heads(x, wqkv, cfg, qk_scales)
    else:
        qkv = x @ wqkv  # (B, S, 3D)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        if cfg.qk_norm:
            with jax.named_scope("qk_norm"):
                q = _rmsnorm(q, qk_scales[0], cfg.norm_eps)
                k = _rmsnorm(k, qk_scales[1], cfg.norm_eps)
        q = q.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
        k = k.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
        v = v.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
        if cfg.positions == "rope":
            with jax.named_scope("rope"):
                q, k = _rope(q, k, cfg.rope_theta, cfg.rotary_share, cfg.yarn)
    with _core_kind_scope(cfg), jax.named_scope("attn_core"):
        ctx = (core or attention_core_of(cfg))(q, k, v, *segments)
    if cfg.head_gate:
        with jax.named_scope("attn_gate"):
            gated_out = _gated_out if cfg.layer_remat else _gated_out_kept
            return gated_out(ctx, x @ w_head_gate, wo)
    if gate is not None:
        with jax.named_scope("attn_gate"):
            gated_out = (_feature_gated_out if cfg.layer_remat
                         else _feature_gated_out_kept)
            return gated_out(ctx, gate, wo)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    return ctx @ wo


# ---------------------------------------------------------------------------
# the same mixer under a learned sparse index
# ---------------------------------------------------------------------------

SPARSE_OFF_THE_NORMAL_PATH = (
    "sparse_index runs on the normal path (`transformer_loss`): the "
    "ring and pipeline paths have no place for the indexer's loss, "
    "and a sequence shard's choice would range over other shards' keys")


def sparse_check(cfg):
    """What a learned sparse index stands with, each refusal a sentence."""
    if not (len(cfg.sparse_index) == 3 and min(cfg.sparse_index) >= 1
            and cfg.sparse_index[1] % 2 == 0):
        raise ValueError("sparse_index is (indexer heads, an even indexer "
                         "head size, keys a query), got "
                         f"{cfg.sparse_index}")
    if cfg.mixer != "attention" or not cfg.split_qkv or (
            cfg.positions != "rope"):
        raise ValueError("sparse_index chooses the keys of softmax "
                         "attention with projections of its own (a head "
                         "size or key/value heads) and rotary positions, "
                         f"not of mixer {cfg.mixer!r} with positions "
                         f"{cfg.positions!r}")
    for field, what, unset in (
            ("window", "a window beside the choice", 0),
            ("end_of_document", "packed documents under the choice", None),
            ("mtp_depth", "a multi-token-prediction module", 0),
            ("layer_kinds", "layers that differ in kind", ()),
            ("head_gate", "a gate a head", False),
            ("q_gate", "a gate a feature", False),
            ("attention_multiplier", "a scale of the scores' own", 0.0),
            ("yarn", "YaRN's frequencies", ())):
        if getattr(cfg, field) != unset:
            raise ValueError(
                f"sparse_index is not built with {what} ({field}): the "
                "choice is made under the causal bound alone, in a stack "
                "of one kind of layer, and no test holds it to more")
    if cfg.loop_steps > 1 or cfg.rotary_share != 1.0:
        raise ValueError("sparse_index is not built under a loop "
                         "(loop_steps > 1), which has no place for the "
                         "indexer's loss a loop step, nor with a rotary "
                         "share of the head (rotary_share)")


def sparse_init(key, cfg, dense, unit):
    """The attention layer's leaves and the lightning indexer's five, its
    three matrices from the split of fold 6. The number is fixed because the
    states of the cells are."""
    D = cfg.d_model
    Hi, di, _ = cfg.sparse_index
    ik = jax.random.split(jax.random.fold_in(key, 6), 3)
    return dict(init(key, cfg, dense, unit),
                index_wq=dense(ik[0], (D, Hi * di)),
                index_wk=dense(ik[1], (D, di)),
                index_w=dense(ik[2], (D, Hi)),
                index_ln_scale=jnp.ones((di,), jnp.float32),
                index_ln_bias=jnp.zeros((di,), jnp.float32))


def sparse_pspecs(cfg, t):
    """The indexer's five leaves whole on every chip: every shard of the
    heads attends under the one choice."""
    return dict(pspecs(cfg, t),
                index_wq=P(None, None, None), index_wk=P(None, None, None),
                index_w=P(None, None, None), index_ln_scale=P(None, None),
                index_ln_bias=P(None, None))


def sparse_apply(x, layer, cfg, core, segments, marks):
    scales = _qk_scales(layer, cfg)
    return _sparse_attention(_mixer_input(x, layer, cfg), layer, cfg, scales)


def _layer_norm(x, scale, bias, eps):
    """LayerNorm over the last axis, float32: (x - mean) / sqrt(var + eps) *
    scale + bias."""
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                              + eps) * scale + bias)


def _rotate_half(t, cos, sin):
    """t cos + rotate_half(t) sin over the last axis, cos and sin of its
    width: the plain form, for the indexer's small float32 arrays."""
    half = t.shape[-1] // 2
    return t * cos + jnp.concatenate([-t[..., half:], t[..., :half]], -1) * sin


def _sparse_attention(h, layer, cfg, qk_scales):
    """Learned sparse attention on normed hidden states h (B, S, D) -> (the
    mixer's output (B, S, D), the indexer's KL loss, a scalar). q, k, v as
    every attention layer's (`_split_heads`: the norm a head, the rotary
    pass). The lightning indexer reads h with its gradient stopped, in
    float32 at the highest precision, as a router does (a rounded score moves
    the last chosen key as a rounded router moves the last chosen expert): qI
    = h W_qI as (S, Hi, di), kI = LN(h W_kI), both rotated over all di
    features at `rope_theta`, w = h W_w / sqrt(Hi di), I[t, s] = sum_j w[t, j]
    relu(qI[t, j] . kI[s]). Each query's `sparse_index[2]` best-scored keys
    at or before it are its choice (all of them where it has no more), one
    choice for all heads; the softmax core runs over the chosen keys; the
    indexer's loss is the KL divergence of softmax over the chosen keys of I
    from the head-mean of the core's probabilities there, a constant. The
    cross-entropy's gradient reaches q, k, v through the chosen keys and no
    leaf of the indexer; the KL's reaches the indexer's five leaves and
    nothing else. On the flash core's setting (`attn_core` "flash") the five
    pieces are `ops.sparse_attention`'s kernels (four at `flash_blocks`, the
    choice at a block of whole rows of its own), on "dense" its plain forms.
    Scopes `attn_proj` (the four projections, with `qk_norm` and `rope`
    inside), `dsa_index`, `dsa_select`, `attn_sparse` > `attn_core` and
    `dsa_kl`."""
    from kungfu_tpu.ops import sparse_attention as dsa

    dt = cfg.dtype
    B, S, _ = h.shape
    kernels = cfg.attn_core == "flash"
    how = (*cfg.flash_blocks, cfg.flash_interpret)
    with jax.named_scope("attn_proj"):
        q, k, v, _ = _split_heads(
            h, tuple(layer[w].astype(dt) for w in ("wq", "wk", "wv")), cfg,
            qk_scales)
    scores, chosen = _sparse_choice(h, layer, cfg)
    with jax.named_scope("attn_sparse"), jax.named_scope("attn_core"):
        ctx, lse = (dsa.sparse_attention(q, k, v, chosen, None, *how) if kernels
                    else dsa.plain_sparse_attention(q, k, v, chosen))
    with jax.named_scope("dsa_kl"):
        p, entropy = (dsa.head_mean_probs(q, k, lse, chosen, None, *how)
                      if kernels else
                      dsa.plain_head_mean_probs(q, k, lse, chosen))
        kl = dsa.indexer_kl(scores, chosen, p, entropy)
    with jax.named_scope("attn_proj"):
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, -1)
        return ctx @ layer["wo"].astype(dt), kl


def _sparse_choice(h, layer, cfg):
    """The lightning indexer on normed hidden states h (B, S, D) -> (its
    scores I (B, S, S) float32, defined at s <= t, and the choice (B, S, S)
    int8): `_sparse_attention`'s first half, scopes `dsa_index` and
    `dsa_select`."""
    from kungfu_tpu.ops import sparse_attention as dsa

    f32 = jnp.float32
    B, S, _ = h.shape
    Hi, di, keys = cfg.sparse_index
    kernels = cfg.attn_core == "flash"
    how = (*cfg.flash_blocks, cfg.flash_interpret)
    with jax.named_scope("dsa_index"):
        ub = jax.lax.stop_gradient(h).astype(f32)

        def projected(w):
            return jnp.dot(ub, layer[w].astype(f32),
                           precision=jax.lax.Precision.HIGHEST)

        cos, sin = _rotary_tables(S, di, cfg.rope_theta, 1.0, ())
        qI = _rotate_half(projected("index_wq").reshape(B, S, Hi, di),
                          cos[:, None], sin[:, None])
        kI = _rotate_half(_layer_norm(
            projected("index_wk"), layer["index_ln_scale"],
            layer["index_ln_bias"], cfg.norm_eps), cos, sin)
        w = projected("index_w") * (Hi ** -0.5 * di ** -0.5)
        scores = (dsa.index_scores(qI, kI, w, *how) if kernels
                  else dsa.plain_index_scores(qI, kI, w))
    with jax.named_scope("dsa_select"):
        # Handed on through its bits, a bit a pair under the name
        # `dsa_chosen` (8.4 MB a layer of 8,192 positions): a layer that is
        # run again keeps them (`transformer._layer_again`) and makes the
        # scores again, which the indexer's loss reads, but not the choice,
        # whose counting passes then run once a step and not twice.
        chosen = (dsa.select(scores, keys, cfg.flash_interpret) if kernels
                  else dsa.plain_select(scores, keys))
        packed = checkpoint_name(
            jnp.packbits(chosen.astype(jnp.uint8), axis=-1), "dsa_chosen")
        return scores, jnp.unpackbits(packed, axis=-1, count=S).astype(jnp.int8)
