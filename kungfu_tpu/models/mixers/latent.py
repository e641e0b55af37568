"""Latent attention as a layer's token mixer (`mixer="latent"`: DeepSeek-V2's
MLA as GLM-4.7-Flash and Kimi Linear have it). q through a normed latent, or,
`latent_dims[0]` 0, straight from the hidden states (q = h W_q: no
`w_q_down`, no `q_latent_norm`, and `w_q_up` is (D, heads x features));
keys and values through another latent, one key shared by every head beside
each head's own features, turned by its position with q's like features
(`positions` "rope", under `yarn` at YaRN's blended frequencies over the
rotated features, cos and sin times its attention factor, as
`mixers/attention.py` has them) or, `positions` "none", as the projection
gives it: nothing is turned, and the one shared key still stands beside each
head's own features. The softmax's scale is 1 / sqrt(q/k head size) or
`attention_multiplier` in its place (DeepSeek-V2's mscale^2 / sqrt(head size)
under YaRN goes there), which `blocks.attention_core_of` hands the flash core;
the dense core has no scale of its own and the configuration refuses it. `latent_dims` = (q latent rank or 0, key/value latent rank,
features a q/k head of its own, features of the shared key, features a value
head). k and v are laid out a head for the core every other attention layer
runs; the value heads' size `hd_v` may differ from the q/k heads' on either
core (`ops.flash_attention`: `hd` of q and k, `hd_v` of v and o). Leaves
`w_q_down`, `q_latent_norm` (with a q latent), `w_q_up`, `w_kv_down`,
`kv_latent_norm`, `w_kv_up`, `wo`. It keeps no packed documents apart.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from kungfu_tpu.models.blocks import (_layer_keys, _mixer_input, _rmsnorm,
                                      _rope, _scale, attention_core_of)


def check(cfg):
    if not (len(cfg.latent_dims) == 5 and cfg.latent_dims[0] >= 0
            and min(cfg.latent_dims[1:]) >= 1 and cfg.latent_dims[3] % 2 == 0):
        raise ValueError("mixer 'latent' needs latent_dims = (q rank or 0, "
                         "key/value rank, unrotated, rotated (even), "
                         f"value features a head), got {cfg.latent_dims}")
    if cfg.positions not in ("rope", "none"):
        raise ValueError("mixer 'latent' turns its rotated features "
                         "by positions 'rope', or turns nothing ('none')")
    _, _, nope, rope, value = cfg.latent_dims
    if cfg.positions == "rope" and (
            int((nope + rope) * (rope / (nope + rope))) != rope):
        raise ValueError(
            f"{rope} rotated of {nope + rope} features is a share "
            "that the rotary pass (`_rope`) rounds down")


def init(key, cfg, dense, unit):
    """The four projections from [0] to [3] of the split of fold 3, which
    the router's selection bias draws from too, wo from [1] of the layer's
    first split. The numbers are fixed because the states of the cells are.
    The published layout: W_q_up's columns a head at a time, its unrotated
    features and then its rotated; W_kv_down's the latent and then the one
    rotated key; W_kv_up's a head at a time, its unrotated key features and
    then its value."""
    D, H = cfg.d_model, cfg.n_heads
    rq, rkv, nope, rope, value = cfg.latent_dims
    mk = jax.random.split(jax.random.fold_in(key, 3), 5)
    q_latent = dict(w_q_down=dense(mk[0], (D, rq)),
                    q_latent_norm=unit(cfg, (rq,))) if rq else {}
    return dict(
        **q_latent,
        w_q_up=dense(mk[1], (rq or D, H * (nope + rope))),
        w_kv_down=dense(mk[2], (D, rkv + rope)),
        kv_latent_norm=unit(cfg, (rkv,)),
        w_kv_up=dense(mk[3], (rkv, H * (nope + value))),
        wo=dense(_layer_keys(key, cfg)[1], (H * value, D)))


def pspecs(cfg, t):
    """The up-projections are a head at a time and column-parallel, wo
    row-parallel; the down-projections (the one rotary key's columns among
    them) and the latents' norms whole."""
    q_latent = dict(w_q_down=P(None, None, None),
                    q_latent_norm=P(None, None)) if cfg.latent_dims[0] else {}
    return dict(**q_latent,
                w_q_up=P(None, None, t), w_kv_down=P(None, None, None),
                kv_latent_norm=P(None, None), w_kv_up=P(None, None, t),
                wo=P(None, t, None))


def apply(x, layer, cfg, core, segments, marks):
    return _latent_attention(_mixer_input(x, layer, cfg), layer, cfg,
                             core=core), None


def _latent_attention(h, layer, cfg, core=None):
    """Latent attention (MLA) on normed hidden states h (B, S, D) -> (B, S,
    D). c_q = norm(h W_q_down), or h itself without a q latent, and a head's
    [q_nope | q_rope] = c_q W_q_up;
    [c_kv | k_r] = h W_kv_down, c_kv normed, and a head's [k_nope | v] =
    c_kv W_kv_up; q = [q_nope | rot(q_rope)] and every head's k = [its
    k_nope | rot(k_r)], the one rotated key of all heads (rot at `yarn`'s
    frequencies where the configuration has it); the causal core
    the configuration names over heads of nope + rope features, at the scale
    1 / sqrt(nope + rope) or `attention_multiplier`; W_o. Training lays k and v out a head, as the
    published implementations do (absorbing W_kv_up into q is a decode
    device). Inside, a head's rotated features stand first: the same
    permutation of q's and k's features, made on W_q_up's columns and where
    k is put together, leaves every q . k as it is, and puts the rotated
    features where the one rotary pass that also lays a projection's output
    out a head expects them (`blocks._turned`). With `positions` "none"
    nothing is turned and the features keep the published order. Scopes `mla_down`,
    `mla_norm`, `mla_up` (the up-projections and what lays k out a head),
    `rope`, `attn_latent` > `attn_core`."""
    dt, eps = cfg.dtype, cfg.norm_eps
    rq, rkv, nope, rope, value = cfg.latent_dims
    H, hd = cfg.n_heads, nope + rope
    B, S, _ = h.shape
    turned = cfg.positions == "rope"
    with jax.named_scope("mla_down"):
        c_q = h @ layer["w_q_down"].astype(dt) if rq else h
        c_kv = h @ layer["w_kv_down"].astype(dt)
        c_kv, k_r = c_kv[..., :rkv], c_kv[..., rkv:]
    with jax.named_scope("mla_norm"):
        if rq:
            c_q = _rmsnorm(c_q, _scale(layer["q_latent_norm"], cfg), eps)
        c_kv = _rmsnorm(c_kv, _scale(layer["kv_latent_norm"], cfg), eps)
    with jax.named_scope("mla_up"):
        w_q = layer["w_q_up"].astype(dt)
        if turned:  # a head's rotated features first
            w_q = w_q.reshape(-1, H, hd)
            w_q = jnp.concatenate([w_q[..., nope:], w_q[..., :nope]], axis=-1)
        q = c_q @ w_q.reshape(-1, H * hd)
        w_kv = layer["w_kv_up"].astype(dt).reshape(rkv, H, nope + value)
        k_nope = c_kv @ w_kv[..., :nope].reshape(rkv, H * nope)
        v = c_kv @ w_kv[..., nope:].reshape(rkv, H * value)
        shared = jnp.broadcast_to(k_r[:, :, None, :], (B, S, H, rope))
        own = k_nope.reshape(B, S, H, nope)
        k = jnp.concatenate([shared, own] if turned else [own, shared], axis=-1)
        v = v.reshape(B, S, H, value).transpose(0, 2, 1, 3)
    with jax.named_scope("rope" if turned else "mla_up"):
        q, k = q.reshape(B, S, H, hd).transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3)
        if turned:
            q, k = _rope(q, k, cfg.rope_theta, rope / hd, cfg.yarn)
    with jax.named_scope("attn_latent"), jax.named_scope("attn_core"):
        ctx = (core or attention_core_of(cfg))(q, k, v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, H * value)
    return ctx @ layer["wo"].astype(dt)
