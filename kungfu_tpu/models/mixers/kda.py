"""Kimi Delta Attention as a layer's token mixer (`mixer="kda"`: Kimi Linear's
KDA, arXiv:2510.26692, `ops.kda`): q, k and v each through a projection, a
causal depthwise convolution of `conv_taps` taps and a silu, over `kda_heads`
= (heads, head size) heads of one size; a linear recurrence with a matrix
state a head whose decay is a number a key feature, from a projection through
a rank of the head size; a norm a head under a sigmoid gate from a second
such projection. Layers of it stand in one stack of `layer_kinds` beside
latent-attention layers. Leaves `w_q`, `w_k`, `w_v`, `conv_q`, `conv_k`,
`conv_v`, `w_f_a`, `w_f_b`, `A_log`, `dt_bias`, `w_beta`, `kda_norm_scale`,
`w_g_a`, `w_g_b`, `wo`. It keeps no packed documents apart, and the ring and
pipeline paths refuse it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from kungfu_tpu.models.blocks import _layer_keys, _mixer_input, _recompute
from kungfu_tpu.models.mixers import gated_delta
from kungfu_tpu.models.mixers.gated_delta import _l2_normed

OFF_THE_NORMAL_PATH = (
    "mixer 'kda' is built for the normal path (`_hidden`): the rule's state "
    "runs from a sequence's first position to its last on one chip, and no "
    "ring step or pipeline stage hands a state on")


def check(cfg):
    if not (len(cfg.kda_heads) == 2 and min(cfg.kda_heads) >= 1):
        raise ValueError("mixer 'kda' needs kda_heads = (heads, head size), "
                         f"got {cfg.kda_heads}")


def init(key, cfg, dense, unit):
    """The twelve drawn leaves from the split of fold 4, which nothing else
    draws from, wo from [1] of the layer's first split. The decay's
    parameters as `mixers.gated_delta.init` draws them (Mamba2's): A uniform
    in (0, 16) a head, dt log-uniform in (0.001, 0.1) a feature and dt_bias
    its inverse softplus, so g = -A softplus(f + dt_bias) is about -A dt at
    the start; the taps as a depthwise Conv1d's default, uniform within
    1 / sqrt(K)."""
    D, K = cfg.d_model, cfg.conv_taps
    H, d = cfg.kda_heads
    ks = jax.random.split(jax.random.fold_in(key, 4), 12)
    dt = jnp.exp(jax.random.uniform(ks[9], (H * d,), jnp.float32,
                                    math.log(0.001), math.log(0.1)))

    def taps(k):
        return jax.random.uniform(k, (K, H * d), jnp.float32, -K ** -0.5, K ** -0.5)

    return dict(
        w_q=dense(ks[0], (D, H * d)), w_k=dense(ks[1], (D, H * d)),
        w_v=dense(ks[2], (D, H * d)),
        conv_q=taps(ks[3]), conv_k=taps(ks[4]), conv_v=taps(ks[5]),
        w_f_a=dense(ks[6], (D, d)), w_f_b=dense(ks[7], (d, H * d)),
        A_log=jnp.log(jax.random.uniform(ks[8], (H,), jnp.float32, 1e-3, 16.0)),
        dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
        w_beta=dense(ks[10], (D, H)),
        kda_norm_scale=jnp.ones((d,), jnp.float32),
        w_g_a=dense(ks[11], (D, d)),
        w_g_b=dense(jax.random.fold_in(ks[11], 1), (d, H * d)),
        wo=dense(_layer_keys(key, cfg)[1], (H * d, D)))


def pspecs(cfg, t):
    """The projections' and the convolutions' channels over tp like any
    column-parallel matrix's, wo's rows; the two low ranks, a number a head
    or feature and the norm's scale whole."""
    column, whole = P(None, None, t), P(None, None, None)
    return dict(w_q=column, w_k=column, w_v=column, conv_q=column,
                conv_k=column, conv_v=column, w_f_a=whole, w_f_b=column,
                A_log=P(None, None), dt_bias=P(None, None), w_beta=whole,
                kda_norm_scale=P(None, None), w_g_a=whole, w_g_b=column,
                wo=P(None, t, None))


def apply(x, layer, cfg, core, segments, marks):
    return _kda_mixer(_mixer_input(x, layer, cfg), layer, cfg), None


# rms(o) * scale * sigmoid(gate) over a head: the Gated DeltaNet mixer's norm
# under the other gate
_gated_norm = functools.partial(gated_delta._gated_norm, gate=jax.nn.sigmoid)


def _log_decay(f, A_log, dt_bias):
    """g = -exp(A_log) softplus(f + dt_bias): f (B, S, heads, d) float32,
    A_log a number a head, dt_bias a number a feature -> (B, heads, S, d)."""
    f32 = jnp.float32
    heads, d = f.shape[2:]
    g = -jnp.exp(A_log.astype(f32))[:, None] * jax.nn.softplus(
        f + dt_bias.astype(f32).reshape(heads, d))
    return g.transpose(0, 2, 1, 3)


# heads a block of the mixer (`_kda_mixer`)
KDA_HEAD_BLOCK = 8


@functools.partial(_recompute, static_argnums=(5,))
def _kda_heads(h, f_a, g_a, part, norm_scale, cfg):
    """A block of the KDA mixer's heads, from normed hidden states h (B, S,
    D) to the block's part of the mixer's output (B, S, D). `f_a` (B, S, d)
    float32 and `g_a` (B, S, d) are the two low ranks' first halves, the
    whole mixer's; `part` = the block's columns of W_q, W_k, W_v, their
    taps, W_fb and W_gb, its A_log and dt_bias, its beta (B, S, heads)
    float32, its rows of W_o. Keeps its arguments and runs again in the
    backward pass."""
    from kungfu_tpu.ops.gated_delta import causal_conv
    from kungfu_tpu.ops.kda import kda_rule

    (w_q, w_k, w_v, conv_q, conv_k, conv_v, w_f_b, w_g_b, A_log, dt_bias,
     beta, wo) = part
    dt, f32 = cfg.dtype, jnp.float32
    B, S, _ = h.shape
    d = cfg.kda_heads[1]
    hb = A_log.shape[0]  # heads in this block

    def heads(t):  # (B, S, hb d) -> (B, S, hb, d)
        return t.reshape(B, S, hb, d)

    with jax.named_scope("kda_proj"):
        q, k, v = (h @ w.astype(dt) for w in (w_q, w_k, w_v))
        f = jnp.dot(f_a, w_f_b.astype(f32), precision=jax.lax.Precision.HIGHEST)
        g = _log_decay(heads(f), A_log, dt_bias)
        gate = g_a @ w_g_b.astype(dt)
    with jax.named_scope("kda_conv"):
        q, k, v = (heads(jax.nn.silu(causal_conv(t, taps)))
                   for t, taps in ((q, conv_q), (k, conv_k), (v, conv_v)))
        q = _l2_normed(q, d ** -0.5, dt).transpose(0, 2, 1, 3)
        k = _l2_normed(k, 1.0, dt).transpose(0, 2, 1, 3)
        v = v.transpose(0, 2, 1, 3)
    with jax.named_scope("kda_core"):
        o = kda_rule(q, k, v, g, beta.transpose(0, 2, 1))
    with jax.named_scope("kda_norm"):
        y = _gated_norm(o.transpose(0, 2, 1, 3), norm_scale, heads(gate),
                        cfg.norm_eps)
    with jax.named_scope("kda_proj"):
        return y.reshape(B, S, hb * d) @ wo.astype(dt)


def _kda_mixer(h, layer, cfg):
    """The KDA mixer on normed hidden states h (B, S, D), H heads of one
    size d for q, k and v alike. q = l2(silu(conv(h W_q))) / sqrt(d), k =
    l2(silu(conv(h W_k))), v = silu(conv(h W_v)), the l2 a head; the log
    decay a key feature g = -exp(A_log) softplus((h W_fa) W_fb + dt_bias)
    and beta = sigmoid(h W_beta), a number a head, are float32 from float32
    projections at the highest precision as the router's is; the delta rule
    with that decay (`ops.kda`); an RMSNorm a head times sigmoid((h W_ga)
    W_gb); W_o. No bias anywhere. The two low ranks' first halves and beta
    are made once for all heads; the heads are taken a block of
    `KDA_HEAD_BLOCK` at a time, one after another, each block run again in
    the backward pass (`_kda_heads`), as the gated delta rule's
    (`mixers.gated_delta`): a block keeps q, k, v, g, the gate and the
    chunks' states, and without the blocks' checkpoint a step of 16,384
    positions does not fit the chip. The result carries the name `kda_mix`,
    which a layer that is run again keeps (`transformer._layer_again`), so
    the mixer's forward runs twice a step and not three times. Scopes
    `kda_proj`, `kda_conv`, `kda_core`, `kda_norm`."""
    H, _ = cfg.kda_heads
    f32, highest = jnp.float32, jax.lax.Precision.HIGHEST
    hb = max(b for b in range(1, min(H, KDA_HEAD_BLOCK) + 1) if H % b == 0)
    with jax.named_scope("kda_proj"):
        h32 = h.astype(f32)
        f_a = jnp.dot(h32, layer["w_f_a"].astype(f32), precision=highest)
        beta = jax.nn.sigmoid(
            jnp.dot(h32, layer["w_beta"].astype(f32), precision=highest))
        g_a = h @ layer["w_g_a"].astype(cfg.dtype)

    def blocks(w, axis):
        """`axis`, a head at a time, as (blocks, ..., a block's, ...)."""
        shape = w.shape[:axis] + (H // hb, -1) + w.shape[axis + 1:]
        return jnp.moveaxis(w.reshape(shape), axis, 0)

    parts = tuple(blocks(layer[name], 1) for name in (
        "w_q", "w_k", "w_v", "conv_q", "conv_k", "conv_v", "w_f_b", "w_g_b"))
    parts += (blocks(layer["A_log"], 0), blocks(layer["dt_bias"], 0),
              blocks(beta, 2), blocks(layer["wo"], 0))

    def one(out, part):
        return out + _kda_heads(h, f_a, g_a, part, layer["kda_norm_scale"], cfg
                                ).astype(f32), None

    out, _ = jax.lax.scan(one, jnp.zeros(h.shape, f32), parts)
    return checkpoint_name(out.astype(h.dtype), "kda_mix")
