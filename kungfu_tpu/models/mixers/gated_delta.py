"""The gated delta rule as a layer's token mixer (`mixer="gated_delta"`:
Gated DeltaNet, `ops.gated_delta`): a fused q, k, v, z projection, a causal
depthwise convolution of `conv_taps` taps, a linear recurrence with a matrix
state a head over `delta_heads` = (key heads, value heads, head size), a gated
norm. Layers of it stand in one stack of `layer_kinds` beside attention
layers. Leaves `w_qkvz`, `w_ba`, `conv_w`, `A_log`, `dt_bias`,
`gdn_norm_scale`, `wo`. It keeps no packed documents apart, and the ring and
pipeline paths run it as the normal path does.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from kungfu_tpu.models.blocks import _layer_keys, _mixer_input, _recompute


def check(cfg):
    if not (len(cfg.delta_heads) == 3 and cfg.delta_heads[0] >= 1
            and cfg.delta_heads[1] % cfg.delta_heads[0] == 0):
        raise ValueError("mixer 'gated_delta' needs delta_heads = (key "
                         "heads, value heads a multiple of them, head "
                         f"size), got {cfg.delta_heads}")


def init(key, cfg, dense, unit):
    """From [0] to [4] of the split of fold 2, which the shared expert's
    gate draws from too, wo from [1] of the layer's first split. The numbers
    are fixed because the states of the cells are. The decay's parameters as
    the Gated DeltaNet reference implementation draws them (Mamba2's): A
    uniform in (0, 16), dt log-uniform in (0.001, 0.1) and dt_bias its
    inverse softplus, so g = -A softplus(a + dt_bias) is about -A dt at the
    start, from a memory of a thousand positions to one of less than one,
    head by head; the taps as a depthwise Conv1d's default, uniform within
    1 / sqrt(K)."""
    D, K = cfg.d_model, cfg.conv_taps
    Hk, Hv, d = cfg.delta_heads
    gk = jax.random.split(jax.random.fold_in(key, 2), 6)
    dt = jnp.exp(jax.random.uniform(gk[4], (Hv,), jnp.float32,
                                    math.log(0.001), math.log(0.1)))
    return dict(
        w_qkvz=dense(gk[0], (D, 2 * (Hk + Hv) * d)),
        w_ba=dense(gk[1], (D, 2 * Hv)),
        conv_w=jax.random.uniform(gk[2], (K, (2 * Hk + Hv) * d),
                                  jnp.float32, -K ** -0.5, K ** -0.5),
        A_log=jnp.log(jax.random.uniform(gk[3], (Hv,), jnp.float32,
                                         1e-3, 16.0)),
        dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
        gdn_norm_scale=jnp.ones((d,), jnp.float32),
        wo=dense(_layer_keys(key, cfg)[1], (Hv * d, D)))


def pspecs(cfg, t):
    """The fused projection's and the convolution's channels over tp like
    any column-parallel matrix's, wo's rows; a number a head and the norm's
    scale whole."""
    return dict(w_qkvz=P(None, None, t), w_ba=P(None, None, None),
                conv_w=P(None, None, t), A_log=P(None, None),
                dt_bias=P(None, None), gdn_norm_scale=P(None, None),
                wo=P(None, t, None))


def apply(x, layer, cfg, core, segments, marks):
    return _gated_delta_mixer(_mixer_input(x, layer, cfg), layer, cfg), None


def _l2_normed(t, scale: float, dtype):
    """t / sqrt(|t|^2 + 1e-6) * scale over the last axis, in float32."""
    t = t.astype(jnp.float32)
    norm = jax.lax.rsqrt(jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)
    return (t * (norm * scale)).astype(dtype)


def _gated_norm(o, scale, z, eps, gate=jax.nn.silu):
    """rms(o) * scale * gate(z) over the last axis (a head), the gate silu
    (sigmoid for `mixers.kda`), in float32, the result in o's type. No
    checkpoint of its own, nor `_l2_normed`: the block of heads they stand
    in is run again whole (`_delta_heads`)."""
    o32 = o.astype(jnp.float32)
    var = jnp.mean(jnp.square(o32), axis=-1, keepdims=True)
    y = o32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return (y * gate(z.astype(jnp.float32))).astype(o.dtype)


# value heads a block of the gated delta mixer (`_gated_delta_mixer`)
DELTA_HEAD_BLOCK = 8


@functools.partial(_recompute, static_argnums=(3,))
def _delta_heads(h, part, norm_scale, cfg):
    """A block of the Gated DeltaNet mixer's key heads with their value
    heads, from normed hidden states h (B, S, D) to the block's part of the
    mixer's output (B, S, D); `part` = the block's columns of W_qkvz, W_ba
    and the taps, its A_log and dt_bias, its rows of W_o. Keeps its
    arguments and runs again in the backward pass."""
    from kungfu_tpu.ops.gated_delta import causal_conv, gated_delta_rule

    w_qkvz, w_ba, conv_w, A_log, dt_bias, wo = part
    dt, f32 = cfg.dtype, jnp.float32
    B, S, _ = h.shape
    d = cfg.delta_heads[2]
    r = cfg.delta_heads[1] // cfg.delta_heads[0]
    kb = A_log.shape[0] // r  # key heads in this block
    with jax.named_scope("gdn_proj"):
        qkvz = (h @ w_qkvz.astype(dt)).reshape(B, S, kb, (2 + 2 * r) * d)
        ba = jnp.dot(h.astype(f32), w_ba.astype(f32),
                     precision=jax.lax.Precision.HIGHEST).reshape(B, S, kb, 2 * r)
        b, a = (t.reshape(B, S, kb * r).transpose(0, 2, 1)
                for t in (ba[..., :r], ba[..., r:]))
        beta = jax.nn.sigmoid(b)
        g = -jnp.exp(A_log.astype(f32))[:, None] * jax.nn.softplus(
            a + dt_bias.astype(f32)[:, None])
    with jax.named_scope("gdn_conv"):
        qkv = qkvz[..., :(2 + r) * d].reshape(B, S, kb * (2 + r) * d)
        qkv = jax.nn.silu(causal_conv(qkv, conv_w)).reshape(B, S, kb, (2 + r) * d)
        q = _l2_normed(qkv[..., :d], d ** -0.5, dt).transpose(0, 2, 1, 3)
        k = _l2_normed(qkv[..., d:2 * d], 1.0, dt).transpose(0, 2, 1, 3)
        v = qkv[..., 2 * d:].reshape(B, S, kb * r, d).transpose(0, 2, 1, 3)
        if r > 1:  # value head j reads key head j // r
            q, k = jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1)
    with jax.named_scope("gdn_core"):
        o = gated_delta_rule(q, k, v, g, beta)
    with jax.named_scope("gdn_norm"):
        z = qkvz[..., (2 + r) * d:].reshape(B, S, kb * r, d)
        y = _gated_norm(o.transpose(0, 2, 1, 3), norm_scale, z, cfg.norm_eps)
    with jax.named_scope("gdn_proj"):
        return y.reshape(B, S, kb * r * d) @ wo.astype(dt)


def _gated_delta_mixer(h, layer, cfg):
    """The Gated DeltaNet mixer on normed hidden states h (B, S, D), Hk key
    heads and Hv = r Hk value heads of one size d. W_qkvz's columns lie a
    key head at a time, as the published layout has them: its q, its k, its
    r value heads' v and their z; W_ba's likewise, its r b and r a; the
    taps' its q, k and v channels. [q | k | v] go through the causal
    convolution and a silu; q and k are normalised a head (q over sqrt(d)
    besides); beta = sigmoid(b) and the log decay g = -exp(A_log)
    softplus(a + dt_bias), a number a value head and position, are float32
    from a float32 projection as the router's is; the gated delta rule
    (`ops.gated_delta`); an RMSNorm a head times silu(z); W_o. The heads are
    taken a block of `DELTA_HEAD_BLOCK` value heads at a time, one after
    another, each block run again in the backward pass (`_delta_heads`):
    the rule's kernels hold a chunk in VMEM (XLA's temporaries were 0.15 GB
    a head), but a block still keeps q, k, v, z and the chunks' states, and
    without the blocks' checkpoint the step does not fit the chip (ROADMAP
    S17). The result carries the name `gdn_mix`, which a layer that is run
    again keeps (`transformer._layer_again`): the second run of such a layer
    has no reader for the blocks, so the mixer's forward runs twice a step,
    in the forward pass and once for each block's gradients, not three
    times; the identity anywhere else. Scopes `gdn_proj`, `gdn_conv`,
    `gdn_core`, `gdn_norm`."""
    Hk, Hv, _ = cfg.delta_heads
    r = Hv // Hk
    kb = max(b for b in range(1, Hk + 1)
             if Hk % b == 0 and b * r <= max(DELTA_HEAD_BLOCK, r))

    def blocks(w, axis):
        """`axis`, a key head at a time, as (blocks, ..., a block's, ...)."""
        shape = w.shape[:axis] + (Hk // kb, -1) + w.shape[axis + 1:]
        return jnp.moveaxis(w.reshape(shape), axis, 0)

    parts = (blocks(layer["w_qkvz"], 1), blocks(layer["w_ba"], 1),
             blocks(layer["conv_w"], 1), blocks(layer["A_log"], 0),
             blocks(layer["dt_bias"], 0), blocks(layer["wo"], 0))

    def one(out, part):
        return out + _delta_heads(h, part, layer["gdn_norm_scale"], cfg
                                  ).astype(jnp.float32), None

    out, _ = jax.lax.scan(one, jnp.zeros(h.shape, jnp.float32), parts)
    return checkpoint_name(out.astype(h.dtype), "gdn_mix")
