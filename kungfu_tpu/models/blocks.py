"""What the layer (`models/transformer.py`) and its token mixers
(`models/mixers/`) are both built from, below both: the checkpoint that the
pieces say their residuals with, RMSNorm, the rotary pass, the dense causal
attention core and the choice of a core, and the first split of a layer's key.
The arrows are `ops/` <- this file <- `models/mixers/` <-
`models/transformer.py`: nothing here imports either of the two, and a
configuration (`TransformerConfig`) reaches a function here as an argument.
An op is imported inside the function that calls it, so that importing the
model imports no Pallas kernel.
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


# What the backward pass keeps (PERF.md, PR 25). The layer scan stacks every
# residual of its body once a layer, so each piece below whose residuals are
# cheap functions of something smaller that is saved anyway says so itself
# with `jax.checkpoint`: it keeps its inputs and recomputes the rest where
# the backward pass wants it. One HBM byte costs the v5e 240 operations, so
# an S x S probability array (12 bytes an element, written and read) is
# worth 2,900 operations against the 128 of a second QK^T, at every length.
# `prevent_cse=False`: inside a scan body the barrier is unnecessary and
# costs fusions.
_recompute = functools.partial(jax.checkpoint, prevent_cse=False)


def _layer_keys(key, cfg):
    """The first split of a layer's key, which the mixer's projections (wq or
    wqkv from [0], wo from [1]) and the feed-forward's leaves ([2] on) are
    drawn from: four keys under the gelu block, six under any other
    feed-forward. The numbers are fixed because the states of the cells are."""
    return jax.random.split(key, 4 if cfg.ffn == "gelu" else 6)


def _scale(w, cfg):
    """A norm's scale from its weight: the weight, or 1 + it."""
    return 1.0 + w if cfg.norm_offset else w


def _rmsnorm(x, scale, eps=1e-6):
    """Keeps x and scale; the f32 upcast, the variance and the normalised
    output are recomputed. `eps` is data of the configuration, not of the
    program: a Python number."""
    return _rmsnorm_at(x, scale, eps)


@functools.partial(_recompute, static_argnums=(2,))
def _rmsnorm_at(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(x.dtype)


def _mixer_input(x, layer, cfg):
    """What a mixer reads: the residual stream x behind the layer's first
    norm (`ln1_scale`)."""
    return _rmsnorm(x, _scale(layer["ln1_scale"], cfg), cfg.norm_eps)


@_recompute
def _full_attention_core(q, k, v):
    """(B, H, S, hd) q/k/v -> causal attention context, same shape.

    Keeps q, k, v; scores, mask, the f32 softmax and its cast are
    recomputed. The checkpoint is this core's own, not `_attention`'s or
    `_block`'s: a core plugged from outside (the ring, flash attention's
    `custom_vjp`) keeps its own residuals and is never run twice."""
    hd = q.shape[-1]
    S = q.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(hd).astype(q.dtype)
    mask = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def attention_core_of(cfg):
    """The (q, k, v) -> ctx core the configuration names."""
    if cfg.attn_core == "dense":
        return _full_attention_core
    from kungfu_tpu.ops.flash_attention import flash_attention

    blk_q, blk_k = cfg.flash_blocks
    return lambda q, k, v, *segments: flash_attention(
        q, k, v, True, cfg.attention_multiplier or None, blk_q, blk_k,
        cfg.flash_interpret, cfg.window or None, *segments)


def _core_kind_scope(cfg):
    """`attn_window` or `attn_full` around the core where a model has both
    kinds of layer to tell apart (a window anywhere in it, or split
    projections); nothing more around the one core every other
    configuration runs."""
    if cfg.window:
        return jax.named_scope("attn_window")
    if cfg.split_qkv:
        return jax.named_scope("attn_full")
    return contextlib.nullcontext()


def _yarn_ramp(rd: int, theta: float, yarn: Tuple):
    """YaRN's blend (arXiv:2309.00071, as the transformers library's
    `_compute_yarn_parameters` computes it): 0 for the rd // 2 frequencies
    that turn more than beta_fast times over the original positions and
    keep their own frequency, 1 for those that turn fewer than beta_slow
    times and take theirs over `factor`, linear between."""
    _, original, beta_fast, beta_slow, _ = yarn

    def dim_of(turns):
        return (rd * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), rd - 1)
    span = (high - low) or 0.001
    return jnp.clip((jnp.arange(rd // 2, dtype=jnp.float32) - low) / span, 0, 1)


def _rotary_tables(S: int, hd: int, theta: float, share: float, yarn: Tuple):
    """(S, hd) float32 cos and sin of positions 0..S-1 for the rotate-half
    form over the leading `share` of the head: the rd // 2 frequencies on
    both halves of the rotated features, under `yarn` its blended
    frequencies and its attention factor on both tables, and cos 1, sin 0
    on the features that pass through. Traced `jnp` of static shapes: made
    again inside the program wherever a pass wants them."""
    rd = int(hd * share)  # the rotated features
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    if yarn:
        ramp = _yarn_ramp(rd, theta, yarn)
        inv_freq = inv_freq / yarn[0] * ramp + inv_freq * (1 - ramp)
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)  # (S, rd // 2)
    if yarn:
        cos, sin = cos * yarn[4], sin * yarn[4]
    through = jnp.ones((S, hd - rd), jnp.float32)
    return (jnp.concatenate([cos, cos, through], axis=-1),
            jnp.concatenate([sin, sin, jnp.zeros_like(through)], axis=-1))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _turned(t, rule: Tuple, back: bool):
    """One pass over (B, H, S, hd) t (`ops.rotary.rotate`): cos * t + sin *
    P t, P the signed swap of the two halves of the rotated features, or,
    `back`, its transpose cos * t - sin * P t on a cotangent; the sign of P
    rides in the sine table. The kernel reads t as (B, S, H * hd) and writes
    (B, H, S, hd), and the other way about on the way back: the transposition
    below undoes the caller's own, so a projection's output goes to the
    attention core through this one pass. Mosaic where the program is
    lowered for the TPU, the same kernel interpreted anywhere else. Under a
    `jax.jit` of its own, so that a step's calls of one shape trace and
    lower one body: the Laguna cell's first step is 2 s shorter warm and 9 s
    cold for it on the chip's host (PERF.md, PR 35)."""
    from kungfu_tpu.ops.rotary import rotate

    theta, share, yarn = rule
    B, H, S, hd = t.shape
    half = int(hd * share) // 2
    cos, sin = _rotary_tables(S, hd, theta, share, yarn)
    sin = jnp.where((jnp.arange(hd) < half) != back, -sin, sin)
    if not back:
        t = t.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    kernel = functools.partial(rotate, half=half, into_heads=not back)
    out = jax.lax.platform_dependent(
        t, cos, sin, tpu=kernel,
        default=functools.partial(kernel, interpret=True))
    return out.reshape(B, S, H, hd).transpose(0, 2, 1, 3) if back else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _rotated(t, rule: Tuple):
    """The rotation is linear in t and its transpose is the same pass with
    the sine's sign turned, so the backward pass needs nothing of t: no
    residual, and no transposed slices (pads) and concatenations (slices
    and adds) of q's size in float32, which is what autodiff writes for the
    rotate-half form (33.7 ms of the Laguna cell's step, PERF.md, PR 35)."""
    return _turned(t, rule, False)


_rotated.defvjp(lambda t, rule: (_turned(t, rule, False), None),
                lambda rule, _, dy: (_turned(dy, rule, True),))


def _rope(q, k, theta: float, share: float, yarn: Tuple):
    """Rotary positions on (B, H, S, hd) q and k (each its own H),
    positions 0..S-1: the rotate-half form over the leading `share` of the
    head dimension (the rest passes through), angles and the rotation in
    float32, under `yarn` its frequencies and attention factor. Keeps
    nothing for the backward pass."""
    rule = (theta, share, yarn)
    return _rotated(q, rule), _rotated(k, rule)
