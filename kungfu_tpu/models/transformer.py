"""Flagship decoder-only transformer LM with an explicit sharding plan.

TPU-first design notes:
- Params live in a plain pytree with a parallel tree of PartitionSpecs
  (param_pspecs): Megatron-style tensor parallelism over the 'tp' mesh
  axis (column-parallel QKV/FF-in, row-parallel O/FF-out), batch over
  'dp', optional sequence sharding over 'sp' for activations. XLA's SPMD
  partitioner inserts the AllReduce/AllGather collectives over ICI from
  these annotations — nothing is hand-scheduled.
- Compute in bfloat16 (MXU native), params and optimizer state in f32.
- Static shapes everywhere; layers are stacked and scanned-friendly.

The reference has no model code (KungFu is model-agnostic); this model is
the framework's flagship workload for the BERT-config benchmark
(BASELINE.md config 3) and the long-context/sequence-parallel path.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 2048
    max_seq: int = 512
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @classmethod
    def bert_base(cls) -> "TransformerConfig":
        return cls(vocab_size=30522, d_model=768, n_heads=12, n_layers=12,
                   d_ff=3072, max_seq=512)

    @classmethod
    def tiny(cls) -> "TransformerConfig":
        return cls(vocab_size=256, d_model=64, n_heads=4, n_layers=2,
                   d_ff=128, max_seq=64)


def init_transformer(key, cfg: TransformerConfig) -> Dict:
    """Params in f32; cast to cfg.dtype at apply time."""
    keys = jax.random.split(key, 2 + cfg.n_layers)
    scale = 0.02

    def dense(k, shape):
        return jax.random.normal(k, shape, jnp.float32) * scale

    layers = []
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[2 + i], 4)
        layers.append({
            "ln1_scale": jnp.ones((cfg.d_model,), jnp.float32),
            "ln2_scale": jnp.ones((cfg.d_model,), jnp.float32),
            "wqkv": dense(lk[0], (cfg.d_model, 3 * cfg.d_model)),
            "wo": dense(lk[1], (cfg.d_model, cfg.d_model)),
            "w_in": dense(lk[2], (cfg.d_model, cfg.d_ff)),
            "w_out": dense(lk[3], (cfg.d_ff, cfg.d_model)),
        })
    # stack layers: leading axis = layer, enables lax.scan over layers
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    return {
        "embed": dense(keys[0], (cfg.vocab_size, cfg.d_model)),
        "pos_embed": dense(keys[1], (cfg.max_seq, cfg.d_model)),
        "ln_f_scale": jnp.ones((cfg.d_model,), jnp.float32),
        "layers": stacked,
    }


def param_pspecs(cfg: TransformerConfig, tp_axis: str = "tp") -> Dict:
    """PartitionSpec tree matching init_transformer's param tree.

    Column-parallel wqkv/w_in (shard output features over tp), row-parallel
    wo/w_out (shard input features over tp); embedding sharded over vocab.
    Layer-stacked leaves have a leading layer axis (unsharded).
    """
    t = tp_axis
    return {
        "embed": P(t, None),
        "pos_embed": P(),
        "ln_f_scale": P(),
        "layers": {
            "ln1_scale": P(None),
            "ln2_scale": P(None),
            "wqkv": P(None, None, t),
            "wo": P(None, t, None),
            "w_in": P(None, None, t),
            "w_out": P(None, t, None),
        },
    }


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


@_recompute
def _rmsnorm(x, scale, eps=1e-6):
    """Keeps x and scale; the f32 upcast, the variance and the normalised
    output are recomputed."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(x.dtype)


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


@_recompute
def _gelu_out(pre, w_out):
    """gelu(pre) @ w_out. Keeps the pre-activation and w_out; the
    tanh-gelu, its four temporaries and with them the matmul's operand are
    recomputed. Saving the gelu's output for that matmul instead was 0.15
    ms a step slower at bert_base's size and 0.6 GB larger (PERF.md, PR 25)."""
    return jax.nn.gelu(pre) @ w_out


def _attention(x, wqkv, wo, cfg: TransformerConfig, core=_full_attention_core):
    """QKV projection + head reshape around a pluggable (q,k,v)->ctx core
    (full attention by default, the ring core for sequence parallelism —
    ONE copy of the projection plumbing for both paths)."""
    B, S, D = x.shape
    H, hd = cfg.n_heads, cfg.head_dim
    qkv = x @ wqkv  # (B, S, 3D)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    k = k.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    v = v.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    with jax.named_scope("attn_core"):
        ctx = core(q, k, v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, D)
    return ctx @ wo


def _block(x, layer, cfg: TransformerConfig, core=_full_attention_core):
    dt = cfg.dtype
    with jax.named_scope("attn"):
        x = x + _attention(_rmsnorm(x, layer["ln1_scale"]),
                           layer["wqkv"].astype(dt), layer["wo"].astype(dt),
                           cfg, core=core)
    with jax.named_scope("ffn"):
        pre = _rmsnorm(x, layer["ln2_scale"]) @ layer["w_in"].astype(dt)
        return x + _gelu_out(pre, layer["w_out"].astype(dt))


def lm_head_loss(params, x, targets, cfg: TransformerConfig):
    """Final norm + tied-embedding LM head + next-token cross-entropy on
    hidden states `x` (..., S, D). The ONE implementation shared by the
    dense, ring (sequence-parallel) and pipeline paths — a loss change
    (label smoothing, z-loss, dtype policy) lands everywhere at once."""
    with jax.named_scope("head_loss"):
        h = _rmsnorm(x, params["ln_f_scale"])
        logits = h.astype(jnp.float32) @ params["embed"].astype(jnp.float32).T
        logp = jax.nn.log_softmax(logits)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return -jnp.mean(ll)


def transformer_hidden(params, tokens, cfg: TransformerConfig):
    """tokens (B, S) int32 -> final hidden states (B, S, D) pre-norm."""
    B, S = tokens.shape
    dt = cfg.dtype
    with jax.named_scope("embed"):
        x = params["embed"].astype(dt)[tokens] + params["pos_embed"].astype(dt)[:S]

    def body(x, layer):
        return _block(x, layer, cfg), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return x


def transformer_apply(params, tokens, cfg: TransformerConfig):
    """tokens (B, S) int32 -> logits (B, S, V) in f32."""
    x = transformer_hidden(params, tokens, cfg)
    x = _rmsnorm(x, params["ln_f_scale"])
    return x.astype(jnp.float32) @ params["embed"].astype(jnp.float32).T


def transformer_loss(params, batch, cfg: TransformerConfig):
    """Next-token cross-entropy. batch = tokens (B, S+1) or (tokens, targets)."""
    if isinstance(batch, (tuple, list)):
        tokens, targets = batch
    else:
        tokens, targets = batch[:, :-1], batch[:, 1:]
    x = transformer_hidden(params, tokens, cfg)
    return lm_head_loss(params, x, targets, cfg)


# ---------------------------------------------------------------------------
# sequence-parallel (ring attention) path: the long-context mode. The whole
# forward runs per sequence-SHARD inside a shard_map over (dp, sp) — token
# embedding, norms and FFN are pointwise over positions, so only attention
# needs cross-shard traffic, and that traffic is the K/V ring on ICI
# (ops/ring_attention.py). Peak activation memory per chip scales with
# S/sp instead of S.
# ---------------------------------------------------------------------------


def ring_transformer_apply_shard(params, tokens, cfg: TransformerConfig,
                                 sp_axis: str, sp_size: int):
    """Per-shard forward for shard_map: tokens (B, S_local) is this
    device's sequence chunk; returns per-shard pre-norm hidden states
    (B, S_local, D) — feed them to lm_head_loss."""
    from kungfu_tpu.ops.ring_attention import ring_self_attention

    B, Sl = tokens.shape
    if sp_size * Sl > cfg.max_seq:
        # loud, like the dense path: dynamic_slice would otherwise CLAMP
        # the out-of-range start and silently duplicate positional rows
        raise ValueError(
            f"global sequence {sp_size * Sl} exceeds max_seq {cfg.max_seq}"
        )
    dt = cfg.dtype
    with jax.named_scope("embed"):
        idx = jax.lax.axis_index(sp_axis)
        pos = jax.lax.dynamic_slice(
            params["pos_embed"], (idx * Sl, 0), (Sl, cfg.d_model)
        )
        x = params["embed"].astype(dt)[tokens] + pos.astype(dt)

    def ring_core(q, k, v):
        return ring_self_attention(q, k, v, sp_axis, sp_size, causal=True)

    def body(x, layer):
        # the ONE block implementation, with the ring attention core
        return _block(x, layer, cfg, core=ring_core), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return x  # pre-final-norm hidden states, like transformer_hidden


def make_ring_transformer_loss(cfg: TransformerConfig, mesh,
                               sp_axis: str = "sp", dp_axis: str = "dp"):
    """Sequence-parallel causal-LM loss: batch = (tokens, targets), both
    (B, S) with B divisible by dp and S by sp. Returns loss_fn(params,
    batch) -> replicated scalar, jit/grad-compatible (shard_map inside)."""
    sp_size = mesh.shape[sp_axis]

    def shard_loss(params, batch):
        tokens, targets = batch
        x = ring_transformer_apply_shard(params, tokens, cfg, sp_axis, sp_size)
        loss = lm_head_loss(params, x, targets, cfg)
        return jax.lax.pmean(jax.lax.pmean(loss, sp_axis), dp_axis)

    return jax.shard_map(
        shard_loss,
        mesh=mesh,
        in_specs=(P(), (P(dp_axis, sp_axis), P(dp_axis, sp_axis))),
        out_specs=P(),
        check_vma=False,
    )
