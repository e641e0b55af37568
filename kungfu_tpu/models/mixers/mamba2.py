"""The Mamba-2 state-space mixer as a layer's token mixer (`mixer="mamba2"`,
Nemotron-H's and Granite 4.0's): one fused projection to [z | x B C | dt], a
causal depthwise convolution with a bias, a selective step Delta =
softplus(dt + dt_bias) and the diagonal state-space recurrence H_t =
exp(Delta_t A) H_{t-1} + Delta_t x_t B_t^T, y_t = H_t C_t + D x_t
(`ops.ssm_scan`, the chunked scan's second rule), a norm over groups of
features behind the gate silu(z), W_out; `ssm_dims` = (heads, head size, state
size, groups of heads that share B and C). Leaves `w_ssm_in`, `conv_w`,
`conv_b`, `A_log`, `dt_bias`, `D_skip`, `ssm_norm_scale`, `wo`. It keeps the
documents of packed rows apart: no tap reaches into an earlier document
(`ops.ssm_conv`, whose kernels read each position's depth into its document,
made beside the documents' numbers once a step: `document_marks`), and the
scan's state is zero before a document's first position.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from kungfu_tpu.models.blocks import _layer_keys, _mixer_input


def check(cfg):
    if not (len(cfg.ssm_dims) == 4 and min(cfg.ssm_dims) >= 1
            and cfg.ssm_dims[0] % cfg.ssm_dims[3] == 0):
        raise ValueError("mixer 'mamba2' needs ssm_dims = (heads, head "
                         "size, state size, groups that divide the "
                         f"heads), got {cfg.ssm_dims}")


def init(key, cfg, dense, unit):
    """From the five keys of the split of fold 4, wo from [1] of the layer's
    first split. The numbers are fixed because the states of the cells are.
    Mamba-2's own start (its `time_step_min`, `_max`, `_floor` and
    `A_init_range`): A uniform on [1, 16], the step log-uniform on [0.001,
    0.1] and at least 1e-4, dt_bias its inverse softplus, D 1; taps and bias
    as a depthwise Conv1d's default, uniform within 1 / sqrt(K)."""
    D, K = cfg.d_model, cfg.conv_taps
    H, hp, N, G = cfg.ssm_dims
    conv = H * hp + 2 * G * N
    sk = jax.random.split(jax.random.fold_in(key, 4), 5)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        sk[3], (H,), jnp.float32, math.log(0.001), math.log(0.1))), 1e-4)
    return dict(
        w_ssm_in=dense(sk[0], (D, H * hp + conv + H)),
        conv_w=jax.random.uniform(sk[1], (K, conv), jnp.float32,
                                  -K ** -0.5, K ** -0.5),
        conv_b=jax.random.uniform(sk[2], (conv,), jnp.float32,
                                  -K ** -0.5, K ** -0.5),
        A_log=jnp.log(jax.random.uniform(sk[4], (H,), jnp.float32,
                                         1.0, 16.0)),
        dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
        D_skip=jnp.ones((H,), jnp.float32),
        ssm_norm_scale=jnp.ones((H * hp,), jnp.float32),
        wo=dense(_layer_keys(key, cfg)[1], (H * hp, D)))


def pspecs(cfg, t):
    """The fused projection's, the convolution's and the gated norm's
    channels over tp like any column-parallel matrix's, wo's rows; a number a
    head whole."""
    return dict(w_ssm_in=P(None, None, t), conv_w=P(None, None, t),
                conv_b=P(None, t), A_log=P(None, None),
                dt_bias=P(None, None), D_skip=P(None, None),
                ssm_norm_scale=P(None, t), wo=P(None, t, None))


def document_marks(segments):
    """What the convolution's kernels read beside the documents' numbers
    (B, S): made once a step, for every such layer."""
    from kungfu_tpu.ops import ssm_conv

    return ssm_conv.document_marks(segments)


def apply(x, layer, cfg, core, segments, marks):
    return _mamba2_mixer(_mixer_input(x, layer, cfg), layer, cfg, segments,
                         marks), None


def _mamba2_mixer(h, layer, cfg, segments=(), marks=()):
    """The Mamba-2 mixer on normed hidden states h (B, S, D): H heads of P
    features, a state of N a feature, G groups of H / G heads that share B
    and C (`ssm_dims`). [z | x B C | dt] = h W_in (H P + (H P + 2 G N) + H
    columns); the step Delta = softplus(dt + dt_bias) and the log decay g =
    Delta A, A = -exp(A_log), a number a head and position, float32 from a
    float32 projection as the router's is; [x | B | C] through the causal
    convolution with its bias and a silu, and v = Delta x, one kernel each
    way (`ops.ssm_conv`: it reads the projection's columns from x on where
    the matmul left them, writes [x | B | C] once and v once in the layout
    the scan reads, float32 between, and keeps its inputs alone); the
    state-space recurrence (`ops.ssm_scan`) with q = C, k = B (a group's,
    never repeated a head) and that v; + D x, the gate silu(z) and then an
    RMSNorm over each group's features, one kernel each way
    (`ops.gated_norm`: it reads the scan's output as the scan lays it out, x
    and z as the first H P columns of the convolution's and the projection's
    outputs, writes y once, and keeps those inputs alone); W_out. Both ops
    are the same kept or run again. `segments`, (the documents' numbers (B,
    S),) of packed rows, go to the convolution and to the scan, `marks`
    (their `document_marks`,) to the convolution's kernels, and nothing else
    of the mixer looks beyond its own position. Scopes `ssm_proj`,
    `ssm_conv`, `ssm_core`, `ssm_norm`."""
    from kungfu_tpu.ops import gated_norm
    from kungfu_tpu.ops.ssm_conv import ssm_conv
    from kungfu_tpu.ops.ssm_scan import CHUNK, ssm_scan

    H, hp, N, G = cfg.ssm_dims
    inner, bc = H * hp, G * N
    dt, f32 = cfg.dtype, jnp.float32
    B, S, _ = h.shape
    w_in = layer["w_ssm_in"]
    with jax.named_scope("ssm_proj"):
        zxbc = h @ w_in[:, :2 * inner + 2 * bc].astype(dt)  # z its first columns
        step = jnp.dot(h.astype(f32), w_in[:, 2 * inner + 2 * bc:].astype(f32),
                       precision=jax.lax.Precision.HIGHEST)  # (B, S, H)
    with jax.named_scope("ssm_conv"):
        delta = jax.nn.softplus(step + layer["dt_bias"].astype(f32))
        g = (delta * -jnp.exp(layer["A_log"].astype(f32))).transpose(0, 2, 1)
        xbc, v = ssm_conv(zxbc, layer["conv_w"], layer["conv_b"], delta,
                          *segments, *marks)
        b, c = (xbc[..., at:at + bc].reshape(B, S, G, N).transpose(0, 2, 1, 3)
                for at in (inner, inner + bc))
    with jax.named_scope("ssm_core"):
        # the published chunk, or the largest power of two under it that
        # divides a shorter sequence: the result does not depend on it
        o = ssm_scan(c, b, v, g, math.gcd(S, CHUNK), *segments)  # (B, H, S, hp)
    with jax.named_scope("ssm_norm"):
        y = gated_norm.gated_norm(o, xbc, zxbc, layer["D_skip"],
                                  layer["ssm_norm_scale"], G, cfg.norm_eps)
    with jax.named_scope("ssm_proj"):
        return y @ layer["wo"].astype(dt)
