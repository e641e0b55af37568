"""The gated short convolution as a layer's token mixer (`mixer="short_conv"`,
LFM2's operator): [B | C | x] = h W_in (three equal thirds of 3 D columns), a
causal depthwise convolution of `conv_taps` taps over B * x, the gate C on its
output and W_out (`ops.short_conv`: the two gates and the taps in one kernel
each way): no recurrence, no softmax, no activation, no bias, no norm of its
own and no state in training. Leaves `conv_in`, `conv_w`, `conv_out`. Layers
of it stand in one stack of `layer_kinds` beside attention, dense and expert
layers. It keeps the documents of packed rows apart: a tap that would reach
into another document reads zero.
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from kungfu_tpu.models.blocks import _mixer_input

OFF_THE_NORMAL_PATH = (
    "mixer 'short_conv' runs on the normal path (`transformer_loss`): "
    "the ring path would have to hand a shard the rows before it, "
    "and neither it nor the pipeline path is built for the mixer")


def check(cfg):
    if cfg.conv_taps != 3:
        raise ValueError("mixer 'short_conv' convolves over 3 taps, what "
                         "`ops/short_conv.py`'s kernels are written for, "
                         f"got conv_taps {cfg.conv_taps}")
    if cfg.loop_steps > 1 or cfg.mtp_depth:
        raise ValueError("mixer 'short_conv' is built for the plain "
                         "stack: not under a loop (loop_steps > 1) nor "
                         "in a model with a multi-token-prediction "
                         "module, which no test holds it to")


def init(key, cfg, dense, unit):
    """From the three keys of the split of fold 5. The number is fixed
    because the states of the cells are."""
    D = cfg.d_model
    ck = jax.random.split(jax.random.fold_in(key, 5), 3)
    return dict(conv_in=dense(ck[0], (D, 3 * D)),
                conv_w=dense(ck[1], (cfg.conv_taps, D)),
                conv_out=dense(ck[2], (D, D)))


def pspecs(cfg, t):
    """The projection's columns over tp like any column-parallel matrix's (a
    shard holds a slice of B, C and x each, the partitioner's affair), the
    taps with the channels, W_out's rows."""
    return dict(conv_in=P(None, None, t), conv_w=P(None, None, t),
                conv_out=P(None, t, None))


def apply(x, layer, cfg, core, segments, marks):
    return _short_conv_mixer(_mixer_input(x, layer, cfg), layer, cfg,
                             segments), None


def _short_conv_mixer(h, layer, cfg, segments=()):
    """The gated short convolution on normed hidden states h (B, S, D): [B |
    C | x] = h W_in, three equal thirds in that order; y = (C * conv(B * x))
    W_out with conv a causal depthwise convolution of `conv_taps` taps a
    channel, c_t = sum_i k_i z_{t-(K-1)+i}, zeros before the row's first
    position. No activation, no bias, no norm and no state. The gates and
    the taps are one op (`ops.short_conv`) that reads the projection's
    output once and keeps it, the taps and the segments alone, so the mixer
    is the same kept or run again. `segments`, (the documents' numbers (B,
    S),) of packed rows, go to the op: a tap that would reach into another
    document reads zero. Scopes `sconv_proj`, `sconv_core`."""
    from kungfu_tpu.ops.short_conv import short_conv

    dt = cfg.dtype
    with jax.named_scope("sconv_proj"):
        bcx = h @ layer["conv_in"].astype(dt)
    with jax.named_scope("sconv_core"):
        y = short_conv(bcx, layer["conv_w"], *segments)
    with jax.named_scope("sconv_proj"):
        return y @ layer["conv_out"].astype(dt)
