"""ResNet-50 (flax) — the headline throughput benchmark workload.

Parity: the reference's benchmark model (README "Benchmark": ResNet-50
S-SGD throughput vs Horovod on 16 V100; BASELINE.md north-star metric is
ResNet-50 images/sec/chip). Standard bottleneck-v1.5 architecture.

TPU notes: NHWC layout (XLA-TPU native), bfloat16 compute with f32
batch-norm statistics and params.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

ModuleDef = Any


class BottleneckBlock(nn.Module):
    filters: int
    strides: Tuple[int, int]
    conv: ModuleDef
    norm: ModuleDef

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1), self.strides, name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return nn.relu(residual + y)


class SpaceToDepthStem(nn.Module):
    """The 7x7/s2 stem computed via space-to-depth (MLPerf TPU trick).

    A 7x7 conv over 3 input channels uses 3 of the MXU's 128 input lanes;
    block-decomposing the input into 2x2 blocks (12 channels) and the
    zero-padded 8x8 kernel into an equivalent 4x4 kernel over 12 channels
    quadruples MXU occupancy on the stem. The stored parameter stays the
    canonical (7, 7, in, filters) kernel — checkpoints are interchangeable
    with a plain conv stem, and the rewrite is numerically exact (same
    taps, reassociated)."""

    filters: int = 64
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, h, w, c = x.shape
        if h % 2 or w % 2:
            raise ValueError(f"space-to-depth stem needs even H/W, got {h}x{w}")
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (7, 7, c, self.filters),
            jnp.float32,
        ).astype(self.dtype)
        # zero-pad kernel at the front: out[i] = sum_u x[2i-4+u] w8[u]
        w8 = jnp.pad(kernel, ((1, 0), (1, 0), (0, 0), (0, 0)))
        w4 = (
            w8.reshape(4, 2, 4, 2, c, self.filters)
            .transpose(0, 2, 1, 3, 4, 5)
            .reshape(4, 4, 4 * c, self.filters)
        )
        xp = jnp.pad(x, ((0, 0), (4, 4), (4, 4), (0, 0)))
        hb, wb = (h + 8) // 2, (w + 8) // 2
        xs = (
            xp.reshape(b, hb, 2, wb, 2, c)
            .transpose(0, 1, 3, 2, 4, 5)
            .reshape(b, hb, wb, 4 * c)
        )
        out = jax.lax.conv_general_dilated(
            xs, w4, window_strides=(1, 1), padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        return out[:, : h // 2, : w // 2, :]


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    # space-to-depth stem: ~5% faster FORWARD on TPU (4x MXU occupancy on
    # conv1) but measured flat on the full train step (XLA already folds
    # stride-2 spatial dims into the conv), so inference configs opt in
    s2d_stem: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        norm = partial(
            nn.BatchNorm,
            use_running_average=not train,
            momentum=0.9,
            epsilon=1e-5,
            dtype=self.dtype,
        )
        x = x.astype(self.dtype)
        if self.s2d_stem and x.shape[1] % 2 == 0 and x.shape[2] % 2 == 0:
            x = SpaceToDepthStem(self.num_filters, self.dtype, name="conv_init")(x)
        else:
            x = conv(self.num_filters, (7, 7), (2, 2), padding=[(3, 3), (3, 3)], name="conv_init")(x)
        x = norm(name="bn_init")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = BottleneckBlock(
                    filters=self.num_filters * 2**i,
                    strides=strides,
                    conv=conv,
                    norm=norm,
                )(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32)(x)
        return x.astype(jnp.float32)


def resnet50(num_classes: int = 1000, dtype=jnp.bfloat16) -> ResNet:
    return ResNet(stage_sizes=[3, 4, 6, 3], num_classes=num_classes, dtype=dtype)


def resnet18_thin(num_classes: int = 10, dtype=jnp.bfloat16) -> ResNet:
    """Small variant for CPU-mesh tests."""
    return ResNet(stage_sizes=[1, 1], num_classes=num_classes, num_filters=8, dtype=dtype)


def init_resnet(key, model: ResNet, image_size: int = 224, batch: int = 1):
    dummy = jnp.zeros((batch, image_size, image_size, 3), jnp.float32)
    variables = model.init({"params": key}, dummy, train=False)
    return variables["params"], variables.get("batch_stats", {})


def resnet_loss(model: ResNet, params, batch_stats, batch):
    """Returns (loss, new_batch_stats)."""
    images, labels = batch
    logits, updates = model.apply(
        {"params": params, "batch_stats": batch_stats},
        images,
        train=True,
        mutable=["batch_stats"],
    )
    with jax.named_scope("head_loss"):
        logp = jax.nn.log_softmax(logits)
        loss = -jnp.mean(
            jnp.sum(jax.nn.one_hot(labels, logits.shape[-1]) * logp, axis=-1)
        )
    return loss, updates["batch_stats"]
