"""Plain float32 reference of the ResNet cell's loss, written from the
layer equations (He et al., arXiv:1512.03385, with the stride of a
down-sampling bottleneck in its 3x3 convolution). It imports nothing from
kungfu_tpu.models; it reads the program's parameter tree by its names
(conv_init, bn_init, BottleneckBlock_<i>.{Conv_0..2, BatchNorm_0..2,
conv_proj, norm_proj}, Dense_0).

    conv    : NHWC x HWIO, "SAME" padding (the stem pads 3 on each side)
    bn(x)   : (x - mean_b) / sqrt(var_b + 1e-5) * scale + bias, statistics
              of the batch over N, H, W (training mode), var = E[x^2] - E[x]^2
    block   : relu(shortcut + bn(conv1x1(relu(bn(conv3x3_s(relu(bn(conv1x1(x)))))))))
    loss    : mean_n -log softmax(mean_hw(x) W + b)[label_n]
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _conv(x, kernel, stride=1, padding="SAME"):
    return lax.conv_general_dilated(
        x, kernel.astype(jnp.float32), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(x * x, axis=(0, 1, 2)) - mean * mean
    return (x - mean) / jnp.sqrt(var + 1e-5) * p["scale"] + p["bias"]


def loss(params, batch):
    images, labels = batch
    x = images.astype(jnp.float32)
    x = _conv(x, params["conv_init"]["kernel"], 2, [(3, 3), (3, 3)])
    x = jax.nn.relu(_bn(x, params["bn_init"]))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
    n_blocks = sum(1 for k in params if k.startswith("BottleneckBlock_"))
    for i in range(n_blocks):
        p = params[f"BottleneckBlock_{i}"]
        # a block that changes the shape has a projection; it strides
        # exactly when its output is spatially smaller, i.e. when it is not
        # the first block (whose projection only widens)
        stride = 2 if "conv_proj" in p and i > 0 else 1
        y = jax.nn.relu(_bn(_conv(x, p["Conv_0"]["kernel"]), p["BatchNorm_0"]))
        y = jax.nn.relu(_bn(_conv(y, p["Conv_1"]["kernel"], stride), p["BatchNorm_1"]))
        y = _bn(_conv(y, p["Conv_2"]["kernel"]), p["BatchNorm_2"])
        if "conv_proj" in p:
            x = _bn(_conv(x, p["conv_proj"]["kernel"], stride), p["norm_proj"])
        x = jax.nn.relu(x + y)
    x = jnp.mean(x, axis=(1, 2))
    logits = x @ params["Dense_0"]["kernel"] + params["Dense_0"]["bias"]
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def loss_and_grads(params, batch):
    """Float32 throughout; on a TPU a float32 matmul or convolution runs
    in lower precision unless this is set."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(loss))(params, batch)
