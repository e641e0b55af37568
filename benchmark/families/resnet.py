"""The ResNet family: kungfu_tpu.models.resnet under a configuration file
that holds the paper's table (stage sizes, widths, classes, image size).

The step cannot go through `make_train_step`: the loss carries batch-norm
statistics as auxiliary state, which that factory has no place for (PERF.md,
Open questions). So the family gives the per-chip body of a step itself
(`local_step`) — the optimizer's traced `pmean` on the
gradients, the batch statistics `pmean`ed like them — and the traffic's step
factory (`benchmark/steps/`) puts it over the mesh.
"""

from __future__ import annotations

import numpy as np

REFERENCE_SAMPLES = 4  # images in the reference check

# bfloat16 compute against the float32 reference, as in the transformer
# family, through 53 convolutions each followed by a batch-norm. Measured on
# the chip at resnet50's size over 29 runs (PR 23): the loss differs by 1.0e-5
# to 3.0e-4 of itself and the gradients, as one vector, by 3.9 to 5.0 %. A
# larger sample does not bring that down (on the CPU at the real size, same
# program and reference: 4.1-4.2 % on 4 images, 3.7-3.9 % on 32), so it is the
# rounding and not the batch statistics of four images. The tolerances are
# three times the largest loss error and one and a half times the largest
# gradient error; an 8-bit float's compute (16 times the error) fails both.
LOSS_RTOL = 1e-3
GRAD_RTOL = 7.5e-2


def model(cfg: dict):
    import jax.numpy as jnp

    from kungfu_tpu.models.resnet import ResNet

    return ResNet(
        stage_sizes=list(cfg["stage_sizes"]),
        num_classes=cfg["num_classes"],
        num_filters=cfg["num_filters"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
    )


def init(cfg: dict, seed: int):
    """The train state {"params", "batch_stats"}, made on the device in one
    jitted call from the seed."""
    import jax

    from kungfu_tpu.models.resnet import init_resnet

    net = model(cfg)

    def make(key):
        params, stats = init_resnet(key, net, cfg["image_size"], batch=2)
        return {"params": params, "batch_stats": stats}

    return jax.jit(make)(jax.random.PRNGKey(seed))


def local_step(cfg: dict, optimizer, axis_name: str):
    """The per-chip body of step(state, opt_state, batch) -> (state,
    opt_state, loss); batch is this chip's (images, labels)."""
    import jax
    import optax
    from jax import lax

    from kungfu_tpu.models.resnet import resnet_loss

    net = model(cfg)

    def local_step(state, opt_state, batch):
        def loss_of(params):
            return resnet_loss(net, params, state["batch_stats"], batch)

        (loss, stats), grads = jax.value_and_grad(loss_of, has_aux=True)(
            state["params"])
        # synchronous_sgd's update pmeans the gradients (the all-reduce)
        updates, opt_state = optimizer.update(grads, opt_state, state["params"])
        params = optax.apply_updates(state["params"], updates)
        stats = jax.tree.map(lambda x: lax.pmean(x, axis_name), stats)
        return ({"params": params, "batch_stats": stats}, opt_state,
                lax.pmean(loss, axis_name))

    return local_step


def trainable(state):
    """The part of the state the optimizer updates."""
    return state["params"]


def head_width(cfg: dict) -> int:
    """The output dimension of the head, which the configuration states in
    `head_dtype`."""
    return cfg["num_classes"]


def program_loss_and_grads(cfg: dict):
    """The jitted (state, batch) -> (loss, gradients of `trainable(state)`),
    on one device (no mesh): what the reference is compared with.
    Everything seeded is an argument: a closed-over sample would be a
    constant of the program, and every seed a compile-cache miss."""
    import jax

    from kungfu_tpu.models.resnet import resnet_loss

    net = model(cfg)

    def loss_of(params, stats, batch):
        return resnet_loss(net, params, stats, batch)[0]

    def loss_and_grads(state, batch):
        return jax.value_and_grad(loss_of)(
            state["params"], state["batch_stats"], batch)

    return jax.jit(loss_and_grads)


def reference_loss_and_grads(cfg: dict, state, batch):
    from benchmark.reference import resnet as ref

    return ref.loss_and_grads(state["params"], batch)


def host_batch(cfg: dict, seed: int, i: int, n: int):
    """The i-th host batch of n samples: (images, labels). Images are
    byte-valued pixels normalised to about unit scale, in the compute type
    (bfloat16: 2 bytes a pixel cross to the device, as a decoded and
    normalised input pipeline would send); labels are drawn from the first
    `label_classes_used` classes."""
    import ml_dtypes

    rng = np.random.default_rng([seed, i])
    size, ch = cfg["image_size"], cfg["image_channels"]
    pixels = rng.integers(0, 256, (n, size, size, ch), dtype=np.uint8)
    table = ((np.arange(256, dtype=np.float32) - 127.5) / 64.0).astype(
        np.dtype(getattr(ml_dtypes, cfg["compute_dtype"], cfg["compute_dtype"])))
    labels = rng.integers(0, cfg["label_classes_used"], (n,), dtype=np.int32)
    return table[pixels], labels


def conv_macs(cfg: dict) -> int:
    """Multiply-adds of one forward pass over one image, convolutions and
    the classifier: every output position of a k x k convolution from c_in
    to c_out channels costs k*k*c_in*c_out."""
    size = cfg["image_size"] // 2  # the 7x7 stem has stride 2
    width = cfg["num_filters"]
    macs = size * size * 7 * 7 * cfg["image_channels"] * width
    size //= 2  # 3x3 max pool, stride 2
    c_in = width
    expansion = cfg["bottleneck_expansion"]
    for i, blocks in enumerate(cfg["stage_sizes"]):
        f = width * 2 ** i
        for j in range(blocks):
            stride = 2 if i > 0 and j == 0 else 1
            out = size // stride
            macs += size * size * c_in * f  # 1x1, before the stride
            macs += out * out * 9 * f * f  # 3x3, carries the stride (v1.5)
            macs += out * out * f * expansion * f  # 1x1
            if j == 0:  # projection shortcut where the shape changes
                macs += out * out * c_in * expansion * f
            size, c_in = out, expansion * f
    return macs + c_in * cfg["num_classes"]


def flops_per_sample(cfg: dict) -> float:
    """Operations forward and backward require for one image: 2 per
    multiply-add, backward twice the forward. Norms, activations and
    pooling are not matmul work and are not counted."""
    return 3.0 * 2.0 * conv_macs(cfg)
