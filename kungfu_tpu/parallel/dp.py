"""Data-parallel training-step factory.

The TPU-native replacement for "wrap your optimizer and run sess.run":
given a loss and a (possibly communication-injecting) optax optimizer,
build ONE jitted SPMD program that
  - shards the batch over the mesh's data axis,
  - computes local grads,
  - lets the optimizer's traced collectives (pmean etc.) synchronize,
  - applies updates.
Params/optimizer state are replicated across the dp axis. XLA overlaps the
grad AllReduce with backprop automatically (no hand scheduling — contrast
with the reference's NCCL scheduler + fuse-ordering workarounds,
sync_sgd.py:81-94).
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_train_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    axis_name: str = "dp",
    batch_spec: Optional[P] = None,
    donate: bool = True,
):
    """Build a jitted SPMD train step.

    loss_fn(params, batch) -> scalar loss (per local shard).
    Returns step(params, opt_state, batch) -> (params, opt_state, loss)
    where loss is the mean over the axis.
    """
    if batch_spec is None:
        batch_spec = P(axis_name)

    def local_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        # forward and backward need no scope: under value_and_grad JAX names
        # their ops jvp(<scope>) and transpose(jvp(<scope>)) (docs/telemetry.md)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        loss = jax.lax.pmean(loss, axis_name)
        return params, opt_state, loss

    spmd = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(), P(), batch_spec),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(spmd, donate_argnums=(0, 1) if donate else ())


def replicate(tree, mesh: Mesh):
    """Place a pytree fully replicated on the mesh."""
    return jax.device_put(tree, NamedSharding(mesh, P()))


def shard_batch(batch, mesh: Mesh, axis_name: str = "dp"):
    """Place a batch sharded over the data axis (leading dim)."""
    return jax.device_put(batch, NamedSharding(mesh, P(axis_name)))
