"""Sharded (pjit-style) train steps: DP x TP over a mesh.

The jit-with-shardings path: params carry PartitionSpecs (tensor
parallelism), the batch shards over 'dp', and XLA's SPMD partitioner
derives every collective (grad AllReduce over dp, activation collectives
over tp) from the annotations. This is the TPU-idiomatic generalization of
the reference's data-parallel-only engine — the "strategy" is a mesh-axis
layout instead of a communication graph (SURVEY.md §7 stage 4).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def named(mesh: Mesh, spec_tree):
    """Map a PartitionSpec tree to NamedShardings."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda s: isinstance(s, P),
    )


def make_sharded_train_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    param_specs,
    batch_spec: P = P("dp"),
    donate: bool = True,
):
    """Build a jitted SPMD train step with sharded params.

    loss_fn(params, batch) -> scalar. Optimizer state keeps the shardings
    it arrives with; build it with `init_opt_state` so that what the step
    returns is placed like what it took.
    Returns step(params, opt_state, batch) -> (params, opt_state, loss).
    """

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    param_sh = named(mesh, param_specs)
    step = jax.jit(
        train_step,
        in_shardings=(param_sh, None, named(mesh, batch_spec)),
        donate_argnums=(0, 1) if donate else (),
    )
    return step


def shard_params(params, mesh: Mesh, param_specs):
    return jax.device_put(params, named(mesh, param_specs))


def init_opt_state(optimizer: optax.GradientTransformation, params, mesh: Mesh):
    """optimizer.init(params), placed for make_sharded_train_step.

    Parameter-shaped leaves (optax's moments) take their parameter's
    sharding from `zeros_like`. Leaves that depend on no parameter — the
    step count — come out on one device; the step would return them
    replicated over the mesh, and the changed input sharding would
    compile the whole step a second time. They are replicated here.
    """
    replicated = NamedSharding(mesh, P())
    return jax.tree.map(
        lambda x: x if isinstance(x.sharding, NamedSharding)
        else jax.device_put(x, replicated),
        optimizer.init(params),
    )
