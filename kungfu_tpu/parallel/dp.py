"""Data-parallel training-step factory.

The TPU-native replacement for "wrap your optimizer and run sess.run":
given a loss and a (possibly communication-injecting) optax optimizer,
build ONE jitted SPMD program that
  - shards the batch over the mesh's data axis,
  - computes local grads,
  - lets the optimizer's traced collectives (pmean etc.) synchronize,
  - applies updates.
Params/optimizer state are replicated across the dp axis. There is no hand
scheduling of collectives (contrast with the reference's NCCL scheduler +
fuse-ordering workarounds, sync_sgd.py:81-94), and none is hidden either:
on the TPU XLA runs an all-reduce as a synchronous operation of the step
program, so the gradients' all-reduce is exposed whole wherever it stands
(7.69 ms of `bert_base`'s 66.67 ms step on four v5e chips after the
backward scan, 7.39 ms inside it; PERF.md, PR 29). For plain S-SGD
(`optimizers.synchronous_sgd` over `axis_name`, more than one member on the
axis) the step lets the loss reduce each gradient where its backward pass
produces it (`ops.collective.reduce_in_backward`, which
`models.transformer` calls in its layer scan) and applies the base update
to what comes out. A loss that does not, any other optimizer and an axis of
one member get the step as it always was.
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
    # deferred: nothing of the optimizers is imported before a step is built
    from kungfu_tpu.ops import collective
    from kungfu_tpu.optimizers import core

    in_backward = (isinstance(optimizer, core.SynchronousSGD)
                   and optimizer.axis_name == axis_name
                   and mesh.shape[axis_name] > 1)

    def local_step(params, opt_state, batch):
        reduced = False
        if in_backward:
            with collective.reducing_in_backward(axis_name) as sync:
                loss, grads = jax.value_and_grad(sync.watching(loss_fn))(
                    params, batch)
            reduced = sync.covers_all()
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        _record_grad_sync(grads, reduced, in_backward)
        # forward and backward need no scope: under value_and_grad JAX names
        # their ops jvp(<scope>) and transpose(jvp(<scope>)) (docs/telemetry.md)
        with jax.named_scope("optimizer"):
            # S-SGD's update either way: a loss that has averaged every
            # gradient leaves the base's part of it, and a second pmean of a
            # mean would change no value and cost the all-reduce again
            update = optimizer.update_reduced if reduced else optimizer.update
            updates, opt_state = update(grads, opt_state, params)
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


def _record_grad_sync(grads, reduced: bool, in_backward: bool) -> None:
    """At trace time, who reduces the step's gradients: the gauges
    `kungfu_grad_bytes_reduced_in_backward` and
    `kungfu_grad_bytes_reduced_by_optimizer` of the step built last
    (docs/telemetry.md). Both 0 where there is nothing to reduce or an
    optimizer other than plain S-SGD does it its own way."""
    from kungfu_tpu.telemetry import metrics

    nbytes = sum(g.size * g.dtype.itemsize for g in jax.tree.leaves(grads))
    metrics.gauge(
        "kungfu_grad_bytes_reduced_in_backward",
        "gradient bytes a step that the loss averages inside its backward pass",
    ).set(nbytes if reduced else 0)
    metrics.gauge(
        "kungfu_grad_bytes_reduced_by_optimizer",
        "gradient bytes a step left to synchronous_sgd's pmean after the pass",
    ).set(nbytes if in_backward and not reduced else 0)


def replicate(tree, mesh: Mesh):
    """Place a pytree fully replicated on the mesh."""
    return jax.device_put(tree, NamedSharding(mesh, P()))


def shard_batch(batch, mesh: Mesh, axis_name: str = "dp"):
    """Place a batch sharded over the data axis (leading dim)."""
    return jax.device_put(batch, NamedSharding(mesh, P(axis_name)))
