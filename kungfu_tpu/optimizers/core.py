"""Distributed optimizer wrappers as optax gradient transformations.

Capability parity with the reference optimizer framework
(srcs/python/kungfu/tensorflow/optimizers/core.py + sync_sgd.py, sma_sgd.py,
ada_sgd.py): each wrapper takes a base optax optimizer and injects
cross-replica communication into the update. TPU-first: the communication
is `lax.pmean`/`psum` traced into the SAME compiled program as the model
step, so there is no op-ordering problem (the NCCL scheduler's job,
scheduler.cpp:37-129, is subsumed by XLA's static schedule).

Where the reduction runs is the program's to say, and on the TPU it is
never hidden: XLA there emits every all-reduce as a synchronous operation
of the step program, beside independent matmuls as after a scan, so no
all-reduce overlaps backprop (read from the compiled programs and from the
chip, PERF.md, PR 29; the reference's claim for its NCCL scheduler does not
carry over). A wrapper's `update` gets the gradients when the whole
backward pass has produced them; for a model whose layers are a `lax.scan`
that is after the scan, four all-reduces of 435 MB for `bert_base`. Plain
S-SGD through `parallel.make_train_step` over an axis of more than one
member instead reduces a layer's gradients in the iteration of the backward
scan that produces them (`ops.collective.reduce_in_backward`): one
all-reduce of the layer's 28 MB an iteration, as synchronous as before,
0.6 ms a step less on four v5e chips. Every other wrapper, and `synchronous_sgd.update`
called by hand, reduces after the backward pass as it always did.

All wrappers must run inside a `shard_map` over the mesh axis they reduce
on (see kungfu_tpu.parallel.make_train_step).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax


class SynchronousSGD(optax.GradientTransformation):
    """What `synchronous_sgd` returns: an `optax.GradientTransformation`
    that also says it is plain S-SGD, over which axis and of which base, so
    that `make_train_step` may go past its `pmean` to the base where the
    loss has averaged every gradient already. A transformation built from
    it (`optax.chain`, `adaptive_sgd`) is not one."""

    def __new__(cls, base: optax.GradientTransformation, axis_name: str):
        def update_reduced(grads, state, params=None, **extra):
            with jax.named_scope("optimizer_update"):
                return base.update(grads, state, params, **extra)

        def update(grads, state, params=None, **extra):
            with jax.named_scope("grad_allreduce"):
                grads = jax.tree.map(lambda g: lax.pmean(g, axis_name), grads)
            return update_reduced(grads, state, params, **extra)

        self = super().__new__(cls, base.init, update)
        self.axis_name = axis_name
        # the base's update, for gradients that are the axis' mean already
        self.update_reduced = update_reduced
        return self


def synchronous_sgd(base: optax.GradientTransformation, axis_name: str = "dp") -> SynchronousSGD:
    """S-SGD (parity: SynchronousSGDOptimizer, sync_sgd.py:15-109): average
    gradients over the axis before the base update. `update` reduces every
    leaf after the backward pass (XLA combines the per-leaf psums into a few
    all-reduces, each a synchronous operation on the TPU: none overlaps
    anything); through `make_train_step`, with a loss that calls
    `ops.collective.reduce_in_backward`, each leaf is reduced inside the
    backward pass instead. Once either way, to the same values."""
    return SynchronousSGD(base, axis_name)


class _ZeroState(NamedTuple):
    base: optax.OptState


def zero_sharded(
    base: optax.GradientTransformation,
    axis_size: int,
    axis_name: str = "dp",
) -> optax.GradientTransformation:
    """ZeRO-1 sharded weight update on the device plane (ISSUE 11;
    "Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
    Training", arXiv:2004.13336): gradients are reduce-scattered
    (`lax.psum_scatter`) so each replica averages only its 1/k shard,
    the base optimizer updates that shard — its state exists for the
    shard only, the k-fold state/FLOP cut — and the updated parameters
    are re-assembled with `lax.all_gather`. The returned updates equal
    S-SGD's up to float reassociation (psum_scatter associates like
    psum), at 1/k optimizer state and update FLOPs per replica.

    Each leaf is flattened and zero-padded to a multiple of
    ``axis_size`` (the mapped axis size, passed explicitly so state
    shapes are static); padding lanes carry zero gradients, so
    stateful base transforms see zeros there on every replica alike.
    Like the other wrappers this must run inside a `shard_map` over
    `axis_name` — init() included, since each replica initializes state
    for ITS shard (use out_specs ``P(axis_name)`` on the state so the
    global view concatenates the shards)."""
    k = int(axis_size)
    if k < 1:
        raise ValueError(f"axis_size must be >= 1, got {axis_size}")

    def _shard_len(n: int) -> int:
        return -(-n // k)

    def _pad_flat(leaf):
        flat = leaf.reshape(-1)
        m = _shard_len(flat.size)
        return jnp.pad(flat, (0, m * k - flat.size)), m

    def _my_shard(leaf):
        padded, m = _pad_flat(leaf)
        idx = lax.axis_index(axis_name)
        return lax.dynamic_slice(padded, (idx * m,), (m,))

    def init(params):
        return _ZeroState(base=base.init(jax.tree.map(_my_shard, params)))

    def update(grads, state, params=None, **extra):
        if params is None:
            raise ValueError("zero_sharded requires params")
        # reduce-scatter + average: each replica holds the mean of its
        # 1/k gradient shard (psum_scatter of the padded flat leaf)
        def g_shard(g):
            padded, _ = _pad_flat(g)
            return lax.psum_scatter(
                padded, axis_name, scatter_dimension=0, tiled=True
            ) / k

        with jax.named_scope("grad_allreduce"):
            grad_shards = jax.tree.map(g_shard, grads)
        param_shards = jax.tree.map(_my_shard, params)
        with jax.named_scope("optimizer_update"):
            shard_updates, base_state = base.update(
                grad_shards, state.base, param_shards, **extra
            )
            new_shards = optax.apply_updates(param_shards, shard_updates)

        # all-gather the updated shards and express the result as an
        # optax update (new - old), unpadded and reshaped per leaf
        def regather(new_shard, p):
            full = lax.all_gather(new_shard, axis_name, tiled=True)
            return full[: p.size].reshape(p.shape) - p

        updates = jax.tree.map(regather, new_shards, params)
        return updates, _ZeroState(base=base_state)

    return optax.GradientTransformation(init, update)


class _SMAState(NamedTuple):
    base: optax.OptState


def synchronous_averaging(
    base: optax.GradientTransformation,
    axis_name: str = "dp",
    alpha: float = 0.1,
) -> optax.GradientTransformation:
    """SMA / EA-SGD (parity: SynchronousAveragingOptimizer, sma_sgd.py:9-75):
    each step blends params toward the cluster average with weight ``alpha``,
    then applies the LOCAL gradients. Converges better than S-SGD at large
    cluster sizes (reference README: 75% vs 59% top-1 at 16 workers)."""

    def init(params):
        return _SMAState(base=base.init(params))

    def update(grads, state, params, **extra):
        if params is None:
            raise ValueError("synchronous_averaging requires params")
        # the scope is named for what it is in S-SGD; here the reduction
        # averages the parameters, not the gradients
        with jax.named_scope("grad_allreduce"):
            avg = jax.tree.map(lambda p: lax.pmean(p, axis_name), params)
        with jax.named_scope("optimizer_update"):
            base_updates, base_state = base.update(
                grads, state.base, params, **extra)
        # total update = alpha * (avg - p) + base_update(local grads)
        updates = jax.tree.map(
            lambda a, p, u: alpha * (a - p) + u, avg, params, base_updates
        )
        return updates, _SMAState(base=base_state)

    return optax.GradientTransformation(init, update)


class _AdaSGDState(NamedTuple):
    step: jnp.ndarray
    sma: optax.OptState
    ssgd: optax.OptState


def adaptive_sgd(
    base: optax.GradientTransformation,
    change_step: int,
    axis_name: str = "dp",
    alpha: float = 0.1,
) -> optax.GradientTransformation:
    """AdaptiveSGD (parity: AdaSGDOptimizer, ada_sgd.py:12-84): SMA before
    ``change_step``, S-SGD after. The switch is a `lax.cond` so one compiled
    program covers both phases (no recompilation at the switch). At the
    switch step the update folds in a rank-0 re-broadcast of the params
    (parity: AdaSGDHook re-broadcast) — SMA's local-gradient steps let
    replicas diverge, and S-SGD alone would freeze that divergence in."""
    sma = synchronous_averaging(base, axis_name, alpha)
    ssgd = synchronous_sgd(base, axis_name)

    def init(params):
        return _AdaSGDState(
            step=jnp.zeros((), jnp.int32),
            sma=sma.init(params),
            ssgd=ssgd.init(params),
        )

    def update(grads, state, params, **extra):
        def run_sma(_):
            u, s = sma.update(grads, state.sma, params, **extra)
            return u, _AdaSGDState(state.step + 1, s, state.ssgd)

        def run_ssgd(_):
            u, s = ssgd.update(grads, state.ssgd, params, **extra)
            if params is not None:
                # switch step: fold in the rank-0 re-sync broadcast
                from kungfu_tpu.ops.collective import broadcast

                at_switch = state.step == change_step
                u = jax.tree.map(
                    lambda ui, p: ui
                    + at_switch.astype(ui.dtype)
                    * (broadcast(p, axis_name) - p).astype(ui.dtype),
                    u,
                    params,
                )
            return u, _AdaSGDState(state.step + 1, state.sma, s)

        return lax.cond(state.step < change_step, run_sma, run_ssgd, None)

    return optax.GradientTransformation(init, update)
