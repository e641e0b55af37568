"""Hierarchical allreduce: ICI psum within a slice x host-plane allreduce
across slices — the multi-slice data path.

Capability parity: the reference's bridged hierarchical collective
(srcs/cpp/src/tensorflow/ops/gpu/collective.cpp:108-162 — local NCCL
reduce, CPU cross-host allreduce, local NCCL bcast; cross strategies
srcs/go/kungfu/session/strategy.go:188-210). TPU mapping: each kfrun
worker owns one jax world (a slice / ICI domain); gradient sync composes

  1. ``lax.pmean`` over the in-world mesh axis (XLA collective on ICI),
  2. a host-plane allreduce across worlds (DCN), entered from INSIDE the
     jitted step via ``jax.experimental.io_callback`` so the training step
     stays one compiled program per world.

Semantics: hierarchical mean — mean over worlds of the in-world mean.
With equal-sized worlds this equals the global mean over all replicas
(exactly, when the addends are exactly representable; to rounding
otherwise, like any reassociated float sum).
"""

from __future__ import annotations

from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.experimental import io_callback
from jax.sharding import PartitionSpec as P

from kungfu_tpu.base.ops import ReduceOp
from kungfu_tpu.base.workspace import Workspace


class CrossSliceReducer:
    """Host-side cross-world gradient averaging, callable from io_callback.

    Keeps a per-instance step counter so every collective round gets fresh
    wire names (all worlds advance in lockstep — the host collective
    itself is the synchronizer). Leaves are fused per dtype into one
    workspace each, reduced concurrently via the session group op, and a
    single division by the world count lands after the wire SUM (the
    reference's reduce-then-scale order)."""

    def __init__(self, peer=None, name: str = "hier", compress: str = ""):
        """compress="bf16": f32/f64 leaves cross the DCN wire as bfloat16
        (half/quarter the bytes; the in-slice ICI psum stays full
        precision, so only the CROSS-slice term is rounded — the standard
        gradient-compression trade for bandwidth-bound DCN links).
        Integer and already-half-precision leaves pass through."""
        self._peer = peer
        self.name = name
        self.step = 0
        if compress not in ("", "bf16"):
            raise ValueError(f"unknown compression {compress!r}")
        self.compress = compress

    def _session(self):
        if self._peer is None:
            from kungfu_tpu.peer import get_default_peer

            self._peer = get_default_peer()
        return self._peer.current_session()

    def __call__(self, *leaves: np.ndarray) -> List[np.ndarray]:
        sess = self._session()
        step = self.step
        self.step += 1
        n = sess.size
        if n <= 1:
            return [np.asarray(l) for l in leaves]
        arrs = [np.ascontiguousarray(l) for l in leaves]
        orig_dtypes = [a.dtype for a in arrs]
        if self.compress == "bf16":
            import ml_dtypes

            arrs = [
                a.astype(ml_dtypes.bfloat16)
                if np.issubdtype(a.dtype, np.floating) and a.dtype.itemsize > 2
                else a
                for a in arrs
            ]
        outs = [np.empty_like(a) for a in arrs]
        ws = [
            Workspace(
                send=a.reshape(-1),
                recv=o.reshape(-1),
                op=ReduceOp.SUM,
                name=f"kungfu::hier:{self.name}:{step}:{i}",
            )
            for i, (a, o) in enumerate(zip(arrs, outs))
        ]
        sess.group_all_reduce(ws)
        return [self._mean(o, n, dt) for o, dt in zip(outs, orig_dtypes)]

    @staticmethod
    def _mean(o: np.ndarray, n: int, out_dtype=None) -> np.ndarray:
        """sum/n, cast ONCE to out_dtype (default: o's dtype) — the
        compressed path divides the bf16 wire sum at f32 precision and
        lands directly in the original f32/f64 without an intermediate
        bf16 rounding. NOTE the branch check must be issubdtype(...,
        integer), not floating: ml_dtypes bfloat16 has numpy kind 'V', so
        a floating-check would send bf16 down the integer floor-division
        branch and zero out sub-1.0 gradient sums."""
        if out_dtype is None:
            out_dtype = o.dtype
        if np.issubdtype(o.dtype, np.integer):
            return (o // n).astype(out_dtype, copy=False)
        if o.dtype.itemsize < 4:
            # bf16/f16/f8 wire sums: divide at f32 precision
            return (o.astype(np.float32) / np.float32(n)).astype(
                out_dtype, copy=False
            )
        return (o / o.dtype.type(n)).astype(out_dtype, copy=False)


def cross_slice_mean(tree, reducer: CrossSliceReducer):
    """Average a pytree across worlds on the host plane, from inside jit.

    Call OUTSIDE any shard_map region (on replicated values) so the
    callback fires once per world per step, not once per device. The
    callback is pinned to device 0 (XLA's SPMD partitioner refuses a
    REPLICATED side-effecting custom-call); XLA inserts the gather/
    broadcast around the pinned call."""
    from jax.sharding import SingleDeviceSharding

    leaves, treedef = jax.tree.flatten(tree)
    shapes = [jax.ShapeDtypeStruct(l.shape, l.dtype) for l in leaves]
    # ordered=False: the ordered variant threads a replicated token that
    # XLA's partitioner rejects next to a device-pinned custom-call. One
    # callback per step + a data dependency on its results gives the
    # needed sequencing anyway (steps are serialized by the param chain).
    out = io_callback(
        reducer,
        shapes,
        *leaves,
        ordered=False,
        sharding=SingleDeviceSharding(jax.devices()[0]),
    )
    return jax.tree.unflatten(treedef, out)


def make_hier_train_step(
    loss_fn: Callable,
    opt: optax.GradientTransformation,
    mesh,
    axis_name: str = "dp",
    peer=None,
    name: str = "hier",
    batch_spec: Optional[P] = None,
    donate: bool = False,
    compress: str = "",
):
    """One jitted S-SGD step with hierarchical gradient sync.

    loss_fn(params, batch) -> scalar loss, evaluated per-shard inside a
    shard_map over `axis_name`; gradients are pmean'd over the in-world
    mesh (ICI), then averaged across worlds on the host plane, then the
    optax update applies identically in every world.
    """
    reducer = CrossSliceReducer(peer=peer, name=name, compress=compress)
    bspec = batch_spec if batch_spec is not None else P(axis_name)

    def local_grads(params, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        grads = jax.tree.map(lambda g: lax.pmean(g, axis_name), grads)
        return lax.pmean(loss, axis_name), grads

    sharded_grads = jax.shard_map(
        local_grads,
        mesh=mesh,
        in_specs=(P(), bspec),
        out_specs=(P(), P()),
        check_vma=False,
    )

    @jax.jit
    def step(params, opt_state, batch):
        loss, grads = sharded_grads(params, batch)
        grads = cross_slice_mean(grads, reducer)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    if donate:
        step = jax.jit(step.__wrapped__, donate_argnums=(0, 1))
    return step


def synchronous_sgd_hierarchical(
    base: optax.GradientTransformation,
    axis_name: str = "dp",
    peer=None,
    name: str = "hier-ssgd",
) -> optax.GradientTransformation:
    """S-SGD whose gradient averaging is hierarchical (in-world pmean +
    cross-world host allreduce). Use inside shard_map ONLY via
    make_hier_train_step; as a bare optax transformation it must run on
    replicated values (the cross-world callback fires per call site)."""
    reducer = CrossSliceReducer(peer=peer, name=name)

    def init(params):
        return base.init(params)

    def update(grads, state, params=None, **extra):
        grads = jax.tree.map(lambda g: lax.pmean(g, axis_name), grads)
        grads = cross_slice_mean(grads, reducer)
        return base.update(grads, state, params, **extra)

    return optax.GradientTransformation(init, update)
