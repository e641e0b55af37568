"""Device collectives: XLA ops over ICI mesh axes.

Capability parity: the reference's collective op kernels
(srcs/cpp/src/tensorflow/ops/cpu/collective.cpp, gpu/collective.cpp and the
python wrappers srcs/python/kungfu/tensorflow/ops/collective.py). On TPU
these are not graph-walks over TCP nor NCCL calls: each op lowers to an XLA
collective (AllReduce / AllGather / CollectivePermute) that rides the ICI
torus inside a compiled program. XLA's static schedule subsumes the
reference's NCCL scheduler (srcs/cpp/src/nccl/scheduler.cpp) — cross-worker
op order is fixed at compile time, so no runtime order negotiation exists.

All functions here must be called inside a `shard_map`/`pmap` context where
`axis_name` is bound. The fuse/defuse helpers mirror the reference's tensor
packing (ops/__init__.py:29-46) and are pure reshapes that XLA fuses away.

`reduce_in_backward`, last, is the identity a loss passes its parameters
through so that their gradients are averaged in the place of the backward
pass that produces them: the step that wants it declares the axis
(`reducing_in_backward`), a model that offers it knows of no optimizer.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from kungfu_tpu.base.ops import ReduceOp

_PSUM_OPS = {
    ReduceOp.SUM: lax.psum,
    ReduceOp.MIN: lax.pmin,
    ReduceOp.MAX: lax.pmax,
}


def all_reduce(x: jax.Array, axis_name: str = "dp", op: ReduceOp = ReduceOp.SUM) -> jax.Array:
    """AllReduce one array over a mesh axis. SUM/MIN/MAX lower to a single
    XLA AllReduce; PROD via exp/log is intentionally unsupported — the
    reference only uses SUM/MIN/MAX on device."""
    try:
        fn = _PSUM_OPS[op]
    except KeyError:
        raise ValueError(f"unsupported device reduce op: {op!r}") from None
    return fn(x, axis_name)


def all_average(x: jax.Array, axis_name: str = "dp") -> jax.Array:
    return lax.pmean(x, axis_name)


def group_all_reduce(xs, axis_name: str = "dp", op: ReduceOp = ReduceOp.SUM):
    """AllReduce a pytree of arrays (one logical call; XLA may combine the
    AllReduces — the analogue of the reference's group_all_reduce)."""
    return jax.tree.map(lambda x: all_reduce(x, axis_name, op), xs)


def group_all_average(xs, axis_name: str = "dp"):
    return jax.tree.map(lambda x: lax.pmean(x, axis_name), xs)


def all_gather(x: jax.Array, axis_name: str = "dp", axis: int = 0, tiled: bool = False) -> jax.Array:
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def broadcast(x: jax.Array, axis_name: str = "dp", root: int = 0) -> jax.Array:
    """Broadcast root's value to all ranks on the axis.

    Lowered as a masked psum (one XLA AllReduce) — the standard XLA idiom;
    replaces the reference's broadcast graph walk.
    """
    idx = lax.axis_index(axis_name)
    zero = jnp.zeros_like(x)
    return lax.psum(jnp.where(idx == root, x, zero), axis_name)


def group_broadcast(xs, axis_name: str = "dp", root: int = 0):
    return jax.tree.map(lambda x: broadcast(x, axis_name, root), xs)


def subset_all_reduce(
    x: jax.Array,
    mask: jax.Array,
    axis_name: str = "dp",
) -> jax.Array:
    """AllReduce over a subset of ranks (capability parity with
    KungfuSubsetAllReduce, ops/cpu/collective.cpp:105-147).

    mask: bool/int array indexed by rank on the axis; ranks with mask==0
    contribute zero and receive the subset sum. On TPU a static subset is
    better expressed as a smaller mesh axis; this dynamic-mask form supports
    elastic subsets without recompilation.
    """
    idx = lax.axis_index(axis_name)
    m = mask[idx].astype(x.dtype)
    return lax.psum(x * m, axis_name)


# ---------------------------------------------------------------------------
# fuse / defuse: pack a list of tensors into one flat buffer and back.
# ---------------------------------------------------------------------------

def fuse(xs: Sequence[jax.Array]) -> jax.Array:
    """Concatenate raveled tensors (reference fuse, ops/__init__.py:29-34)."""
    return jnp.concatenate([jnp.ravel(x) for x in xs])


def defuse(fused: jax.Array, shapes: Sequence[Tuple[int, ...]]) -> List[jax.Array]:
    """Split a fused buffer back into tensors of the given shapes."""
    out = []
    off = 0
    for shape in shapes:
        size = int(np.prod(shape)) if shape else 1
        out.append(jnp.reshape(fused[off:off + size], shape))
        off += size
    return out


def fuse_pytree(tree):
    """Pack a pytree into (flat_vector, unflatten_fn)."""
    leaves, treedef = jax.tree.flatten(tree)
    shapes = [l.shape for l in leaves]
    dtypes = [l.dtype for l in leaves]
    fused = fuse(leaves)

    def unflatten(vec):
        parts = defuse(vec, shapes)
        parts = [p.astype(dt) for p, dt in zip(parts, dtypes)]
        return jax.tree.unflatten(treedef, parts)

    return fused, unflatten


class _GradSync:
    """One traced step's record: the axis `make_train_step` declared, the
    parameter leaves the loss was called with, and those of them whose
    gradients the loss has averaged over that axis itself."""

    def __init__(self, axis_name: str):
        self.axis_name = axis_name
        self.seen, self.reduced = [], []

    def watching(self, loss_fn):
        """`loss_fn`, noting the parameters it is differentiated at."""
        def watched(params, *args):
            self.seen.extend(jax.tree.leaves(params))
            return loss_fn(params, *args)

        return watched

    def covers_all(self) -> bool:
        return bool(self.seen) and all(
            any(leaf is r for r in self.reduced) for leaf in self.seen)


_grad_sync = contextvars.ContextVar("grad_sync", default=None)


@contextlib.contextmanager
def reducing_in_backward(axis_name: str):
    """While this is open (`make_train_step` opens it around the trace of
    `value_and_grad(loss_fn)`), `reduce_in_backward` averages over
    `axis_name`. Yields the trace's `_GradSync`."""
    sync = _GradSync(axis_name)
    token = _grad_sync.set(sync)
    try:
        yield sync
    finally:
        _grad_sync.reset(token)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mean_cotangent(axis_name, tree):
    return tree


def _mean_cotangent_bwd(axis_name, _, cotangent):
    with jax.named_scope("grad_allreduce"):
        return (jax.tree.map(lambda g: lax.pmean(g, axis_name), cotangent),)


_mean_cotangent.defvjp(lambda axis_name, tree: (tree, None), _mean_cotangent_bwd)


def reduce_in_backward(params, of=None):
    """For a loss to pass its parameters through, where it first uses them:
    the identity, whose cotangent is `lax.pmean`ed over the data axis in the
    place of the backward pass that produces it. Inside a scan over stacked
    parameters, call it in the body on the iteration's slice and give the
    stacked tree as `of`: each layer's gradient is then reduced in the
    iteration of the backward scan that produces it.

    With no axis declared (any caller but `make_train_step` under plain
    S-SGD over more than one member) it returns `params` itself and nothing
    is traced."""
    sync = _grad_sync.get()
    if sync is None:
        return params
    whole = jax.tree.leaves(params if of is None else of)
    if of is not None:
        for part, leaf in zip(jax.tree.leaves(params), whole, strict=True):
            if part.shape != leaf.shape[1:]:
                raise ValueError(
                    f"reduce_in_backward: {part.shape} is no slice along the "
                    f"first axis of {leaf.shape}")
    sync.reduced.extend(whole)
    return _mean_cotangent(sync.axis_name, params)
