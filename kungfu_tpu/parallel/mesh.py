"""Device mesh session: the TPU-native Session epoch.

Capability parity: srcs/go/kungfu/session/session.go — an immutable
peer-list epoch exposing rank/size/local metadata, barrier, and collectives.
On TPU the "peer list" is a `jax.sharding.Mesh` over the slice's chips: the
membership of a compiled program is fixed at compile time exactly like a
Session is fixed per cluster version. An elastic resize creates a NEW
DeviceSession over a new mesh (and retriggers compilation), mirroring
`Peer.updateTo` building a new Session per cluster version.

Rank vocabulary (multi-host TPU pod):
- process == host (jax.process_index) — the unit the control plane manages;
- device == chip — the unit the data plane (ICI collectives) runs over.
The reference's rank/local-rank/host-count map to device index / index on
host / process count.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(shape: Optional[Dict[str, int]] = None, *, devices=None) -> Mesh:
    """Build a Mesh. shape maps axis name -> size; one size may be -1
    (inferred). Default: all devices on a single 'dp' axis.

    Axis order convention follows the scaling-book recipe: put the
    most-communication-hungry axis last ('tp' innermost over ICI
    neighbours), 'dp' outermost (crosses DCN on multi-slice).
    """
    devices = np.asarray(devices if devices is not None else jax.devices())
    n = devices.size
    if shape is None:
        shape = {"dp": n}
    names = tuple(shape)
    sizes = list(shape.values())
    if sizes.count(-1) > 1:
        raise ValueError("at most one axis size may be -1")
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        if n % known:
            raise ValueError(f"cannot infer axis: {n} devices over {shape}")
        sizes[sizes.index(-1)] = n // known
    total = int(np.prod(sizes))
    if total != n:
        raise ValueError(f"mesh {dict(zip(names, sizes))} needs {total} devices, have {n}")
    return Mesh(devices.reshape(sizes), names)


class DeviceSession:
    """An immutable epoch over a device mesh, with KungFu-parity metadata
    and host-callable collectives."""

    def __init__(self, mesh: Optional[Mesh] = None, version: int = 0):
        self.mesh = mesh if mesh is not None else make_mesh()
        self.version = version

    # -- metadata (parity: session.go Rank/Size/LocalRank/LocalSize/HostCount)
    @property
    def size(self) -> int:
        return self.mesh.devices.size

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return tuple(self.mesh.axis_names)

    @property
    def rank(self) -> int:
        return jax.process_index()

    @property
    def host_count(self) -> int:
        return jax.process_count()

    @property
    def local_size(self) -> int:
        return jax.local_device_count()

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    @property
    def replicated(self) -> NamedSharding:
        return self.sharding()

    # -- collectives -------------------------------------------------------
    def spmd(self, fn, in_specs, out_specs, check_vma: bool = False):
        """shard_map+jit over this mesh (one compiled SPMD program)."""
        return jax.jit(
            jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=check_vma)
        )

    @functools.cached_property
    def _barrier_fn(self):
        axes = self.axis_names

        def fence(x):
            for a in axes:
                x = jax.lax.psum(x, a)
            return x

        return self.spmd(fence, in_specs=P(), out_specs=P())

    def barrier(self) -> None:
        """Device-fence barrier: a tiny AllReduce over every mesh axis,
        blocked on. Parity: Session.Barrier (session.go:98-113). In
        multi-process mode this also synchronizes processes (all hosts must
        dispatch the same program)."""
        self._barrier_fn(jnp.zeros((), jnp.int32)).block_until_ready()

    def all_reduce(self, tree, axis_name: Optional[str] = None):
        """AllReduce device-sharded data: each leaf's leading axis is sharded
        over `axis_name` (default: first mesh axis); returns the reduction
        over shards, replicated."""
        from kungfu_tpu.ops.collective import group_all_reduce

        axis = axis_name or self.axis_names[0]
        fn = self.spmd(
            lambda t: group_all_reduce(t, axis),
            in_specs=P(axis),
            out_specs=P(),
        )
        return fn(tree)

    def describe(self) -> str:
        shape = dict(zip(self.axis_names, self.mesh.devices.shape))
        return (
            f"DeviceSession(v{self.version}, {self.size} devices, mesh={shape}, "
            f"process {self.rank}/{self.host_count})"
        )
