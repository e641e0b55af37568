"""State broadcast at (re)initialization.

Capability parity: srcs/python/kungfu/tensorflow/initializer/__init__.py —
broadcast_variables makes every worker start from rank-0's weights (also
used after elastic resizes to bring joiners in sync).

TPU-native mapping:
- Within one mesh (single controller), replication via `jax.device_put` IS
  the broadcast — there is exactly one logical value.
- Across processes (multi-host pod, or workers rejoining after an elastic
  resize), host-level values can diverge; `broadcast_variables` forces
  rank 0's values everywhere (XLA AllReduce under the hood via
  multihost_utils), mirroring BroadcastGlobalVariablesOp.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kungfu_tpu.telemetry import tracing


def broadcast_variables(tree, mesh: Mesh = None):
    """Force every process to rank 0's values, then replicate on-mesh.

    Single-process: pure replication (no communication).

    The source is the worker whose kfrun rank is 0. `jax.process_index()`
    is not the rank: on a TPU host libtpu numbers the processes by where
    their chips sit (chip runs, PR 21: ranks 0..3 got process indices
    1, 3, 2, 0 on one machine and 0, 2, 3, 1 on the next), so JAX's
    default source would be whichever worker holds that chip — after a
    resize, possibly a joiner.
    """
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        from kungfu_tpu.peer import get_default_peer

        peer = get_default_peer()
        # a JAX world kfrun did not form has no ranks: JAX's default then
        is_source = peer.rank == 0 if peer.size == jax.process_count() else None
        leaves = jax.tree.leaves(tree)
        # returns host arrays, so the span ends when the values are here
        with tracing.span("broadcast.one_to_all", leaves=len(leaves),
                          bytes=sum(getattr(l, "nbytes", 0) for l in leaves)):
            tree = multihost_utils.broadcast_one_to_all(tree, is_source=is_source)
    if mesh is not None:
        # set-up code: the span waits for the copies, so that it holds them
        with tracing.span("broadcast.replicate"):
            tree = jax.block_until_ready(
                jax.device_put(tree, NamedSharding(mesh, P())))
    return tree
