"""State broadcast at (re)initialization.

Capability parity: srcs/python/kungfu/tensorflow/initializer/__init__.py —
broadcast_variables makes every worker start from rank-0's weights (also
used after elastic resizes to bring joiners in sync).

TPU-native mapping:
- Within one mesh (single controller), replication via `jax.device_put` IS
  the broadcast — there is exactly one logical value.
- Across processes (multi-host pod, or workers rejoining after an elastic
  resize), each process's values can diverge; `broadcast_variables` forces
  rank 0's values everywhere, mirroring BroadcastGlobalVariablesOp, with
  one program over one chip of every process: each process's own copy of a
  leaf is, where it lies, its shard of an array of all processes' copies,
  the program keeps the source's (who that is is an array of flags, data
  and not program text, so every rank runs the same text) and sums over
  the processes, an XLA all-reduce. What comes out is on every process's
  chip, and with a `mesh` it is assembled there into the replicated arrays
  the caller trains on. No leaf passes through the host, and nothing is
  `jax.device_put` from the host onto a sharding that spans processes: JAX
  0.9.0 answers that with `multihost_utils.assert_equal`, an all-gather of
  every process's copy and a comparison on the host, a leaf at a time
  (PERF.md, PR 51).

What a caller may rely on (PR 51):
- with a `mesh`, every leaf is a committed `jax.Array` with
  `NamedSharding(mesh, P())`, as `jax.device_put` gives in one process;
  with `mesh=None` in a world of several processes, host (numpy) values;
- the spans `broadcast.one_to_all` (`leaves`, `bytes`, and `host_bytes`:
  the bytes of the leaves that were not device arrays and had to be put on
  the chip first), which ends when rank 0's values are on this process's
  chip and so holds the wait for the last worker, and
  `broadcast.replicate`, the local copies that make them the mesh's arrays.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kungfu_tpu.telemetry import tracing


def _source_rows(is_source, rows, shapes):
    """The source's copy of every leaf, `rows` being each leaf's copies of
    all processes one after the other along its first axis: a select, not
    a product (the other copies may hold anything, NaN included), then the
    sum over the processes in the leaf's own dtype."""
    def one(x, shape):
        x = x.reshape(is_source.shape + shape)
        keep = is_source.reshape(is_source.shape + (1,) * len(shape))
        return jnp.sum(jnp.where(keep, x, jnp.zeros((), x.dtype)),
                       axis=0, dtype=x.dtype)

    return [one(x, shape) for x, shape in zip(rows, shapes)]


def _stack(leaves, is_source: bool):
    """`(program, arguments, host_bytes)`: the one program of every rank
    and this process's part of its arguments; run it as
    `program(*arguments)`. It runs on the first device of every process. A
    leaf that is not on this process's one is put there, from the host
    where it is no device array, and is then this process's shard of the
    global array as it stands: no copy and no program of its own (a 0-d
    device leaf but for a reshape)."""
    firsts = [jax.local_devices(process_index=p)[0]
              for p in range(jax.process_count())]
    here = firsts[jax.process_index()]
    world = Mesh(np.array(firsts), ("processes",))
    by_process = NamedSharding(world, P("processes"))

    def shard(x):
        x = x if isinstance(x, jax.Array) else np.asarray(x)
        return jax.device_put(x if x.ndim else x.reshape(1), here)

    def stacked(x):
        return jax.make_array_from_single_device_arrays(
            (len(firsts) * x.shape[0],) + x.shape[1:], by_process, [x])

    shards = [shard(l) for l in leaves]
    host_bytes = sum(s.nbytes for l, s in zip(leaves, shards)
                     if not isinstance(l, jax.Array))
    program = jax.jit(_source_rows, static_argnums=2,
                      out_shardings=NamedSharding(world, P()))
    return (program, (stacked(shard([is_source])), [stacked(s) for s in shards],
                      tuple(np.shape(l) for l in leaves)), host_bytes)


def _is_source() -> bool:
    """The source is the worker whose kfrun rank is 0. `jax.process_index()`
    is not the rank: on a TPU host libtpu numbers the processes by where
    their chips sit (chip runs, PR 21: ranks 0..3 got process indices
    1, 3, 2, 0 on one machine and 0, 2, 3, 1 on the next), so JAX's
    default source would be whichever worker holds that chip — after a
    resize, possibly a joiner."""
    from kungfu_tpu.peer import get_default_peer

    peer = get_default_peer()
    # a JAX world kfrun did not form has no ranks: JAX's default then
    if peer.size == jax.process_count():
        return peer.rank == 0
    return jax.process_index() == 0


def broadcast_variables(tree, mesh: Mesh = None):
    """Force every process to rank 0's values, replicated on `mesh`.

    Single-process: pure replication (no communication). Several: one
    collective program whose result stays on the chip (the module's
    docstring has what a caller may rely on).
    """
    if jax.process_count() == 1:
        if mesh is not None:
            # set-up code: the span waits for the copies, so that it holds them
            with tracing.span("broadcast.replicate"):
                tree = jax.block_until_ready(
                    jax.device_put(tree, NamedSharding(mesh, P())))
        return tree
    leaves, treedef = jax.tree.flatten(tree)
    with tracing.span("broadcast.one_to_all", leaves=len(leaves),
                      bytes=sum(getattr(l, "nbytes", 0) for l in leaves)) as s:
        program, arguments, host_bytes = _stack(leaves, _is_source())
        s.args["host_bytes"] = host_bytes
        # the span ends when the values are on this process's chip
        mine = [x.addressable_data(0)
                for x in jax.block_until_ready(program(*arguments))]
    if mesh is None:
        return jax.tree.unflatten(treedef, jax.device_get(mine))
    with tracing.span("broadcast.replicate"):
        replicated = NamedSharding(mesh, P())
        # chip to chip, and nothing where a device already holds the values
        copies = [jax.device_put(mine, d) for d in mesh.local_devices]
        placed = jax.block_until_ready([
            jax.make_array_from_single_device_arrays(x.shape, replicated, bufs)
            for x, *bufs in zip(mine, *copies)])
    return jax.tree.unflatten(treedef, placed)
