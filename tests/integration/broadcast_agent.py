"""`broadcast_variables` between processes that disagree: a kfrun worker
joins the one JAX world (CPU backend), holds values of its own rank in
every leaf (a NaN in the float ones of every rank but 0), broadcasts them
once as numpy and once as device arrays with JAX's own host-side helpers
patched to raise, and prints what came back as one JSON line.
"""

import hashlib
import json
import sys

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import multihost_utils  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from kungfu_tpu import api, initializer  # noqa: E402
from kungfu_tpu.parallel import initialize_device_plane, make_mesh  # noqa: E402
from kungfu_tpu.telemetry import tracing  # noqa: E402

TAG = "BROADCAST_AGENT "
HELPERS = ("broadcast_one_to_all", "process_allgather", "assert_equal")


def values(rank: int) -> dict:
    """What rank `rank` holds before the broadcast, as numpy."""
    rng = np.random.RandomState(1234 + rank)
    f32 = rng.standard_normal((5, 3)).astype(np.float32)
    bf16 = rng.standard_normal((7,)).astype(jnp.bfloat16)
    scalar = np.float32(rng.standard_normal())
    if rank:
        f32[1, 2] = bf16[3] = scalar = np.nan
    return {"float32": f32, "bfloat16": bf16,
            "int32": rng.randint(-2**31, 2**31 - 1, (4, 2)).astype(np.int32),
            "bool": rng.rand(9) < 0.5,
            "scalar": np.asarray(scalar, np.float32)}


def bits(x) -> str:
    return np.asarray(x).tobytes().hex()


def main() -> int:
    rank = api.current_rank()
    initialize_device_plane()
    mesh = make_mesh({"dp": jax.device_count()})
    called = []
    for name in HELPERS:
        def refuse(*a, _name=name, **k):
            called.append(_name)
            raise AssertionError(f"multihost_utils.{_name} was called")
        setattr(multihost_utils, name, refuse)

    mine = values(rank)
    report = {"rank": rank, "local_devices": jax.local_device_count(),
              "want": {k: bits(v) for k, v in values(0).items()},
              "nbytes": {k: v.nbytes for k, v in mine.items()}, "kinds": {}}
    for kind, tree in (("numpy", mine), ("device", jax.tree.map(jnp.asarray, mine))):
        tracing.clear()
        placed = initializer.broadcast_variables(tree, mesh)
        report["kinds"][kind] = {
            "spans": {e.name: e.args for e in tracing.full_events("broadcast.")},
            "leaves": {k: {
                "bits": bits(x), "dtype": str(x.dtype), "shape": list(x.shape),
                "array": isinstance(x, jax.Array),
                "sharding": x.sharding == NamedSharding(mesh, P()),
                "replicated": x.is_fully_replicated, "committed": x.committed,
                "devices": sorted(d.id for d in x.sharding.device_set),
                "shards": [bits(s.data) for s in x.addressable_shards],
            } for k, x in placed.items()}}

    # the source is data: every rank lowers the same text
    leaves = jax.tree.leaves(mine)
    program, arguments, _ = initializer._stack(leaves, initializer._is_source())
    report["program"] = hashlib.sha256(
        program.lower(*arguments).as_text().encode()).hexdigest()

    n = initializer.broadcast_variables({"n": np.asarray(rank + 7, np.int32)})["n"]
    report["no_mesh"] = {"int": int(n), "asarray": np.asarray(n).tolist(),
                         "dtype": str(np.asarray(n).dtype)}
    report["called"] = called
    print(TAG + json.dumps(report), flush=True)
    api.run_barrier()
    return 0


if __name__ == "__main__":
    sys.exit(main())
