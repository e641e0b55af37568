"""Reload-mode elastic agent: every resize restarts ALL workers from the
carried progress, and each incarnation bootstraps a fresh JAX device plane
spanning the new cluster.

Parity: ElasticModeReload (peer.go ChangeCluster + watcher updateFull) —
the PRIMARY elastic mode on TPU (SURVEY §7: ICI mesh shape is fixed per
slice, so membership changes get a fresh mesh via process restart).
"""

import json
import sys
import time

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from kungfu_tpu import api  # noqa: E402
from kungfu_tpu.elastic.state import ElasticState  # noqa: E402
from kungfu_tpu.parallel import initialize_device_plane, make_mesh  # noqa: E402

MAX_PROGRESS = 30
RESIZES = {10: 3, 20: 2}  # progress -> new cluster size


def device_psum_check() -> None:
    """The compiled mesh must span every process of THIS incarnation."""
    size = api.cluster_size()
    n_dev = jax.device_count()
    assert jax.process_count() == size, (jax.process_count(), size)
    mesh = make_mesh({"dp": n_dev})
    from jax import shard_map

    f = jax.jit(
        shard_map(lambda x: jax.lax.psum(x, "dp"), mesh=mesh,
                  in_specs=P("dp"), out_specs=P(), check_vma=False)
    )
    local = np.full((jax.local_device_count(),), 1.0, np.float32)
    x = jax.make_array_from_process_local_data(
        jax.sharding.NamedSharding(mesh, P("dp")), local, (n_dev,)
    )
    assert float(np.asarray(f(x))[0]) == n_dev


def main() -> int:
    initialize_device_plane()
    es = ElasticState(max_progress=MAX_PROGRESS, reload_mode=True)
    rank = api.current_rank()
    size = api.cluster_size()
    print(f"incarnation rank={rank}/{size} start_progress={es.progress}", flush=True)
    device_psum_check()

    first = True
    while not es.stopped():
        with es.scope():
            if rank == 0:
                target = RESIZES.get(es.progress)
                if target is not None and target != api.cluster_size():
                    api.propose_new_size(target)
            es.end(1)
        if first:
            # what this incarnation knows of the reload that started it,
            # and the wall time it knew it at ({} in the first incarnation)
            first = False
            print(f"resize_phases rank={rank}/{size} wall={time.time():.6f} "
                  + json.dumps(api.last_resize_phases()), flush=True)

    print(f"stopping rank={rank}/{size} wall={time.time():.6f}", flush=True)
    print(f"stopped reason={es.stop_reason} progress={es.progress}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
