"""Device-plane agent: kfrun workers bootstrap ONE JAX world (CPU backend)
and run a real cross-process SynchronousSGD train step.

Parity goal (VERDICT r1 #1): the control plane stands up a cross-host mesh
— the analog of NCCL-unique-id bootstrap over the CPU collective
(srcs/cpp/src/nccl/gpu_collective.cpp:190-243).
"""

import sys

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import optax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from kungfu_tpu import api  # noqa: E402
from kungfu_tpu.initializer import broadcast_variables  # noqa: E402
from kungfu_tpu.optimizers import synchronous_sgd  # noqa: E402
from kungfu_tpu.parallel import (  # noqa: E402
    initialize_device_plane,
    make_mesh,
    make_train_step,
)


def main() -> int:
    # host plane first (peer starts on import of api call), then device plane
    rank = api.current_rank()
    size = api.cluster_size()
    initialize_device_plane()

    assert jax.process_count() == size, (jax.process_count(), size)
    n_dev = jax.device_count()
    assert n_dev >= size, (n_dev, size)

    mesh = make_mesh({"dp": n_dev})

    # cross-process psum sanity: every device contributes its global index+1
    from jax import shard_map

    f = jax.jit(
        shard_map(
            lambda x: jax.lax.psum(x, "dp"),
            mesh=mesh, in_specs=P("dp"), out_specs=P(), check_vma=False,
        )
    )
    local = np.full(
        (jax.local_device_count(),), 1.0 + jax.process_index(), np.float32
    )
    x = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp")), local, (n_dev,)
    )
    got = float(np.asarray(f(x))[0])
    # every process contributes (1+proc_idx) per local device
    per_proc = n_dev // size
    want = per_proc * sum(1.0 + p for p in range(size))
    assert got == want, f"cross-process psum: {got} != {want}"

    # one SynchronousSGD step over the mesh: grads must be averaged across
    # processes, params must stay bit-identical on every process
    def loss_fn(params, batch):
        xb, yb = batch
        pred = xb @ params["w"]
        return ((pred - yb) ** 2).mean()

    params = {"w": np.ones((4, 2), np.float32) * (rank + 1)}
    params = broadcast_variables(params, mesh)  # rank-0's weights everywhere
    opt = synchronous_sgd(optax.sgd(0.1), axis_name="dp")
    opt_state = jax.jit(opt.init)(params)

    step = make_train_step(loss_fn, opt, mesh)
    rng = np.random.RandomState(rank)
    local_bs = 8
    xb = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp")),
        rng.randn(local_bs * jax.local_device_count(), 4).astype(np.float32),
        (local_bs * n_dev, 4),
    )
    yb = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp")),
        rng.randn(local_bs * jax.local_device_count(), 2).astype(np.float32),
        (local_bs * n_dev, 2),
    )
    params, opt_state, loss = step(params, opt_state, (xb, yb))
    loss = float(np.asarray(loss))

    # all processes must hold identical params (consensus over host plane)
    digest = np.asarray(params["w"]).tobytes()
    assert api.consensus(digest, "post-step-params"), "params diverged"

    api.run_barrier()
    print(f"OK device-plane rank={rank}/{size} devices={n_dev} loss={loss:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
