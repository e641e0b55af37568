"""Multi-slice data parallelism: ICI psum within each slice, host-plane
allreduce across slices — one jitted step per world.

Each kfrun worker owns one jax world (one TPU slice / ICI domain); the
cross-slice gradient average rides the DCN host plane from INSIDE the
compiled step (parity: the reference's hierarchical NCCL+CPU allreduce,
gpu/collective.cpp:108-162). Run it:

  kfrun -np 2 -devices-per-host 4 python3 examples/multislice_train.py

Each worker trains on the chips the launcher gave it. Without chips,
`--devices 4` makes every worker a 4-device virtual CPU world, so the
dp-within x dp-across composition runs anywhere:

  kfrun -np 2 python3 examples/multislice_train.py --devices 4
"""

import argparse


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--devices", type=int, default=0,
                   help="virtual CPU devices per worker (0 = real backend)")
    args = p.parse_args()

    import jax

    if args.devices:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.devices)

    import jax.numpy as jnp
    import numpy as np
    import optax

    from kungfu_tpu import api
    from kungfu_tpu.models.mlp import init_mlp, mlp_loss
    from kungfu_tpu.ops.hierarchical import make_hier_train_step
    from kungfu_tpu.parallel import make_mesh

    rank, size = api.current_rank(), api.cluster_size()
    mesh = make_mesh()  # all this world's devices on "dp"
    ndev = mesh.devices.size

    params = init_mlp(jax.random.PRNGKey(42))  # same seed in every world
    opt = optax.sgd(0.1)
    step = make_hier_train_step(mlp_loss, opt, mesh)
    opt_state = opt.init(params)

    # each world takes a disjoint shard of the global batch
    per_world = 64 * ndev
    key = jax.random.PRNGKey(1000 + rank)
    for i in range(args.steps):
        key, k1, k2 = jax.random.split(key, 3)
        x = jax.random.normal(k1, (per_world, 784))
        y = jax.random.randint(k2, (per_world,), 0, 10)
        params, opt_state, loss = step(params, opt_state, (x, y))
        if rank == 0:
            print(f"step {i}: loss {float(loss):.4f} "
                  f"({size} worlds x {ndev} devices)", flush=True)

    # worlds must agree bitwise: the cross-slice sync keeps them lockstep.
    # MIN and MAX allreduce both equal to the local value is an exact
    # cross-world equality check (a summed allclose could hide drift)
    from kungfu_tpu.base.ops import ReduceOp

    flat = np.concatenate([np.ravel(l) for l in jax.tree.leaves(
        jax.device_get(params))])
    lo = api.all_reduce_array(flat, ReduceOp.MIN, name="check-min")
    hi = api.all_reduce_array(flat, ReduceOp.MAX, name="check-max")
    assert np.array_equal(lo, flat) and np.array_equal(hi, flat), "worlds diverged"
    print(f"rank {rank}: worlds in sync after {args.steps} steps", flush=True)


if __name__ == "__main__":
    main()
