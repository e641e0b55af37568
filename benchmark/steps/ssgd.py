"""S-SGD: every chip takes the gradients of its rows of the batch,
`synchronous_sgd`'s traced `pmean` over the data axis averages them (XLA's
all-reduce), and every chip applies the same update of the traffic file's
base optimizer. One optimizer step per dispatch, state donated.

A family whose loss is `loss_fn(cfg)(params, batch) -> loss` goes through the
public factory, `parallel.make_train_step`. One whose loss carries auxiliary
state (batch-norm statistics) has no place there (PERF.md, Open questions)
and gives the per-chip body itself, `local_step(cfg, optimizer, axis)`; it is
put over the mesh here.
"""

from benchmark import manifest

BATCH_AXIS = "dp"


def build(family, config: dict, traffic: dict, mesh):
    """-> (step, init_opt_state): `step(state, opt_state, batch) -> (state,
    opt_state, loss)`, jitted; `init_opt_state(state)`, not yet placed."""
    import jax
    from jax.sharding import PartitionSpec as P

    from kungfu_tpu.optimizers import synchronous_sgd
    from kungfu_tpu.parallel import make_train_step

    spec = traffic["optimizer"]
    base = manifest.plugin("optimizers", spec["name"]).make(spec)
    optimizer = synchronous_sgd(base, BATCH_AXIS)
    if hasattr(family, "local_step"):
        spmd = jax.shard_map(
            family.local_step(config, optimizer, BATCH_AXIS), mesh=mesh,
            in_specs=(P(), P(), P(BATCH_AXIS)), out_specs=(P(), P(), P()),
            check_vma=False,
        )
        step = jax.jit(spmd, donate_argnums=(0, 1))
    else:
        step = make_train_step(family.loss_fn(config), optimizer, mesh)
    return step, lambda state: optimizer.init(family.trainable(state))


def place(state, opt_state, mesh):
    """The state stays as the launcher's world placed it (replicated); the
    optimizer's state is replicated like it, its step count with the rest,
    or the step compiles twice (PERF.md, PR 21 finding 3)."""
    from kungfu_tpu.parallel.dp import replicate

    return state, replicate(opt_state, mesh)
