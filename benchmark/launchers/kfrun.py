"""`kfrun`: the traffic file's `launcher_args` start the workers, each runs
the cell's child, and `initialize_device_plane()` joins them into one device
world (it also places the compile cache)."""

import sys


def argv(traffic: dict, child: list) -> list:
    """The command the parent starts (no jax here)."""
    return [sys.executable, "-m", "kungfu_tpu.runner.cli",
            *traffic["launcher_args"], "--", *child]


def join():
    """In every worker, before anything touches the JAX backend."""
    return KfrunWorld()


class KfrunWorld:
    """One worker of the tree. The rank is kfrun's (`api.current_rank()`),
    not `jax.process_index()`, which on the chip is some other worker's
    (PERF.md, PR 21 finding 4)."""

    def __init__(self):
        from kungfu_tpu import api
        from kungfu_tpu.parallel import initialize_device_plane

        self.api = api
        self.rank, self.size = api.current_rank(), api.cluster_size()
        initialize_device_plane()

    def place_state(self, state, mesh):
        from kungfu_tpu.initializer import broadcast_variables

        return broadcast_variables(state, mesh)

    def agree_steps(self, n: int) -> int:
        """Rank 0's step count: a worker that stops on its own clock hangs
        the others in the next all-reduce."""
        import numpy as np

        from kungfu_tpu.initializer import broadcast_variables

        return int(broadcast_variables({"n": np.asarray(n, np.int32)})["n"])

    def agree_digest(self, state) -> bool:
        from benchmark.harness import params_digest

        return bool(self.api.consensus(params_digest(state), "benchmark"))

    def close(self) -> None:
        self.api.run_barrier()
