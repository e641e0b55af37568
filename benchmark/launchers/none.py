"""No launcher: the cell's child is the one process, and its chips are the
world."""


def argv(traffic: dict, child: list) -> list:
    """The command the parent starts (no jax here)."""
    return child


def join():
    """In the child, before anything touches the JAX backend."""
    from kungfu_tpu.parallel.chip import enable_compile_cache

    enable_compile_cache()
    return OneProcess()


class OneProcess:
    rank = 0
    size = 1

    def place_state(self, state, mesh):
        from kungfu_tpu.parallel.dp import replicate

        return replicate(state, mesh)

    def agree_steps(self, n: int) -> int:
        return n

    def agree_digest(self, state) -> bool:
        return True

    def close(self) -> None:
        pass
