"""The device plane's operations, a module each: import the one you use
(`from kungfu_tpu.ops import moe`). The package itself brings in the
collectives alone, which import nothing beyond JAX, so that a model that
reduces in its backward pass (`collective.reduce_in_backward`) pulls in no
kernel and no Pallas with them (`tests/test_rope.py` holds the line)."""

from kungfu_tpu.ops.collective import (
    all_gather,
    all_reduce,
    broadcast,
    defuse,
    fuse,
    group_all_reduce,
    subset_all_reduce,
)

__all__ = [
    "all_gather",
    "all_reduce",
    "broadcast",
    "defuse",
    "fuse",
    "group_all_reduce",
    "subset_all_reduce",
]
