"""Model: own time a step of the device ops under the scope `moe` (the
expert layer: router, dispatch, experts, combine and the norm before them),
forward and backward; the grouped-matmul kernels that XLA makes of
`lax.ragged_dot` carry no scope and are claimed by their name
(`families.olmoe.EXPERT_KERNELS`). Device trace over the step program's
scope table, milliseconds."""

from benchmark.families import olmoe


def read(record, trace):
    return olmoe.scope_own_ms(record, trace, {"moe"}, olmoe.EXPERT_KERNELS)
