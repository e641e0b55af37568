"""Model: own time a step of the device ops under the scope `moe` where
the expert layer holds a share of the experts its router sees (four layers
in the Laguna cell: 8 of 256 held, 10 a token, a shared expert): norm,
router, dispatch, the held experts, the shared expert, combine, forward and
backward; the grouped-matmul kernels that XLA makes of `lax.ragged_dot`
carry no scope and are claimed by their name
(`families.olmoe.EXPERT_KERNELS`). Device trace over the step program's
scope table, milliseconds."""

from benchmark.families import laguna


def read(record, trace):
    return laguna.scope_own_ms(record, trace, {"moe"}, laguna.EXPERT_KERNELS)
