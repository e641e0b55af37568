"""Model: own time a step of the device ops under the scopes `moe_experts`
(the three grouped matmuls of the held experts and the silu gate between
them) and `moe_shared` (the shared expert every token takes), forward and
backward; the kernels that XLA makes of `lax.ragged_dot` carry no scope and
are claimed by their name (`families.olmoe.EXPERT_KERNELS`). Device trace
over the step program's scope table, milliseconds."""

from benchmark.families import laguna


def read(record, trace):
    return laguna.scope_own_ms(record, trace, {"moe_experts", "moe_shared"},
                               laguna.EXPERT_KERNELS)
