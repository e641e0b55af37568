"""Model: own time a step of the device ops under the scope `moe_experts`
(the three grouped matmuls of the chosen experts and the silu gate between
them), forward and backward; the kernels that XLA makes of `lax.ragged_dot`
carry no scope and are claimed by their name
(`families.olmoe.EXPERT_KERNELS`). Device trace over the step program's
scope table, milliseconds."""

from benchmark.families import olmoe


def read(record, trace):
    return olmoe.scope_own_ms(record, trace, {"moe_experts"}, olmoe.EXPERT_KERNELS)
