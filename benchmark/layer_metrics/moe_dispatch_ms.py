"""Model: own time a step of the device ops under the scopes `moe_router`,
`moe_dispatch` and `moe_combine`: the router's matmul, softmax and top-k,
the sort of the token-choices by expert, the gathers and the weighted sum
that puts the results back; memory and latency where the experts are
compute. Forward and backward. Device trace over the step program's scope
table, milliseconds."""

from benchmark.families import olmoe

SCOPES = {"moe_router", "moe_dispatch", "moe_combine"}


def read(record, trace):
    return olmoe.scope_own_ms(record, trace, SCOPES)
