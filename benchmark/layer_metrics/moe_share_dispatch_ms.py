"""Model: own time a step of the device ops under the scopes `moe_router`,
`moe_dispatch` and `moe_combine` of a share's expert layer: the router's
matmul over all 256 experts, softmax and top-10, the sort of the 81,920
token-choices that puts those for the held experts first, the gather into
the worst-case row buffer (T x 8 rows) and the gather and weighted sum that
put the results back; memory and latency where the experts are compute.
Forward and backward. Device trace over the step program's scope table,
milliseconds."""

from benchmark.families import laguna

SCOPES = {"moe_router", "moe_dispatch", "moe_combine"}


def read(record, trace):
    return laguna.scope_own_ms(record, trace, SCOPES)
