"""Model: own time a step of the device ops under the scope `ffn` in the
Granite 4.0-H cell, the gated-silu feed-forward of width 8,192 that every one
of its ten layers has behind its mixer (the norm, the gate and up projections,
silu and product, the down projection; 503 M of the 772 M parameters that
multiply a token), forward and backward. Device trace over the step program's
scope table, milliseconds."""

from benchmark.families.olmoe import scope_own_ms


def read(record, trace):
    return scope_own_ms(record, trace, {"ffn"})
