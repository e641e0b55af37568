"""Model: own time a step of the device ops under the scope `attn_core`
(scores, mask, softmax and the product with v), forward and backward.
Device trace over the step program's scope table, milliseconds."""

from benchmark.trace_reduce import scope_ms


def read(record, trace):
    return scope_ms(record, trace, lambda phase, names: "attn_core" in names)
