"""Model: own time a step of the device ops under the scope `head_loss`
(the last norm, the head's matmul and the cross-entropy), forward and
backward. Device trace over the step program's scope table, milliseconds."""

from benchmark.trace_reduce import scope_ms


def read(record, trace):
    return scope_ms(record, trace, lambda phase, names: "head_loss" in names)
