"""Train step: own time a step of the device ops under the scopes
`optimizer`, `optimizer_update` or `grad_allreduce`, the all-reduces
themselves left to `allreduce_ms`. Device trace over the step program's
scope table, milliseconds. Not for a program whose update XLA fuses into
the model's own fusions (ResNet's momentum SGD, PERF.md section 5)."""

from benchmark.trace_reduce import scope_ms


def read(record, trace):
    return scope_ms(record, trace, lambda phase, names: phase == "optimizer")
