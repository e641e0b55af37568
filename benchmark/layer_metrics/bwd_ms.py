"""Model: own time a step of the device ops of the backward pass: those
under a `transpose(..)` and outside the optimizer's scopes
(`trace_reduce.phase_of`); what the backward pass recomputes of the forward
is here. Device trace over the step program's scope table, milliseconds."""

from benchmark.trace_reduce import scope_ms


def read(record, trace):
    return scope_ms(record, trace, lambda phase, names: phase == "backward")
