"""Model: own time a step of the device ops of the forward pass: those
under `jvp(..)` or a scope of the model and under neither `transpose(..)`
nor the optimizer's scopes (`trace_reduce.phase_of`). Device trace over the
step program's scope table, milliseconds."""

from benchmark.trace_reduce import scope_ms


def read(record, trace):
    return scope_ms(record, trace, lambda phase, names: phase == "forward")
