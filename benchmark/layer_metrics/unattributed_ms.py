"""Device: own time a step of the device ops that no scope of the program
claims: instructions the compiler made itself (copies, bitcasts, the scan's
own bookkeeping) and ops under plumbing alone. With `fwd_ms`, `bwd_ms`,
`optimizer_ms` and `allreduce_ms` it makes up `device_step_ms`. Device trace
over the step program's scope table, milliseconds."""

from benchmark.trace_reduce import scope_ms


def read(record, trace):
    return scope_ms(record, trace, lambda phase, names: phase == "unattributed")
