"""Model: own time a step of the device ops under the scope `head_loss`
(the last norm, the head's matmul and the cross-entropy), forward and
backward. Every pass under the scope is in it: two in the GLM-4.7-Flash
cell (the main head and the multi-token-prediction module's, scope `mtp` >
`head_loss`) and four in the Ouro cell (one a loop step, inside the loop's
body, each made again where the backward pass runs the step again). Device
trace over the step program's scope table, milliseconds."""

from benchmark.trace_reduce import scope_ms


def read(record, trace):
    return scope_ms(record, trace, lambda phase, names: "head_loss" in names)
