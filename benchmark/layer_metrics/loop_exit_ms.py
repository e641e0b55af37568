"""Model: own time a step of the device ops under the scope `exit_gate` in
the Ouro cell: the gate's float32 product on three loop steps' normed states,
the exit distribution, the expected loss over the four head passes' rows and
the entropy, forward and backward. Device trace over the step program's scope
table, milliseconds."""

from benchmark.families import ouro


def read(record, trace):
    return ouro.scope_ms(record, trace, lambda names: "exit_gate" in names)
