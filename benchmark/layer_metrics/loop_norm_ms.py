"""Model: own time a step of the device ops under the scopes `post_norm` (the
second norm of a branch, behind attention and behind the feed-forward, 64 a
forward pass) and `loop_norm` (the model's final norm at the end of each of
the four loop steps) in the Ouro cell: what the looped model's norms cost
beside a plain block's two a layer. Forward, run again and backward. Device
trace over the step program's scope table, milliseconds."""

from benchmark.families import ouro


def read(record, trace):
    return ouro.scope_ms(record, trace,
                         lambda names: bool({"post_norm", "loop_norm"} & names))
