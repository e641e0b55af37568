"""Model: own time a step of the device ops under the scope `moe` of the
SmallThinker cell (four expert layers: the routing from the layer's input
ahead of the mixer, `moe_early_router` and `moe_plan`; the norm before the
experts; dispatch, the 16 held relu-gated experts of 64 over the share's one
chunk of 98,304 rows, combine), forward and backward; the grouped-matmul
kernels that XLA makes of `lax.ragged_dot` carry no scope and are claimed by
their name (`families.olmoe.EXPERT_KERNELS`). Device trace over the step
program's scope table, milliseconds."""

from benchmark.families import smallthinker


def read(record, trace):
    return smallthinker.moe_ms(record, trace)
