"""Model: own time a step of the device ops under the scopes
`moe_early_router` and `moe_plan` of the SmallThinker cell (four expert layers
routed from the layer's own input, ahead of the mixer): the router's float32
product at the highest precision, its softmax and top-6 of 64 (made again in
the backward pass of a layer that is run again, with the gates' derivative in
the layer's input), and the stable sort of the 98,304 token-choices a layer
with their count an expert (once a step: the order is kept under the name
`moe_plan`). Device trace over the step program's scope table, milliseconds."""

from benchmark.families import smallthinker


def read(record, trace):
    return smallthinker.early_router_ms(record, trace)
