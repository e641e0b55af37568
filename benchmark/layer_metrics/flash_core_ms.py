"""Model: own time a step of the device ops under the scope `attn_core`
where the core is `ops.flash_attention`: the forward kernel, the two
backward kernels and the row sums between them. Device trace over the step
program's scope table, milliseconds."""

from benchmark.families import olmoe


def read(record, trace):
    return olmoe.scope_own_ms(record, trace, {"attn_core"})
