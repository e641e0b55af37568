"""Model: own time a step of the device ops under the scope `segments`, what
numbers each position's document from the ids of a packed row
(`kungfu_tpu/models/transformer._segments`: a comparison with the
end-of-document id, a shift and a running sum over 8,192 positions), once a
step and handed to every mixer. 0.0 where the compiler folds it into its
readers. Device trace over the step program's scope table, milliseconds."""

from benchmark.families.olmoe import scope_own_ms


def read(record, trace):
    return scope_own_ms(record, trace, {"segments"})
