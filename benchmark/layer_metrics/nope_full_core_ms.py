"""Kernels: own time a step of the device ops under the scope `attn_full` of
the SmallThinker cell, the attention core of its one full layer (28 query
heads on 4 key/value heads of 128, causal over all 16,384 positions, no
position signal of any kind): the flash forward kernel, the two backward
kernels, the row sums between them and the layout copies at their doors.
Device trace over the step program's scope table, milliseconds."""

from benchmark.families import smallthinker


def read(record, trace):
    return smallthinker.core_ms(record, trace, "full")
