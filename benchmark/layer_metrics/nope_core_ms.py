"""Kernels: own time a step of the device ops under the scope `attn_full` in
the Nemotron-3-Nano cell, the softmax core of its one attention layer (32
query heads on 2 key/value heads of 128, causal over 8,192 positions, no
position signal): the flash forward kernel, the two backward kernels, the row
sums between them and the layout copies at their doors. Device trace over the
step program's scope table, milliseconds."""

from benchmark.families import nemotron_h


def read(record, trace):
    return nemotron_h.core_ms(record, trace, nemotron_h.ATTENTION)
