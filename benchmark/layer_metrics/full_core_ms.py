"""Kernels: own time a step of the device ops under the scope `attn_full`,
the attention cores of the full-attention layers (two in the Laguna cell, 48
query heads on 8 key/value heads, causal over all 8,192 positions): the
flash forward kernel, the two backward kernels and the row sums between
them. Device trace over the step program's scope table, milliseconds."""

from benchmark.families import laguna


def read(record, trace):
    return laguna.core_ms(record, trace, "full")
