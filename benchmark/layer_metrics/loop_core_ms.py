"""Kernels: own time a step of the device ops under the scope `attn_core` in
the Ouro cell, the softmax cores of its 32 layer applications (four loop
steps over eight layers; 16 heads of 128, causal over 4,096 positions): the
flash forward kernel, the two backward kernels, the row sums between them and
the layout copies at their doors. Device trace over the step program's scope
table, milliseconds."""

from benchmark.families import ouro


def read(record, trace):
    return ouro.core_ms(record, trace)
