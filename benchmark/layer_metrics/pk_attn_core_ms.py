"""Kernels: own time a step of the device ops under the scope `attn_full` in
the Granite 4.0-H cell, the softmax core of its one attention layer on packed
rows (32 query heads on 8 key/value heads of 64, the scores times 1/64, causal
and within a document over 8,192 positions, no position signal): the flash
forward kernel, the two backward kernels, the row sums between them, the
documents' numbers laid out for them and the layout copies at their doors.
Device trace over the step program's scope table, milliseconds."""

from benchmark.families import granite_hybrid


def read(record, trace):
    return granite_hybrid.core_ms(record, trace, granite_hybrid.ATTENTION)
