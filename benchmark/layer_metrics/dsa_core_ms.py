"""Kernels: own time a step of the device ops under the scope `attn_sparse` of
the Keye-VL-2.0-30B-A3B cell: the softmax core over the keys each query
chose, of its six layers (`ops.sparse_attention`: the forward kernel, which a
layer that is run again does not run twice since it keeps the output and the
rows' log-sum-exp, the dQ and the dK/dV kernel, the row sums between them and
the layout copies at their doors), 32 query heads on 4 key/value heads of 128
at 8,192 positions, every live block of the causal half under a byte mask.
What `dsa_core_roofline_pct` divides by. Device trace over the step program's
scope table, milliseconds."""

from benchmark.families import keye_vl2


def read(record, trace):
    return keye_vl2.core_ms(record, trace)
