"""Kernels: own time a step of the device ops under the scopes `dsa_index`
and `dsa_select` of the Keye-VL-2.0-30B-A3B cell: the lightning indexer of its
six layers (three float32 projections of the layer's normed input, a
LayerNorm, a rotation, and `ops.sparse_attention.index_scores`, 16 heads of 64
over the causal half each way) and the choice of each query's 2,048
best-scored keys (`select`: 45 counting passes over the scores, once a step:
a layer that is run again makes the scores twice and keeps the choice's bits).
What
`dsa_index_roofline_pct` divides by. Device trace over the step program's
scope table, milliseconds."""

from benchmark.families import keye_vl2


def read(record, trace):
    return keye_vl2.index_ms(record, trace)
