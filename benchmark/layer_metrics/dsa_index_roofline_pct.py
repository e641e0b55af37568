"""Kernels: the indexer's share of its roofline. The least time the chip
could take for the scores, the larger of their products' operations over the
bf16 peak (`families.keye_vl2.index_flops_per_sample`: qI . kI forward and
the two products of its gradient, 16 heads of 64 over the 33,558,528 causal
pairs a layer, 0.21 TFLOP and 1.05 ms) and the bytes of I and its cotangent
written and read once each over the memory peak (`index_bytes_per_sample`,
0.54 GB and 0.66 ms), over the time under `dsa_index` and `dsa_select`. The
products are float32 at the highest precision, six passes of the MXU, the
scores are made twice a step and the choice reads them 45 times: each shows
as a lower share (2.1 on the chip, PR 61). Device trace, %."""

from benchmark.families import keye_vl2


def read(record, trace):
    return keye_vl2.index_roofline_pct(record, trace)
