"""Kernels: the sparse core's share of its roofline. The least time the chip
could take for it, the larger of the chosen pairs' operations over the bf16
peak (`families.keye_vl2.core_flops_per_sample`: 12 operations a chosen pair,
head and feature over 14,681,088 pairs a layer, 0.72 TFLOP and 3.7 ms) and the
bytes it must move over the memory peak (`core_bytes_per_sample`: q, k, v, o
and their cotangents and the choice, a byte a causal pair, three times; 0.55
GB and 0.7 ms), over the time under `attn_sparse`. The kernels visit every
live block of the causal half, 2.3 times the chosen pairs: visited pairs that
are not chosen show as a lower share. Device trace, %."""

from benchmark.families import keye_vl2


def read(record, trace):
    return keye_vl2.core_roofline_pct(record, trace)
