"""Kernels: the window cores' share of their roofline in the SmallThinker
cell. The least time the chip could take for the three band cores, the larger
of the operations the band requires over the bf16 peak
(`families.smallthinker.core_flops_per_sample`: forward 2 products, backward
4, over 16,384 x 4,096 - 4,096^2 / 2 = 58.7e6 pairs a head, 28 heads of 128:
2.525 TFLOP a layer and sequence, 12.8 ms; recomputation not counted) and the
bytes they must move over the memory peak (`core_bytes_per_sample`: 0.805 GB,
1.0 ms), over `swa_core_ms`. Device trace, %."""

from benchmark.families import smallthinker


def read(record, trace):
    return smallthinker.core_roofline_pct(record, trace, "window")
