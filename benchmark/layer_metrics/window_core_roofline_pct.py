"""Kernels: the sliding-window cores' share of their roofline. The least
time the chip could take for them, the larger of the operations the band
requires over the bf16 peak (`families.laguna.core_flops_per_sample`: forward
2 matmuls, backward 4, over S x 512 - 512^2 / 2 pairs a head; recomputation
not counted) and the bytes they must move over the memory peak
(`core_bytes_per_sample`: 12 arrays, q's at 72 heads, k's and v's at 8),
over `window_core_ms`. At window 512 and 9 query heads to a key/value head
the operations bound it, by less than twice: 0.449 TFLOP and 1.007 GB a
layer and sequence, 2.28 ms against 1.23 ms. Device trace, %."""

from benchmark.families import laguna


def read(record, trace):
    return laguna.core_roofline_pct(record, trace, "window")
