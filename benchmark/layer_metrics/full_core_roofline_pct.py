"""Kernels: the full-attention cores' share of their roofline. The least
time the chip could take for them, the larger of the operations the causal
half requires over the bf16 peak (`families.laguna.core_flops_per_sample`:
forward 2 matmuls, backward 4, over S^2 / 2 pairs a head; recomputation not
counted) and the bytes they must move over the memory peak
(`core_bytes_per_sample`), over `full_core_ms`. At 8,192 positions the
operations bound it: 2.474 TFLOP and 0.705 GB a layer and sequence, 12.56 ms
against 0.86 ms. Device trace, %."""

from benchmark.families import laguna


def read(record, trace):
    return laguna.core_roofline_pct(record, trace, "full")
