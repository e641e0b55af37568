"""Kernels: the full core's share of its roofline in the SmallThinker cell.
The least time the chip could take for the causal half of one core a step, the
larger of its required operations over the bf16 peak
(`families.smallthinker.core_flops_per_sample`: forward 2 products, backward
4, over 16,384^2 / 2 = 134.2e6 pairs a head, 28 heads of 128: 5.772 TFLOP,
29.3 ms) and its required bytes over the memory peak (`core_bytes_per_sample`),
over `nope_full_core_ms`. Device trace, %."""

from benchmark.families import smallthinker


def read(record, trace):
    return smallthinker.core_roofline_pct(record, trace, "full")
