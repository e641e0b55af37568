"""Kernels: the attention core's share of its roofline in the Nemotron-3-Nano
cell. The least time the chip could take for the causal half of one core a
step, the larger of its required operations over the bf16 peak
(`families.nemotron_h.attn_core_flops_per_sample`: forward 2 products,
backward 4, at 32 heads of 128 over 8,192 positions: 1.65 TFLOP, 8.4 ms) and
its required bytes over the memory peak (`attn_core_bytes_per_sample`), over
`nope_core_ms`. Device trace, %."""

from benchmark.families import nemotron_h


def read(record, trace):
    return nemotron_h.core_roofline_pct(record, trace, nemotron_h.ATTENTION)
