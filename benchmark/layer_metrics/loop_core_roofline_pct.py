"""Kernels: the attention cores' share of their roofline in the Ouro cell.
The least time the chip could take for the causal half of the T x L = 32
cores a step, the larger of their required operations over the bf16 peak
(`families.ouro.core_flops_per_sample`: forward 2 products, backward 4, at 16
heads of 128 over 4,096 positions: 0.206 TFLOP an application, 6.6 a step,
33.5 ms) and their required bytes over the memory peak
(`core_bytes_per_sample`), over `loop_core_ms`. Device trace, %."""

from benchmark.families import ouro


def read(record, trace):
    return ouro.core_roofline_pct(record, trace)
