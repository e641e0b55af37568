"""Kernels: the two causal cores' share of their roofline. The least time the
chip could take for them, the larger of the operations the causal half
requires over the bf16 peak (`families.lfm2_moe.attn_core_flops_per_sample`:
forward 2 matmuls, backward 4, over S^2 / 2 pairs a query head at 64 features:
0.82 TFLOP a layer, 4.2 ms) and the bytes they must move over the memory peak
(`attn_core_bytes_per_sample`: 6 arrays at 32 heads and 6 at 8, 0.25 ms), over
the time under `attn_full`, which `full_core_ms` reads. The operations bound
it; recomputation is not counted. Device trace, %."""

from benchmark.families import lfm2_moe


def read(record, trace):
    return lfm2_moe.core_roofline_pct(record, trace, lfm2_moe.ATTENTION)
