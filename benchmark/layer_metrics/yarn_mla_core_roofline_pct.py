"""Kernels: the Xing4.0 cell's latent-attention cores' share of their
roofline. The least time the chip could take for the five, the larger of
the operations the causal half requires over the bf16 peak
(`families.glm4_moe_lite.core_flops_per_sample`: forward 2 matmuls, backward
4, over S^2 / 2 pairs a head, QK^T at 192 features and PV at 128;
recomputation not counted) and the bytes they must move over the memory peak
(`core_bytes_per_sample`: q, k, dq, dk at 32 heads of 192 and v, o, do, dv at
128), over `yarn_mla_core_ms`. At 4,096 positions the operations bound it:
0.515 TFLOP and 0.503 GB a layer, 2.6 ms against 0.6 ms. Device trace, %."""

from benchmark.families import xing4_0


def read(record, trace):
    return xing4_0.core_roofline_pct(record, trace)
