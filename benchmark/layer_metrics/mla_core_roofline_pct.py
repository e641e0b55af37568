"""Kernels: the latent-attention cores' share of their roofline. The least
time the chip could take for them, the larger of the operations the causal
half requires over the bf16 peak (`families.glm4_moe_lite.
core_flops_per_sample`: forward 2 matmuls, backward 4, over S^2 / 2 pairs a
head at 256 features; recomputation not counted) and the bytes they must move
over the memory peak (`core_bytes_per_sample`: q, k, v, o, do, dq, dk, dv at
20 heads of 256), over the cores' own time under `attn_latent` (what
`flash_core_ms` reads under `attn_core` there). At 8,192 positions the
operations bound it: 2.062 TFLOP and 1.007 GB a block, 10.5 ms against 1.2
ms, six blocks a step, the multi-token-prediction module's among them, which
`flash_roofline_pct` (a core a layer, at the OLMoE family's sizes) would not
count. The counts are the layer's, whatever implements the core. Device
trace, %."""

from benchmark.families import glm4_moe_lite


def read(record, trace):
    return glm4_moe_lite.core_roofline_pct(record, trace)
