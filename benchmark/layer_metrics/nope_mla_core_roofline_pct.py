"""Kernels: the Kimi-Linear cell's latent-attention core's share of its
roofline. The least time the chip could take for it, the larger of the
operations the causal half requires over the bf16 peak
(`families.kimi_linear.mla_core_flops_per_sample`: forward 2 matmuls,
backward 4, over S^2 / 2 pairs a head, QK^T at 192 features and PV at 128;
recomputation not counted) and the bytes it must move over the memory peak
(`mla_core_bytes_per_sample`: q, k, dq, dk at 32 heads of 192 and v, o, do,
dv at 128), over `nope_mla_core_ms`. At 16,384 positions the operations
bound it: 8.246 TFLOP and 2.013 GB a layer, 41.9 ms against 2.5 ms. Device
trace, %."""

from benchmark.families import kimi_linear


def read(record, trace):
    return kimi_linear.core_roofline_pct(record, trace, kimi_linear.MLA)
