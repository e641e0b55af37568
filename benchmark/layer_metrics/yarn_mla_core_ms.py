"""Kernels: own time a step of the device ops under the scope `attn_latent`
of the Xing4.0 cell, the cores of its five latent-attention layers under
YaRN: the flash forward kernel, the two backward kernels and the row sums
between them, at 32 q/k heads of 128 + 64 features on value heads of 128
(`ops.flash_attention`: `hd` 192, `hd_v` 128), the scores times mscale^2 /
sqrt(192), over the causal half of 4,096 positions. What
`yarn_mla_core_roofline_pct` divides by. Device trace over the step
program's scope table, milliseconds."""

from benchmark.families import xing4_0


def read(record, trace):
    return xing4_0.core_ms(record, trace)
