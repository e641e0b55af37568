"""Kernels: own time a step of the device ops under the scope `attn_latent`
of the Kimi-Linear cell, the core of its one latent-attention layer without
positions: the flash forward kernel, the two backward kernels and the row
sums between them, at 32 q/k heads of 128 + 64 features on value heads of 128
(`ops.flash_attention`: `hd` 192, `hd_v` 128) over the causal half of 16,384
positions. What `nope_mla_core_roofline_pct` divides by. Device trace over
the step program's scope table, milliseconds."""

from benchmark.families import kimi_linear


def read(record, trace):
    return kimi_linear.core_ms(record, trace, kimi_linear.MLA)
