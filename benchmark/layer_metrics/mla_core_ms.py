"""Kernels: own time a step of the device ops under the scope `attn_latent`
of the GLM-4.7-Flash cell, the latent-attention cores of its six blocks (the
five layers' and the multi-token-prediction module's): the flash forward
kernel, the two backward kernels and the row sums between them, at 20 heads
of 256 and 8,192 positions. What `mla_core_roofline_pct` divides by.
`flash_core_ms` reads the same kernels under `attn_core`, and its list is
the OLMoE cell's alone (`tests/benchmark/test_bench_olmoe.py`). Device trace
over the step program's scope table, milliseconds."""

from benchmark.families import glm4_moe_lite


def read(record, trace):
    return glm4_moe_lite.core_ms(record, trace)
