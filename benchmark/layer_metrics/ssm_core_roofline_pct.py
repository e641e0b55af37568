"""Kernels: the state-space scan's share of its roofline. The least time the
chip could take for it, the larger of the operations the chunked form states
at the configuration's chunk of 128 over the bf16 peak
(`families.nemotron_h.ssm_core_flops_per_sample`: the scores a group, their
product with x a head, the chunk states and their read-out, forward once and
backward twice) and the bytes it must move over the memory peak
(`ssm_core_bytes_per_sample`: x, B, C, Delta, y and their cotangents once each
way), over `ssm_core_ms`. At 8,192 positions the bytes bound it: 0.442 GB
against 0.084 TFLOP a layer and sequence, 0.54 ms against 0.42 ms. Device
trace, %."""

from benchmark.families import nemotron_h


def read(record, trace):
    return nemotron_h.core_roofline_pct(record, trace, nemotron_h.MAMBA)
