"""Kernels: the packed state-space scan's share of its roofline. The least
time the chip could take for it, the larger of the operations the chunked form
states at the configuration's `mamba_chunk_size` of 256 and one group over the
bf16 peak (`families.granite_hybrid.ssm_core_flops_per_sample`: the scores a
group, their product with x a head, the chunk states and their read-out,
forward once and backward twice; a boundary takes none away) and the bytes it
must move over the memory peak (`ssm_core_bytes_per_sample`: x, B, C, Delta, y
and their cotangents once each way, the documents' numbers beside them), over
`pk_ssm_core_ms`. At 8,192 positions the operations bound it: 0.105 TFLOP
against 0.354 GB a layer and row, 0.53 ms against 0.43 ms. Device trace, %."""

from benchmark.families import granite_hybrid


def read(record, trace):
    return granite_hybrid.core_roofline_pct(record, trace, granite_hybrid.MAMBA)
