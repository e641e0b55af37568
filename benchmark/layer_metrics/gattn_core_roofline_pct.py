"""Kernels: the head-256 causal core's share of its roofline. The least time
the chip could take for it, the larger of the operations the causal half
requires over the bf16 peak (`families.qwen3_next.attn_core_flops_per_sample`:
forward 2 matmuls, backward 4, over S^2 / 2 pairs a head; recomputation not
counted) and the bytes it must move over the memory peak
(`attn_core_bytes_per_sample`), over `gattn_core_ms`. At 16,384 positions
the operations bound it: 6.597 TFLOP and 0.906 GB a sequence, 33.5 ms
against 1.1 ms. Device trace, %."""

from benchmark.families import qwen3_next


def read(record, trace):
    return qwen3_next.core_roofline_pct(record, trace, qwen3_next.FULL)
