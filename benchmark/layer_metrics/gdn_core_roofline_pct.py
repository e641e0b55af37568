"""Kernels: the gated delta rule's share of its roofline. The least time the
chip could take for it, the larger of the operations the recurrence states
over the bf16 peak (`families.qwen3_next.delta_core_flops_per_sample`: 7 dk
dv a value head and position forward, twice that backward) and the bytes it
must move over the memory peak (`delta_core_bytes_per_sample`: its inputs,
outputs and their cotangents once each way), over `gdn_core_ms`. At 16,384
positions the bytes bound it: 1.086 GB against 0.180 TFLOP a layer and
sequence, 1.33 ms against 0.92 ms. Device trace, %."""

from benchmark.families import qwen3_next


def read(record, trace):
    return qwen3_next.core_roofline_pct(record, trace, qwen3_next.LINEAR)
