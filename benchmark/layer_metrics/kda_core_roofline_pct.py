"""Kernels: the KDA delta rule's share of its roofline. The least time the
chip could take for it, the larger of the operations the recurrence states
over the bf16 peak (`families.kimi_linear.kda_core_flops_per_sample`: 7 dk dv
a head and position forward, twice that backward) and the bytes it must move
over the memory peak (`kda_core_bytes_per_sample`: its inputs, outputs and
their cotangents once each way, the log decay a float32 a key feature), over
`kda_core_ms`. At 16,384 positions the bytes bound it: 2.288 GB against 0.180
TFLOP a layer and sequence, 2.79 ms against 0.92 ms, whatever kernel
implements the rule. Device trace, %."""

from benchmark.families import kimi_linear


def read(record, trace):
    return kimi_linear.core_roofline_pct(record, trace, kimi_linear.KDA)
