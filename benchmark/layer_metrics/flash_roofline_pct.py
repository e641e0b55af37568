"""Kernels: the flash core's share of its roofline. The operations the
causal core requires a step (`families.olmoe.flash_core_flops_per_sample`:
forward 2 matmuls, backward 4, the causal half; what the two-pass backward
recomputes is not counted) over `flash_core_ms` over the chip's bf16 peak.
At head size 128 the core is compute-bound (`flash_core_bytes_per_sample`),
so the peak is the roof. Device trace, %."""

from benchmark.families import olmoe
from benchmark.layer_metrics import flash_core_ms


def read(record, trace):
    ms = flash_core_ms.read(record, trace)
    if ms is None:
        return None
    cfg = olmoe.cell_config(record)
    flops = (record["samples_per_step"] * cfg["num_hidden_layers"]
             * olmoe.flash_core_flops_per_sample(cfg))
    return olmoe.peak_share_pct(record, flops, ms)
