"""Kernels: the share of the chip's bf16 peak that the expert matmuls
reach. The operations the chosen experts' three matmuls require a step
(`families.olmoe.expert_matmul_flops_per_token`: forward and backward, 8
experts a token; nothing recomputed is counted) over `expert_ffn_ms` over
the peak. Device trace, %."""

from benchmark.families import olmoe
from benchmark.layer_metrics import expert_ffn_ms


def read(record, trace):
    ms = expert_ffn_ms.read(record, trace)
    if ms is None:
        return None
    cfg = olmoe.cell_config(record)
    tokens = record["samples_per_step"] * cfg["max_position_embeddings"]
    flops = (tokens * cfg["num_hidden_layers"]
             * olmoe.expert_matmul_flops_per_token(cfg))
    return olmoe.peak_share_pct(record, flops, ms)
