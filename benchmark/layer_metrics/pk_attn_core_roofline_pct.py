"""Kernels: the packed attention core's share of its roofline. The least time
the chip could take for it, the larger of its required operations over the
bf16 peak and its required bytes over the memory peak, over `pk_attn_core_ms`.
The required operations are those of the causal pairs that lie within a
document (`families.granite_hybrid.attn_core_flops` of
`pool_within_document_pairs`: the run's own batches, made again from
`record["seed"]`, the mean over the pool), about 0.3 of a full causal sweep's:
the kernels of PR 52 visit every block under the diagonal and mask, so the
share reads low by what the dead blocks cost, which is the point of it. Device
trace, %."""

from benchmark.families import granite_hybrid


def read(record, trace):
    return granite_hybrid.core_roofline_pct(record, trace,
                                            granite_hybrid.ATTENTION)
