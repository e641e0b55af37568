"""Model: own time a step of the device ops under the scope `dsa_kl` of the
Keye-VL-2.0-30B-A3B cell: what the indexer learns from, of its six layers:
the head-mean of the core's probabilities at the chosen keys
(`ops.sparse_attention.head_mean_probs`, one kernel over the 32 heads, twice a
step: the loss's derivative wants the mean again), the indexer's soft maximum
over the chosen keys, the KL divergence and its derivative in the scores.
Device trace over the step program's scope table, milliseconds."""

from benchmark.families import keye_vl2


def read(record, trace):
    return keye_vl2.kl_ms(record, trace)
