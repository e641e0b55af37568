"""Kernels: own time a step of the device ops under the scope `attn_full`
in the Qwen3-Next cell, the softmax core of its one gated attention layer (16
query heads on 2 key/value heads of 256, causal over 16,384 positions): the
flash forward kernel, the two backward kernels and the row sums between
them. Device trace over the step program's scope table, milliseconds."""

from benchmark.families import qwen3_next


def read(record, trace):
    return qwen3_next.core_ms(record, trace, qwen3_next.FULL)
