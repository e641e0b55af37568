"""Kernels: own time a step of the device ops under the scope `gdn_core`,
the gated delta rule of the Gated DeltaNet layers (three in the Qwen3-Next
cell, 32 value heads on 16 key heads of 128, 16,384 positions in chunks of
64): the chunk-local products and the triangular inverse, the scan over the
chunks that carries the state, the outputs, and the same three in reverse in
the backward pass (`kungfu_tpu/ops/gated_delta.py`). Device trace over the
step program's scope table, milliseconds."""

from benchmark.families import qwen3_next


def read(record, trace):
    return qwen3_next.core_ms(record, trace, qwen3_next.LINEAR)
