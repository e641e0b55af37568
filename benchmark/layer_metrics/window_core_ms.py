"""Kernels: own time a step of the device ops under the scope `attn_window`,
the attention cores of the sliding-window layers (three in the Laguna cell,
72 query heads on 8 key/value heads, window 512): the flash forward kernel,
the two backward kernels and the row sums between them. The kernels visit
the blocks of the band and no others (`ops.flash_attention._kv_steps`).
Device trace over the step program's scope table, milliseconds."""

from benchmark.families import laguna


def read(record, trace):
    return laguna.core_ms(record, trace, "window")
