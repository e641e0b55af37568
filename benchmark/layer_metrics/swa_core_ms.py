"""Kernels: own time a step of the device ops under the scope `attn_window`
of the SmallThinker cell, the attention cores of its three window layers (28
query heads on 4 key/value heads of 128, window 4,096 of 16,384 positions,
rotary positions): the flash forward kernel, the two backward kernels, the
row sums between them and the layout copies at their doors. The kernels visit
the blocks of the band and no others (`ops.flash_attention._kv_steps`): 8
blocks of 512 wide. Device trace over the step program's scope table,
milliseconds."""

from benchmark.families import smallthinker


def read(record, trace):
    return smallthinker.core_ms(record, trace, "window")
