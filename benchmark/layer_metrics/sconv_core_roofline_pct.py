"""Kernels: the gated short convolution's share of its roofline. The least
time the chip could take for it, the bytes it must move over the memory peak
(`families.lfm2_moe.conv_core_bytes_per_sample`: forward the projection's
output in and the gated result out, backward the projection's output and the
cotangent in and the projection's cotangent out, 11 arrays of S x D in
bfloat16, 369 MB a layer and 0.45 ms; its operations,
`conv_core_flops_per_sample`, are 2 microseconds' worth), over the time under
`sconv_core`. A forward pass that a recomputed layer runs again is in the
time and not in the bytes, so four of the cell's six layers cannot pass 74 %.
Device trace, %."""

from benchmark.families import lfm2_moe


def read(record, trace):
    return lfm2_moe.core_roofline_pct(record, trace, lfm2_moe.CONV)
