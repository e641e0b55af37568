"""Kernels: own time a step of the device ops under the scope `sconv_core` of
the LFM2-24B-A2B cell, the gated short convolution C * conv3(B * x) of its six
convolution layers between their two projections (`ops.short_conv`: one
forward and one backward kernel, the taps' gradient's sums added up; a layer
that is run again runs the forward kernel twice a step), at 8,192 positions of
2,048 channels. What `sconv_core_roofline_pct` divides by. Device trace over
the step program's scope table, milliseconds."""

from benchmark.families import lfm2_moe


def read(record, trace):
    return lfm2_moe.core_ms(record, trace, lfm2_moe.CONV)
