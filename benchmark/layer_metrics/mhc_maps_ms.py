"""Model: own time a step of the device ops under the scope `hc_maps` of the
Xing4.0 cell: a branch's three maps from the streams, the root mean square
over all 14,336 features, the float32 product with Phi (14,336 x 24) at the
highest precision, the sigmoids and the 20 Sinkhorn-Knopp passes over a 4 x
4 matrix a position, forward and backward, ten branches. A part of `mhc_ms`.
Device trace over the step program's scope table, milliseconds."""

from benchmark.families import xing4_0


def read(record, trace):
    return xing4_0.hc_ms(record, trace, {"hc_maps"})
