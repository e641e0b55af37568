"""Model: own time a step of the device ops under the scopes `hc`, `hc_in`
or `hc_out` of the Xing4.0 cell, all of its residual path: around each of
the ten branches the maps (`hc_maps`), the branch's input out of the four
streams (`hc_read`) and the streams with the branch's output mixed in
(`hc_write`), and the streams' entry and exit, forward and backward, a layer
run again among it. Device trace over the step program's scope table,
milliseconds."""

from benchmark.families import xing4_0


def read(record, trace):
    return xing4_0.hc_ms(record, trace)
