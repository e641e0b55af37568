"""Model: own time a step of the device ops under the scope `sconv` of the
LFM2-24B-A2B cell that are not the operator's: the norm before the mixer and
the two projections W_in (2,048 x 6,144) and W_out (2,048 x 2,048) under
`sconv_proj`, forward and backward, of its six convolution layers: `sconv`
less what is under `sconv_core`. Device trace over the step program's scope
table, milliseconds."""

from benchmark.families import lfm2_moe


def read(record, trace):
    return lfm2_moe.mix_ms(record, trace)
