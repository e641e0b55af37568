"""Model: own time a step of the device ops under the scope `ssm` that are not
the scan's: the norm before the mixer, both projections and the float32 one of
the step (`ssm_proj`), the causal convolution with its bias, its silu, the
step and the decay (`ssm_conv`), D x, the gate and the norm over groups
(`ssm_norm`), forward and backward: `ssm` less `ssm_core_ms`. Device trace
over the step program's scope table, milliseconds."""

from benchmark.families import nemotron_h


def read(record, trace):
    return nemotron_h.mix_ms(record, trace)
