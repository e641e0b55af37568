"""Model: own time a step of the device ops under the scope `ssm` that are not
the scan's, in the Granite 4.0-H cell's nine Mamba-2 layers: the norm before
the mixer, both projections and the float32 one of the step (`ssm_proj`), the
causal convolution under the documents' boundaries with its bias, its silu,
the step and the decay (`ssm_conv`), D x, the gate and the norm over all 4,096
features (`ssm_norm`), forward and backward: `ssm` less `pk_ssm_core_ms`.
Device trace over the step program's scope table, milliseconds."""

from benchmark.families import granite_hybrid


def read(record, trace):
    return granite_hybrid.mix_ms(record, trace)
