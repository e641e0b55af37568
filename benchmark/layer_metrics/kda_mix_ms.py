"""Model: own time a step of the device ops under the scope `kda` that are
not the delta rule's: the norm before the mixer, the q, k, v projections, the
float32 ones of the decay's low rank and of beta and the gate's low rank
(`kda_proj`), the three causal convolutions, their silu and the q/k
normalisation (`kda_conv`), the gated norm (`kda_norm`) and the output
projection, forward and backward: `kda` less `kda_core_ms`. Device trace
over the step program's scope table, milliseconds."""

from benchmark.families import kimi_linear


def read(record, trace):
    return kimi_linear.mixer_ms(record, trace, kimi_linear.KDA)
