"""Model: own time a step of the device ops under the scope `ffn` in the Ouro
cell and not under `post_norm`: the norm before the gated-silu feed-forward,
its three products, the silu and the gate's product, of all 32 layer
applications, forward, run again and backward; the norm behind the branch is
`loop_norm_ms`'. Device trace over the step program's scope table,
milliseconds."""

from benchmark.families import ouro


def read(record, trace):
    return ouro.scope_ms(record, trace,
                         lambda names: "ffn" in names and "post_norm" not in names)
