"""Model: own time a step of the device ops under the scope `attn` of the
Keye-VL-2.0-30B-A3B cell that are none of the mechanism's: `attn` less
`dsa_index`, `dsa_select`, `attn_sparse` and `dsa_kl`, that is the norm before
the mixer, the four projections (`attn_proj`), the norm a head (`qk_norm`) and
the rotary pass (`rope`), forward and backward, of its six layers. The
accepted `attn_proj_ms` subtracts the `attn_window` and `attn_full` cores and
would count the mechanism's scopes as projections, so the cell does not join
it. Device trace over the step program's scope table, milliseconds."""

from benchmark.families import keye_vl2


def read(record, trace):
    return keye_vl2.mix_ms(record, trace)
