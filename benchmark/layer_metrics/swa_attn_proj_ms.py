"""Model: own time a step of the device ops under the scope `attn` of the
SmallThinker cell that are not the cores': the norm before the mixer, the q,
k, v and output projections (2,560 to 28 and 4 heads of 128) and, in the
three window layers, the rotary pass (`rope`), forward and backward: `attn`
less `swa_core_ms` and `nope_full_core_ms`. Device trace over the step
program's scope table, milliseconds."""

from benchmark.families import smallthinker


def read(record, trace):
    return smallthinker.attn_proj_ms(record, trace)
