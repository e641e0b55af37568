"""Model: own time a step of the device ops under the scope `attn` of the
Xing4.0 cell that are not the cores': the norm before the mixer, the
down-projections to the q latent, the key/value latent and the one rotary
key (`mla_down`), the latents' norms (`mla_norm`), the up-projections to the
heads and what lays k out a head (`mla_up`), the rotary pass at YaRN's
frequencies (`rope`) and the output projection, forward and backward, five
layers: `attn` less what is under `attn_latent`. The residual path's mixings
stand outside `attn` (`mhc_ms`). Device trace over the step program's scope
table, milliseconds."""

from benchmark.families import xing4_0


def read(record, trace):
    return xing4_0.mixer_ms(record, trace)
