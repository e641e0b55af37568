"""Model: own time a step of the device ops under the scope `attn` of the
GLM-4.7-Flash cell that are not the cores': the norm before the mixer, the
down-projections to the two latents and the rotary key (`mla_down`), the
latents' norms (`mla_norm`), the up-projections to the heads and what lays
k out a head (`mla_up`), the rotary pass (`rope`) and the output
projection, forward and backward, of all six blocks: `attn` less what is
under `attn_latent`. Device trace over the step program's scope table,
milliseconds."""

from benchmark.families import glm4_moe_lite


def read(record, trace):
    return glm4_moe_lite.mixer_ms(record, trace)
