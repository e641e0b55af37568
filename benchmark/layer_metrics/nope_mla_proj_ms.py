"""Model: own time a step of the device ops under the scope `attn` of the
Kimi-Linear cell that are not the core's: the norm before the mixer, q's
projection straight from the hidden states (no q latent), the down-projection
to the key/value latent and the one shared key (`mla_down`), the latent's
norm (`mla_norm`), the up-projection to the heads and what lays k out a head
(`mla_up`; nothing is turned by position) and the output projection, forward
and backward: `attn` less what is under `attn_latent`. Device trace over the
step program's scope table, milliseconds."""

from benchmark.families import kimi_linear


def read(record, trace):
    return kimi_linear.mixer_ms(record, trace, kimi_linear.MLA)
