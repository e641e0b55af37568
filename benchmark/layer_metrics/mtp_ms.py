"""Model: own time a step of the device ops under the scope `mtp` of the
GLM-4.7-Flash cell, the multi-token-prediction module: the two norms and the
(2D, D) projection (`mtp_proj`), the module's block (its latent attention,
core included, and its expert layer, but for the grouped-matmul kernels,
which carry no scope and are claimed by name, as `moe_ms` claims them) and
the second head pass with its loss (`head_loss`), forward and backward. The
block's core and mixer are under `attn_latent` and in `mla_proj_ms` too:
this metric cuts the step the other way. Device trace over the step
program's scope table, milliseconds."""

from benchmark.families import glm4_moe_lite


def read(record, trace):
    return glm4_moe_lite.scope_own_ms(record, trace, {"mtp"})
