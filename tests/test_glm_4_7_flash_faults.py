"""Each mechanism of GLM-4.7-Flash's layers knocked out in turn (PR 41): the
float32 program with the fault against the plain reference on the family's
trained-like state (`tests/family_cases.py`); every fault has to read far over
what the bfloat16 program is allowed. A file of its own so that the suite's
workers share the compiles."""

import jax.numpy as jnp

import family_cases as fc
from family_cases import (  # noqa: F401  the shared case
    pytest_generate_tests, test_a_fault_fails_the_familys_tolerance)
from kungfu_tpu.models import transformer
from kungfu_tpu.models.mixers import latent

_as = lambda **changes: fc.model_changed(fc.GLM_4_7_FLASH.module, **changes)


def _no_latent_norm(m):
    norm = latent._rmsnorm

    def skipping(x, scale, eps=1e-6):
        # the latents are the only normed arrays of these widths
        return x if x.shape[-1] in (24, 16) else norm(x, scale, eps)

    m.setattr(latent, "_rmsnorm", skipping)


def _rotary_key_not_shared(m):
    """Each head takes rotary features of its own: head h those of the one
    key rolled by h."""
    broadcast = jnp.broadcast_to

    def apart(x, shape):
        out = broadcast(x, shape)
        if x.ndim == 4 and x.shape[2] == 1 and shape[2] == 4:  # k_r over heads
            out = jnp.stack([jnp.roll(out[:, :, h], h, axis=-1)
                             for h in range(shape[2])], axis=2)
        return out

    m.setattr(latent.jnp, "broadcast_to", apart)


def _mtp_fed_the_same_token(m):
    hidden = transformer._mtp_hidden

    def fed_t_i(params, x, tokens_next, cfg):
        # t_i in the place of t_{i+1}: the ids one back, the first its own
        back = jnp.concatenate([tokens_next[:, :1], tokens_next[:, :-1]], axis=1)
        return hidden(params, x, back, cfg)

    m.setattr(transformer, "_mtp_hidden", fed_t_i)


def _mtp_with_a_head_of_its_own(m):
    head_loss = transformer.lm_head_loss

    def own_head(params, x, targets, cfg):
        if params["ln_f_scale"] is params["mtp"]["ln_f_scale"]:  # the module's pass
            params = {**params, "lm_head": params["lm_head"][::-1]}
        return head_loss(params, x, targets, cfg)

    m.setattr(transformer, "lm_head_loss", own_head)


FAULTS = {
    "no_latent_norm": _no_latent_norm,
    "rotary_key_not_shared": _rotary_key_not_shared,
    "softmax_in_place_of_sigmoid": _as(router_scores="softmax"),
    "bias_in_the_weight": fc.bias_in_the_weight,
    "scale_1_in_place_of_1_8": _as(routed_scale=1.0),
    "no_selection_bias": _as(router_bias=False),
    "mtp_fed_t_i_in_place_of_t_i_plus_1": _mtp_fed_the_same_token,
    "mtp_with_a_head_of_its_own": _mtp_with_a_head_of_its_own,
    "mtp_weight_1_in_place_of_0_3": _as(mtp_weight=1.0),
}


FAMILY = fc.GLM_4_7_FLASH.with_cases(faults=FAULTS)
