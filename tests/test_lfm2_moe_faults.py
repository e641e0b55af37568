"""Each mechanism of LFM2-24B-A2B's layers knocked out in turn (PR 57): the
float32 program with the fault against the plain reference on the family's
trained-like state (`tests/family_cases.py`); every fault has to read far over
what the bfloat16 program is allowed. A file of its own so that the suite's
workers share the compiles."""

import jax
import jax.numpy as jnp

import family_cases as fc
from family_cases import (  # noqa: F401  the shared case
    pytest_generate_tests, test_a_fault_fails_the_familys_tolerance)
from kungfu_tpu.models import transformer
from kungfu_tpu.ops import short_conv

_as = lambda **changes: fc.model_changed(fc.LFM2_MOE.module, **changes)


def _op(changed):
    """The fault that hands the operator other arguments: `changed(bcx, taps)
    -> (bcx, taps)`."""
    def fault(m):
        op = short_conv.short_conv
        m.setattr(short_conv, "short_conv",
                  lambda bcx, taps, *segments: op(*changed(bcx, taps), *segments))

    return fault


def _thirds(bcx, order):
    parts = jnp.split(bcx, 3, axis=-1)
    return jnp.concatenate([parts[i] for i in order], axis=-1)


def _no_gate_behind(m):
    """y = conv(B * x) W_out: the gate C left out."""
    op = short_conv.short_conv

    def ungated(bcx, taps, *segments):
        ones = jnp.ones_like(bcx[..., :taps.shape[1]])
        return op(_thirds(bcx, (0, 0, 2)).at[..., taps.shape[1]:2 * taps.shape[1]]
                  .set(ones), taps, *segments)

    m.setattr(short_conv, "short_conv", ungated)


def _an_untied_head(m):
    """The head's matrix a leaf of its own that happens to hold the
    embedding's values: the embedding is given no gradient through it."""
    logits = transformer._head_logits
    m.setattr(transformer, "_head_logits", lambda params, x, cfg, normed=False: logits(
        {**params, "embed": jax.lax.stop_gradient(params["embed"])}, x, cfg, normed))


FAULTS = {
    "a_dropped_tap": _op(lambda bcx, taps: (bcx, taps.at[0].set(0.0))),
    "the_taps_turned_round": _op(lambda bcx, taps: (bcx, taps[::-1])),
    "the_thirds_in_the_order_x_B_C": _op(
        lambda bcx, taps: (_thirds(bcx, (1, 2, 0)), taps)),
    "no_gate_behind_the_convolution": _no_gate_behind,
    "no_qk_norm": _as(qk_norm=False),
    "no_rotary_pass": _as(positions="none"),
    "an_untied_head": _an_untied_head,
    "bias_in_the_weight": fc.bias_in_the_weight,
}

FAMILY = fc.LFM2_MOE.with_cases(faults=FAULTS)
