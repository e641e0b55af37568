"""Each mechanism of Qwen3-Next's layers knocked out in turn (PR 36): the
float32 program with the fault against the plain reference on the family's
trained-like state (`tests/family_cases.py`); every fault has to read far over
what the bfloat16 program is allowed. A file of its own so that the suite's
workers share the compiles."""

import jax.numpy as jnp

import family_cases as fc
from family_cases import (  # noqa: F401  the shared case
    pytest_generate_tests, test_a_fault_fails_the_familys_tolerance)
from kungfu_tpu.models.mixers import attention, gated_delta as gated_delta_mixer
from kungfu_tpu.ops import gated_delta

_as = lambda **changes: fc.model_changed(fc.QWEN3_NEXT.module, **changes)


def _no_feature_gate(m):
    def ungated(ctx, gate, wo):
        B, H, S, hd = ctx.shape
        return ctx.transpose(0, 2, 1, 3).reshape(B, S, H * hd) @ wo

    m.setattr(attention, "_feature_gated_out", ungated)
    m.setattr(attention, "_feature_gated_out_kept", ungated)


def _no_convolution(m):
    m.setattr(gated_delta, "causal_conv", lambda x, taps: x * taps[-1].astype(x.dtype))


def _no_decay(m):
    rule = gated_delta.gated_delta_rule
    m.setattr(gated_delta, "gated_delta_rule",
              lambda q, k, v, g, beta: rule(q, k, v, jnp.zeros_like(g), beta))


def _keys_of_the_wrong_head(m):
    repeat = jnp.repeat

    def reversed_heads(x, r, axis):
        return repeat(x, r, axis=axis)[:, ::-1]

    m.setattr(gated_delta_mixer.jnp, "repeat", reversed_heads)


FAULTS = {
    "norm_scale_w_not_one_plus_w": _as(norm_offset=False),
    "no_qk_norm_a_head": _as(qk_norm=False),
    "no_gate_a_feature": _no_feature_gate,
    "no_gate_on_the_shared_expert": _as(shared_gate=False),
    "no_shared_expert": _as(shared_ff=0, shared_gate=False),
    "rotary_over_the_whole_head": _as(rotary_share=1.0),
    "gates_not_renormalised": _as(gates="raw"),
    "no_convolution": _no_convolution,
    "no_decay": _no_decay,
    "keys_of_the_wrong_head": _keys_of_the_wrong_head,
}


FAMILY = fc.QWEN3_NEXT.with_cases(faults=FAULTS)
