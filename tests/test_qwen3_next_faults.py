"""Each mechanism of Qwen3-Next's layers knocked out in turn (PR 36): the
float32 program with the fault against the plain reference on the trained-like
state of `tests/test_qwen3_next.py`, whose helpers these are; every fault has
to read far over what the bfloat16 program is allowed. A file of its own so
that the suite's workers share the compiles."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from benchmark import harness
from benchmark.families import qwen3_next as family
from kungfu_tpu.models import transformer
from test_qwen3_next import (CONFIG, _reference, _sample, _state,  # noqa: F401
                             fresh_traces)

_model_config = family.model_config


def _changed(cfg, **changes):
    return dataclasses.replace(_model_config(cfg), **changes)


def _as(make):
    return lambda m: m.setattr(family, "model_config", make)


def _no_feature_gate(m):
    def ungated(ctx, gate, wo):
        B, H, S, hd = ctx.shape
        return ctx.transpose(0, 2, 1, 3).reshape(B, S, H * hd) @ wo

    m.setattr(transformer, "_feature_gated_out", ungated)
    m.setattr(transformer, "_feature_gated_out_kept", ungated)


def _no_convolution(m):
    from kungfu_tpu.ops import gated_delta

    m.setattr(gated_delta, "causal_conv", lambda x, taps: x * taps[-1].astype(x.dtype))


def _no_decay(m):
    from kungfu_tpu.ops import gated_delta

    rule = gated_delta.gated_delta_rule
    m.setattr(gated_delta, "gated_delta_rule",
              lambda q, k, v, g, beta: rule(q, k, v, jnp.zeros_like(g), beta))


def _keys_of_the_wrong_head(m):
    repeat = jnp.repeat

    def reversed_heads(x, r, axis):
        return repeat(x, r, axis=axis)[:, ::-1]

    m.setattr(transformer.jnp, "repeat", reversed_heads)


FAULTS = {
    "eight_bit_operands": lambda m: None,
    "norm_scale_w_not_one_plus_w": _as(lambda cfg: _changed(cfg, norm_offset=False)),
    "no_qk_norm_a_head": _as(lambda cfg: _changed(cfg, qk_norm=False)),
    "no_gate_a_feature": _no_feature_gate,
    "no_gate_on_the_shared_expert": _as(lambda cfg: _changed(cfg, shared_gate=False)),
    "no_shared_expert": _as(lambda cfg: _changed(cfg, shared_ff=0, shared_gate=False)),
    "rotary_over_the_whole_head": _as(lambda cfg: _changed(cfg, rotary_share=1.0)),
    "gates_not_renormalised": _as(lambda cfg: _changed(cfg, gates="raw")),
    "no_convolution": _no_convolution,
    "no_decay": _no_decay,
    "keys_of_the_wrong_head": _keys_of_the_wrong_head,
}


def _eight_bit(state):
    """Every matrix rounded to float8_e4m3 (3 mantissa bits): what 8-bit
    operands do to the matmuls."""
    return jax.tree.map(
        lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype) if w.ndim >= 2 else w,
        state)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_fails_the_familys_tolerance(fault, monkeypatch, fresh_traces):
    """Each in float32 compute, so that nothing but the fault is in the
    error: it has to be far over what the bfloat16 program is allowed."""
    state, sample = _state(), _sample()
    FAULTS[fault](monkeypatch)
    program_state = _eight_bit(state) if fault == "eight_bit_operands" else state
    _, want = _reference()
    loss, grads = family.program_loss_and_grads(CONFIG)(program_state, sample)
    error = harness.relative_error(grads, want)
    assert error > 2 * family.GRAD_RTOL, (fault, error)
