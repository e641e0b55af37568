"""Each mechanism of Kimi Linear's layers knocked out in turn (PR 69): the
float32 program with the fault against the plain reference on the family's
trained-like state (`tests/family_cases.py`); every fault has to read far over
what the bfloat16 program is allowed. A file of its own so that the suite's
workers share the compiles."""

import jax
import jax.numpy as jnp

import family_cases as fc
from family_cases import (  # noqa: F401  the shared case
    pytest_generate_tests, test_a_fault_fails_the_familys_tolerance)
from kungfu_tpu.models.mixers import kda

_as = lambda **changes: fc.model_changed(fc.KIMI_LINEAR.module, **changes)


def _a_decay_a_head(m):
    """Every key feature of a head decayed alike, by the head's mean."""
    log_decay = kda._log_decay

    def a_head(f, A_log, dt_bias):
        g = log_decay(f, A_log, dt_bias)
        return jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)

    m.setattr(kda, "_log_decay", a_head)


def _no_sigmoid_gate(m):
    def ungated(o, scale, gate, eps):
        o32 = o.astype(jnp.float32)
        var = jnp.mean(jnp.square(o32), axis=-1, keepdims=True)
        return (o32 * jax.lax.rsqrt(var + eps) * scale).astype(o.dtype)

    m.setattr(kda, "_gated_norm", ungated)


FAULTS = {
    "a_decay_a_head_in_place_of_a_feature": _a_decay_a_head,
    "no_sigmoid_gate": _no_sigmoid_gate,
    # the shared key and q's like features turned by their positions
    "a_turned_shared_key": _as(positions="rope"),
    # the value heads' size under the root: 1 / sqrt(8) for 1 / sqrt(24)
    "scale_of_the_value_heads_size": _as(attention_multiplier=8 ** -0.5),
}


FAMILY = fc.KIMI_LINEAR.with_cases(faults=FAULTS)
