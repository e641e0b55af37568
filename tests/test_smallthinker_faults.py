"""Each mechanism of SmallThinker-21BA3B-Instruct's layers knocked out in turn
(PR 65): the float32 program with the fault against the plain reference on the
family's trained-like state (`tests/family_cases.py`), one period of the
model; every fault has to read far over what the bfloat16 program is allowed.
A file of its own so that the suite's workers share the compiles."""

import jax
import jax.numpy as jnp

import family_cases as fc
from family_cases import (  # noqa: F401  the shared case
    pytest_generate_tests, test_a_fault_fails_the_familys_tolerance)
from kungfu_tpu.ops import moe

_as = lambda **changes: fc.model_changed(fc.SMALLTHINKER.module, **changes)


def _a_bfloat16_router(m):
    """The router's product on bfloat16 operands: a token whose last chosen
    score is within that rounding of the next takes another expert."""
    def route(x, router_w, top_k, scores="softmax", bias=None):
        logits = jnp.dot(x.astype(jnp.bfloat16), router_w.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        return (logits, probs, *jax.lax.top_k(probs, top_k))

    m.setattr(moe, "route", route)


FAULTS = {
    "the_router_fed_the_normed_state_behind_the_mixer": _as(router_input="ffn"),
    "silu_for_relu": _as(expert_act="swiglu"),
    "rotary_on_a_full_layer": _as(positions="rope"),
    "the_window_off": _as(window=0),
    "a_bfloat16_router": _a_bfloat16_router,
    "gates_not_renormalised": _as(gates="raw"),
}

FAMILY = fc.SMALLTHINKER_ONE_PERIOD.with_cases(faults=FAULTS)
