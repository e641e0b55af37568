"""Each mechanism of Xing4.0's layers knocked out in turn (PR 71): the float32
program with the fault against the plain reference on the family's
trained-like state (`tests/family_cases.py`); every fault has to read far over
what the bfloat16 program is allowed. A file of its own so that the suite's
workers share the compiles."""

import functools

import jax
import jax.numpy as jnp

import family_cases as fc
from benchmark import harness
from family_cases import (  # noqa: F401  the shared case
    pytest_generate_tests, test_a_fault_fails_the_familys_tolerance)
from kungfu_tpu.ops import hyper_connections as hc

_as = lambda **changes: fc.model_changed(fc.XING4_0_STACK.module, **changes)


def _a_softmax_over_the_rows(m):
    """H_res = softmax of each row's logits: the rows sum to one and the
    columns to anything."""
    m.setattr(hc, "sinkhorn", lambda logits, iters, eps, clamp: jax.nn.softmax(
        jnp.clip(logits, *clamp), axis=1))


def _maps_with(pre=None, post=None):
    """The fault that makes H_pre another function of its logits, or H_post
    of its value."""
    def fault(m):
        maps = hc.maps

        def changed(X, phi, a, b, n, iters, eps, clamp):
            made = maps(X, phi, a, b, n, iters, eps, clamp)
            if pre is not None:  # the logits of a sigmoid's value
                made = made._replace(
                    pre=pre(jnp.log(made.pre) - jnp.log1p(-made.pre)))
            return made if post is None else made._replace(post=post(made.post))

        m.setattr(hc, "maps", changed)

    return fault


def _exit_by_the_first_stream(m):
    m.setattr(hc, "leave", lambda X, n: X[..., :X.shape[-1] // n])


def _no_dynamic_part(state):
    """a = 0 in every branch: the maps are their biases' alone."""
    def static(layer):
        return {name: jnp.zeros_like(leaf) if name.endswith("_a") else leaf
                for name, leaf in layer.items()}

    return {**state, "layers": tuple(static(stack) for stack in state["layers"])}


_hd = 24 + 8  # the small configuration's q/k head

FAULTS = {
    "one_sinkhorn_pass_in_place_of_20": _as(hc_sinkhorn_iters=1),
    "a_softmax_over_the_rows_in_place_of_sinkhorn": _a_softmax_over_the_rows,
    "h_post_without_its_2": _maps_with(post=lambda p: p / 2.0),
    "a_softmax_for_h_pre": _maps_with(pre=functools.partial(jax.nn.softmax, axis=0)),
    "exit_by_the_first_stream": _exit_by_the_first_stream,
    "plain_rotary_frequencies": _as(yarn=()),
    "the_softmax_scale_without_mscale_squared": _as(attention_multiplier=_hd ** -0.5),
}


FAMILY = fc.XING4_0_STACK.with_cases(
    faults=FAULTS, state_faults={"the_dynamic_part_dropped": _no_dynamic_part})


def test_bfloat16_maps_fail_the_float32_programs_tolerance(monkeypatch, fresh_traces):
    """The maps (the product with Phi's result, the sigmoids, the Sinkhorn
    passes) in bfloat16 under float32 compute everywhere else: a few per cent
    of the gradients, four orders over the 7e-7 the float32 maps read and a
    hundred times the float32 program's tolerance, and under the bfloat16
    program's own (the shared case's `2 * GRAD_RTOL`): at this size a
    position's maps weigh in the gradients as one branch's rounding does."""
    _, want = FAMILY.reference()
    monkeypatch.setattr(hc, "MAP_DTYPE", jnp.bfloat16)
    _, grads = FAMILY.module.program_loss_and_grads(FAMILY.config)(
        FAMILY.state(), FAMILY.sample())
    error = harness.relative_error(grads, want)
    assert 100 * FAMILY.float32_grad_rtol < error < FAMILY.module.GRAD_RTOL, error
