"""Each mechanism of Nemotron-3-Nano's layers knocked out in turn (PR 43): the
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
from kungfu_tpu.ops import moe, ssm_scan

_as = lambda **changes: fc.model_changed(fc.NEMOTRON_H.module, **changes)


def _b_and_c_of_the_wrong_group(m):
    scan = ssm_scan.ssm_scan
    m.setattr(ssm_scan, "ssm_scan", lambda q, k, v, g, chunk: scan(
        jnp.roll(q, 1, axis=1), jnp.roll(k, 1, axis=1), v, g, chunk))


def _no_softplus(m):
    m.setattr(transformer.jax.nn, "softplus", lambda x: x)


def _expert_function(m, act):
    """`act(up)` in the place of relu(up)^2, in the routed experts and the
    shared expert alike."""
    m.setattr(moe, "_relu2_down", lambda up, w_down, sizes: moe.grouped_matmul(
        act(up), w_down, sizes))
    m.setattr(transformer, "_relu2_out", lambda up, w_down: act(up) @ w_down)


FAULTS = {
    "gate_after_the_norm": fc.gate_after_the_norm,
    "norm_over_all_features_and_not_a_group": fc.norm_over(1),
    "b_and_c_of_the_wrong_group": _b_and_c_of_the_wrong_group,
    "delta_without_the_softplus": _no_softplus,
    "a_rotary_pass": _as(positions="rope"),
    "gated_silu_in_the_experts": lambda m: _expert_function(
        m, lambda up: jax.nn.silu(up) * up),
    "relu_in_place_of_relu2": lambda m: _expert_function(m, jax.nn.relu),
    "bias_in_the_weight": fc.bias_in_the_weight,
    "scale_1_in_place_of_2_5": _as(routed_scale=1.0),
}


def _no_d_x(state):
    """D = 0 in every Mamba-2 layer: `y_t = H_t C_t` without `+ D x_t`."""
    return {**state, "layers": tuple(
        {**stack, "D_skip": jnp.zeros_like(stack["D_skip"])}
        if "D_skip" in stack else stack for stack in state["layers"])}


FAMILY = fc.NEMOTRON_H.with_cases(faults=FAULTS,
                                  state_faults={"no_d_x": _no_d_x})
