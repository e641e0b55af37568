"""Each mechanism of Ouro's loop knocked out in turn (PR 48): the float32
program with the fault against the plain reference on the family's
trained-like state (`tests/family_cases.py`); every fault has to read far
over what the bfloat16 program is allowed. A file of its own so that the
suite's workers share the compiles."""

import jax
import jax.numpy as jnp

import family_cases as fc
from benchmark import harness
from family_cases import (  # noqa: F401  the shared case
    pytest_generate_tests, test_a_fault_fails_the_familys_tolerance)
from kungfu_tpu.models import transformer
from test_ouro import unrolled_hidden

_as = lambda **changes: fc.model_changed(fc.OURO.module, **changes)


def _loop_step_end(m, end):
    """`end(u, normed, each)` -> (what the next loop step reads, what is
    handed back) in the place of the loop step's own end."""
    own = transformer._loop_step_end

    def patched(u, params, cfg, each):
        return end(u, own(u, params, cfg, None)[0], each)

    m.setattr(transformer, "_loop_step_end", patched)


def _last_share_times_its_own_gate(m):
    """p_T = lambda x what is left (the third gate stands in for a fourth,
    which the program does not compute): the four no longer sum to one."""
    shares = transformer._exit_log_shares

    def with_a_gate(gates):
        logp = shares(gates)
        return logp.at[-1].add(jax.nn.log_sigmoid(gates[-1]))

    m.setattr(transformer, "_exit_log_shares", with_a_gate)


def _no_gradient_into_the_gate(m):
    shares = transformer._exit_log_shares
    m.setattr(transformer, "_exit_log_shares",
              lambda gates: shares(jax.lax.stop_gradient(gates)))


def _no_second_norm(m, branch):
    behind = transformer._behind
    m.setattr(transformer, "_behind", lambda y, layer, norm, cfg: (
        y if norm == branch else behind(y, layer, norm, cfg)))


def _bfloat16_logits(m):
    """The head's product in bfloat16, and the logits left so."""
    def low(params, x, cfg, normed=False):
        assert normed and not cfg.tied_head
        return x.astype(jnp.bfloat16) @ params["lm_head"].astype(jnp.bfloat16).T

    m.setattr(transformer, "_head_logits", low)


FAULTS = {
    "three_loop_steps_for_four": _as(loop_steps=3),
    "no_final_norm_between_loop_steps": lambda m: _loop_step_end(
        m, lambda u, normed, each: (u, each(normed))),
    "the_head_on_the_un_normed_state": lambda m: _loop_step_end(
        m, lambda u, normed, each: (normed, each(u))),
    "last_share_as_lambda_times_what_is_left": _last_share_times_its_own_gate,
    "the_entropys_sign_turned": _as(exit_entropy_coef=-2.0),
    "no_second_norm_behind_attention": lambda m: _no_second_norm(
        m, "ln1_post_scale"),
    "no_second_norm_behind_the_feed_forward": lambda m: _no_second_norm(
        m, "ln2_post_scale"),
    "no_gradient_into_the_gate": _no_gradient_into_the_gate,
    "a_loop_steps_weights_detached": lambda m: m.setattr(
        transformer, "_hidden", unrolled_hidden(detached=1)),
}

FAMILY = fc.OURO.with_cases(faults=FAULTS)


def test_bfloat16_logits_fail_the_declared_precision(monkeypatch, fresh_traces):
    """The one fault the numbers cannot see: with the head's product, the
    logits and the cross-entropy's sums in bfloat16 the gradients still read
    under `GRAD_RTOL` off the reference's on this state. The cell's
    `declared_precision` reads it off the traced program
    (`harness.precision_faults`), which is what refuses it."""
    module, config = FAMILY.module, FAMILY.config
    state = jax.eval_shape(lambda: module.init(config, 0))

    def read():
        traced = module.program_loss_and_grads(config).trace(state, FAMILY.sample())
        return harness.precision_faults(config, module.head_width(config),
                                        traced.jaxpr, state, state)

    assert read() == []
    jax.clear_caches()
    _bfloat16_logits(monkeypatch)
    found = read()
    assert found and all("bfloat16" in fault for fault in found)
    _, want = FAMILY.reference()
    _, grads = module.program_loss_and_grads(config)(FAMILY.state(), FAMILY.sample())
    assert harness.relative_error(grads, want) < module.GRAD_RTOL
