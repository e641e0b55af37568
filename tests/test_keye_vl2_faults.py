"""Each mechanism of Keye-VL-2.0-30B-A3B's learned sparse attention knocked
out in turn (PR 61): the float32 program with the fault against the plain
reference on the family's trained-like state (`tests/family_cases.py`); every
fault has to read far over what the bfloat16 program is allowed. A file of its
own so that the suite's workers share the compiles."""

import jax
import jax.numpy as jnp

import family_cases as fc
from family_cases import (  # noqa: F401  the shared case
    pytest_generate_tests, test_a_fault_fails_the_familys_tolerance)
from kungfu_tpu.ops import sparse_attention as dsa

def _patched(name, changed):
    """The fault that puts `changed(the op)` in the place of the op `name`."""
    return lambda m: m.setattr(dsa, name, changed(getattr(dsa, name)))


def _no_relu(scores):
    """I = sum_j w_j (qI_j . kI): the heads' scores as they are."""
    return lambda qI, kI, w: jnp.einsum(
        "btj,btjd,bsd->bts", w, qI, kI, precision=jax.lax.Precision.HIGHEST)


def _the_choice(changed):
    """The fault that puts `changed(the plain choice)` in the place of the
    choice, of both its forms: whichever the model under test runs."""
    def fault(m):
        chosen = changed(dsa.plain_select)
        m.setattr(dsa, "plain_select", chosen)
        m.setattr(dsa, "select", lambda scores, k, interpret=False: chosen(scores, k))

    return fault


def _chosen_above_too(select):
    """The choice over every key, later ones among them."""
    def chosen(scores, k):
        _, taken = jax.lax.top_k(scores, k)
        B, S, _ = scores.shape
        return jnp.zeros(scores.shape, jnp.int8).at[
            jnp.arange(B)[:, None, None], jnp.arange(S)[None, :, None], taken].set(1)

    return chosen


def _the_first_heads_probabilities(plain):
    """p from one head in the place of the heads' mean."""
    return lambda q, k, lse, chosen, sm_scale=None: plain(
        q[:, :1], k[:, :1], lse[:, :1], chosen, sm_scale)


def _a_target_with_a_gradient(kl):
    """The indexer's loss with p as a variable of it: the KL's gradient
    reaches q and k through the core's probabilities."""
    def loss(scores, chosen, p, entropy):
        seen = chosen != 0
        lse = jax.nn.logsumexp(jnp.where(seen, scores, -jnp.inf), axis=-1,
                               keepdims=True)
        return jnp.mean(jnp.sum(jnp.where(
            seen, jax.scipy.special.xlogy(p, p) - p * (scores - lse), 0.0), -1))

    return loss


def _probabilities_with_a_gradient(plain):
    def probs(q, k, lse, chosen, sm_scale=None):
        sm_scale = sm_scale or q.shape[-1] ** -0.5
        kk = jnp.repeat(k, q.shape[1] // k.shape[1], axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kk) * sm_scale
        p = jnp.mean(jnp.where(chosen[:, None] != 0,
                               jnp.exp(s - lse[..., None]), 0.0), axis=1)
        return p, dsa._entropy(p, chosen)

    return probs


def _no_stop_gradients(m):
    m.setattr(dsa, "plain_head_mean_probs", _probabilities_with_a_gradient(None))
    m.setattr(dsa, "indexer_kl", _a_target_with_a_gradient(None))


FAULTS = {
    "no_relu_in_the_scores": _patched("plain_index_scores", _no_relu),
    "the_weights_without_their_scale": _patched(
        "plain_index_scores", lambda scores: lambda qI, kI, w: scores(
            qI, kI, w * (qI.shape[2] * qI.shape[3]) ** 0.5)),
    "half_as_many_keys_chosen": _the_choice(
        lambda select: lambda scores, k: select(scores, k // 2)),
    "the_choice_sees_later_keys": _the_choice(_chosen_above_too),
    "one_heads_probabilities_for_the_mean": _patched(
        "plain_head_mean_probs", _the_first_heads_probabilities),
    "a_target_that_is_no_constant": _no_stop_gradients,
}

FAMILY = fc.KEYE_VL2.with_cases(faults=FAULTS)
