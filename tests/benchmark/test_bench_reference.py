"""The plain float32 references (`benchmark/reference/`) against the
program (`kungfu_tpu.models`) at tiny widths on the CPU: loss and gradients
agree to rounding when the program computes in float32, and within the
family's own tolerance when it computes in bfloat16. What those numbers
cannot see, a lower precision than the configuration states in the head, the
loss, the parameters or the optimizer's state, `precision_faults` reads
off the program."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, manifest as mf
from benchmark.families import resnet, transformer

TINY = {
    "transformer": (transformer, {
        "family": "transformer", "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "intermediate_size": 128, "vocab_size": 256,
        "max_position_embeddings": 64, "param_dtype": "float32",
        "head_dtype": "float32"}),
    "resnet": (resnet, {
        "family": "resnet", "stage_sizes": [1, 2], "num_filters": 8,
        "bottleneck_expansion": 4, "num_classes": 10, "image_size": 32,
        "image_channels": 3, "label_classes_used": 10,
        "param_dtype": "float32", "head_dtype": "float32"}),
}


def _both(name, dtype, seed=5):
    family, config = TINY[name]
    config = dict(config, compute_dtype=dtype)
    state = family.init(config, seed)
    sample = family.host_batch(config, seed, 0, family.REFERENCE_SAMPLES)
    got = family.program_loss_and_grads(config)(state, sample)
    want = family.reference_loss_and_grads(config, state, sample)
    return got, want


@pytest.mark.parametrize("name", sorted(TINY))
def test_reference_equals_program_in_float32(name):
    (loss, grads), (ref_loss, ref_grads) = _both(name, "float32")
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * abs(float(ref_loss))
    assert harness.relative_error(grads, ref_grads) <= 1e-4
    # and leaf by leaf: same tree, same shapes
    assert jax.tree.structure(grads) == jax.tree.structure(ref_grads)
    for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        assert g.shape == r.shape
        np.testing.assert_allclose(g, r, rtol=0, atol=2e-4 * float(abs(r).max()) + 1e-8)


@pytest.mark.parametrize("name", sorted(TINY))
def test_bfloat16_program_is_within_the_loss_tolerance(name):
    (loss, grads), (ref_loss, ref_grads) = _both(name, "bfloat16")
    family = TINY[name][0]
    assert abs(float(loss) - float(ref_loss)) <= family.LOSS_RTOL * abs(float(ref_loss))
    error = harness.relative_error(grads, ref_grads)
    # rounded, so not equal; and not so far off that the tolerance is slack
    assert 1e-4 < error < 0.3, error


def test_transformer_gradient_tolerance_holds_at_tiny_size():
    (_, grads), (_, ref_grads) = _both("transformer", "bfloat16")
    assert harness.relative_error(grads, ref_grads) <= transformer.GRAD_RTOL


def test_a_wrong_program_fails_the_check():
    """The comparison is tight enough to see a fault: a reference fed other
    parameters than the program's does not pass as equal."""
    family, config = TINY["transformer"]
    config = dict(config, compute_dtype="float32")
    sample = family.host_batch(config, 5, 0, 2)
    _, grads = family.program_loss_and_grads(config)(family.init(config, 5), sample)
    _, other = family.reference_loss_and_grads(config, family.init(config, 6), sample)
    assert harness.relative_error(grads, other) > transformer.GRAD_RTOL


def test_relative_error_by_hand():
    got = {"a": np.array([3.0, 0.0], np.float32), "b": np.array([0.0], np.float32)}
    want = {"a": np.array([0.0, 0.0], np.float32), "b": np.array([4.0], np.float32)}
    # |got - want| = sqrt(9 + 16) = 5, |want| = 4
    assert harness.relative_error(
        jax.tree.map(jax.numpy.asarray, got),
        jax.tree.map(jax.numpy.asarray, want)) == pytest.approx(1.25)


def _opt_state(family, state):
    import optax

    return optax.adamw(1e-3).init(family.trainable(state))


@pytest.mark.parametrize("name", sorted(TINY))
def test_reference_check_record(name):
    family, config = TINY[name]
    config = dict(config, compute_dtype="float32")
    state = family.init(config, 3)
    out = harness.reference_check(family, config, 3, state,
                                  _opt_state(family, state))
    assert out["loss_error"] <= 1e-5 and out["grad_error"] <= 1e-4
    assert out["loss_rtol"] == family.LOSS_RTOL
    assert out["grad_rtol"] == family.GRAD_RTOL
    assert out["precision_faults"] == []


def _head_variant(config, head_dtype, loss_dtype):
    """The transformer's loss with its head matmul and its softmax in
    other types than the program's float32."""
    from kungfu_tpu.models.transformer import _rmsnorm, transformer_hidden

    mc = transformer.model_config(config)

    def loss(params, batch):
        tokens, targets = batch[:, :-1], batch[:, 1:]
        h = _rmsnorm(transformer_hidden(params, tokens, mc), params["ln_f_scale"])
        logits = h.astype(head_dtype) @ params["embed"].astype(head_dtype).T
        logp = jax.nn.log_softmax(logits.astype(loss_dtype))
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return -jnp.mean(ll.astype(jnp.float32))

    return jax.jit(jax.value_and_grad(loss))


def _transformer_faults(program, state=None, opt_state=None):
    family, config = TINY["transformer"]
    config = dict(config, compute_dtype="bfloat16")
    init = family.init(config, 5)
    sample = family.host_batch(config, 5, 0, 2)
    state = init if state is None else state
    opt_state = _opt_state(family, state) if opt_state is None else opt_state
    traced = (program or family.program_loss_and_grads)(config).trace(init, sample)
    return harness.precision_faults(config, family.head_width(config),
                                    traced.jaxpr, state, opt_state), traced, init, sample


def test_the_program_holds_to_its_declared_precision():
    faults, *_ = _transformer_faults(None)
    assert faults == []


def test_a_bfloat16_head_fails_the_check():
    """The numbers do not see it (the logits are small at the initial
    parameters), the program's text does."""
    family, _ = TINY["transformer"]
    faults, traced, init, sample = _transformer_faults(
        lambda c: _head_variant(c, jnp.bfloat16, jnp.float32))
    assert faults and all("dot_general over the head's width" in f for f in faults)
    assert "bfloat16" in faults[0]
    # forward logits and both of the head's matmuls in the backward pass
    assert len(faults) == 3
    loss, grads = traced.lower().compile()(init, sample)
    ref_loss, ref_grads = family.reference_loss_and_grads(
        dict(TINY["transformer"][1]), init, sample)
    assert abs(float(loss) - float(ref_loss)) <= family.LOSS_RTOL * abs(float(ref_loss))
    assert harness.relative_error(grads, ref_grads) <= family.GRAD_RTOL


def test_a_bfloat16_loss_fails_the_check():
    faults, *_ = _transformer_faults(
        lambda c: _head_variant(c, jnp.float32, jnp.bfloat16))
    assert any(f.startswith("reduce_") and "bfloat16" in f for f in faults), faults


@pytest.mark.parametrize("what", ["state", "optimizer state"])
def test_bfloat16_parameters_or_optimizer_state_fail_the_check(what):
    family, config = TINY["transformer"]
    state = family.init(dict(config, compute_dtype="bfloat16"), 5)
    low = jax.tree.map(lambda x: x.astype(jnp.bfloat16), state)
    if what == "state":
        faults, *_ = _transformer_faults(None, state=low,
                                         opt_state=_opt_state(family, state))
    else:
        faults, *_ = _transformer_faults(None, state=state,
                                         opt_state=_opt_state(family, low))
    assert faults and all(f.startswith(what + "[") and "bfloat16" in f
                          for f in faults), faults
    assert len(faults) >= len(jax.tree.leaves(state))


@pytest.mark.parametrize("workload", ["bert_base.ssgd_1chip", "resnet50.ssgd_1chip"])
def test_the_cells_programs_hold_to_their_declared_precision(workload):
    """At the real sizes, traced and not run: what the chip's check reads."""
    cell = mf.cell(mf.load(), workload)
    config, traffic = cell["config"], cell["traffic"]
    family = harness.family_of(config)
    from kungfu_tpu.parallel import make_mesh

    state = jax.eval_shape(lambda: family.init(config, 0))
    _, init_opt_state = mf.plugin("steps", traffic["step"]).build(
        family, config, traffic, make_mesh({"dp": 1}, devices=jax.devices()[:1]))
    opt_state = jax.eval_shape(init_opt_state, state)
    sample = family.host_batch(config, 0, 0, family.REFERENCE_SAMPLES)
    traced = family.program_loss_and_grads(config).trace(state, sample)
    assert harness.precision_faults(config, family.head_width(config),
                                    traced.jaxpr, state, opt_state) == []
    # and the check looked at something: the head's matmuls are in there
    width = family.head_width(config)
    dots = [e for e in harness.eqns_of(traced.jaxpr.jaxpr)
            if e.primitive.name == "dot_general"
            and any(width in v.aval.shape for v in (*e.invars, *e.outvars))]
    assert len(dots) >= 3
