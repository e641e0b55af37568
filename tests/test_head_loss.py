"""The cross-entropy of `models/transformer.lm_head_loss` (PERF.md, PR 31):
`mean(logsumexp(logits) - logits[target])` through a `custom_vjp` that keeps
the logits, their per-row log-sum-exp and the targets. The values are those
of the expression it replaced, written out here; the backward pass reads one
array of the logits' size and no more; the benchmark's precision check still
sees into it; and the ring and the pipeline path, which share it, read the
dense path's values."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, manifest as mf
from benchmark.families import olmoe as olmoe_family
from benchmark.families import transformer as transformer_family
from kungfu_tpu.models import transformer
from kungfu_tpu.models.transformer import (TransformerConfig, init_transformer,
                                           lm_head_loss,
                                           make_ring_transformer_loss,
                                           transformer_loss)
from kungfu_tpu.parallel import make_mesh
from kungfu_tpu.parallel.pipeline import make_pp_transformer_loss

V, D = 97, 16  # a vocabulary that no tile divides


def _cfg(tied):
    return TransformerConfig(vocab_size=V, d_model=D, n_heads=2, n_layers=2,
                             d_ff=32, max_seq=16, dtype=jnp.float32,
                             tied_head=tied)


def _log_softmax_loss(params, x, targets, cfg):
    """`lm_head_loss` as it was before PR 31."""
    logp = jax.nn.log_softmax(transformer._head_logits(params, x, cfg))
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.mean(ll)


def _head_inputs(cfg, rows, magnitude, seed=3):
    """Parameters and hidden states whose logits have about `magnitude`
    as their standard deviation: the rows are normed, so the head's scale
    sets it."""
    kp, kx, kt, ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    params = init_transformer(kp, cfg)
    head = "embed" if cfg.tied_head else "lm_head"
    params[head] = magnitude / np.sqrt(D) * jax.random.normal(kp, (V, D))
    params["ln_f_scale"] = 1.0 + 0.1 * jax.random.normal(ks, (D,))
    x = jax.random.normal(kx, (*rows, D), jnp.float32)
    targets = jax.random.randint(kt, rows, 0, V)
    return params, x, targets


@pytest.mark.parametrize("magnitude", [1e-2, 50.0])
@pytest.mark.parametrize("rows", [(3, 7), (11,)], ids=["BSD", "SD"])
@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_loss_and_gradients_are_the_log_softmax_forms(tied, rows, magnitude):
    cfg = _cfg(tied)
    params, x, targets = _head_inputs(cfg, rows, magnitude)
    head = "embed" if tied else "lm_head"

    def of(loss_fn):
        def f(x, scale, w):
            return loss_fn({**params, "ln_f_scale": scale, head: w}, x,
                           targets, cfg)
        return jax.value_and_grad(f, argnums=(0, 1, 2))(
            x, params["ln_f_scale"], params[head])

    loss, grads = of(lm_head_loss)
    want_loss, want_grads = of(_log_softmax_loss)
    # float32 rounding: `logits - lse` is exact to an ulp of the logits,
    # 3.8e-6 at 50, and the softmax is its exponential
    tol = 1e-6 * max(1.0, magnitude / 5)
    assert loss.dtype == jnp.float32 and np.isfinite(float(loss))
    np.testing.assert_allclose(loss, want_loss, rtol=tol)
    if magnitude > 1:  # far from uniform: the shift by the maximum matters
        assert float(loss) > 10.0
    for got, want in zip(grads, want_grads):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.all(np.isfinite(got))
        norm = float(jnp.linalg.norm(want))
        assert norm > 0
        assert float(jnp.linalg.norm(got - want)) <= tol * norm


@pytest.mark.parametrize("g", [1.0, 0.0, -2.5])
def test_the_cotangent_scales_the_gradient(g):
    """The pipeline takes the loss under a `jnp.where` on every stage, so
    the backward pass sees cotangents other than one."""
    cfg = _cfg(True)
    params, x, targets = _head_inputs(cfg, (5, 3), 1.0)
    _, pull = jax.vjp(lambda x: lm_head_loss(params, x, targets, cfg), x)
    _, want_pull = jax.vjp(
        lambda x: _log_softmax_loss(params, x, targets, cfg), x)
    np.testing.assert_allclose(pull(jnp.float32(g))[0],
                               want_pull(jnp.float32(g))[0],
                               rtol=1e-5, atol=1e-8)


# --- (b) the residual account ------------------------------------------------

def _kept_of_logits_size(loss_fn, cfg, rows):
    """(aval, the primitive that made it) of every floating array that
    `loss_fn`'s forward pass hands its backward pass (the outputs of
    `jax.vjp` after the loss, as its jaxpr has them) with the vocabulary
    as one dimension and the row count as another."""
    params, x, targets = _head_inputs(cfg, rows, 1.0)
    jaxpr = jax.make_jaxpr(lambda p, x: jax.vjp(
        lambda p, x: loss_fn(p, x, targets, cfg), p, x))(params, x).jaxpr
    made_by = {id(v): e.primitive.name for e in jaxpr.eqns for v in e.outvars}
    return [(v.aval, made_by.get(id(v), "argument"))
            for v in {id(v): v for v in jaxpr.outvars[1:]}.values()
            if V in v.aval.shape and rows[-1] in v.aval.shape
            and jnp.issubdtype(v.aval.dtype, jnp.floating)]


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_the_backward_pass_keeps_one_array_of_the_logits_size(tied):
    """And that one is the head's matmul's own output, which is written
    whatever the loss keeps."""
    rows = (5, 13)  # neither is V, D or the other
    ((logits, made_by),) = _kept_of_logits_size(lm_head_loss, _cfg(tied), rows)
    assert logits.shape == (*rows, V) and logits.dtype == jnp.float32
    assert made_by == "dot_general"


def _plain_loss(params, x, targets, cfg):
    """The same expression with no `custom_vjp` around it."""
    logits = transformer._head_logits(params, x, cfg)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


@pytest.mark.parametrize("loss_fn", [_log_softmax_loss, _plain_loss])
def test_the_residual_account_sees_a_second_array(loss_fn):
    """The account is not blind: autodiff of `log_softmax`, and of the
    plain `logsumexp` expression, keeps exp(logits - max), an array of the
    logits' size beside the logits that the forward pass has to write (why
    `_xent` is a `custom_vjp`)."""
    kept = _kept_of_logits_size(loss_fn, _cfg(True), (5, 13))
    assert kept and any(made_by != "dot_general" for _, made_by in kept)


def test_no_scatter_in_the_backward_pass():
    """The picked entries' cotangent is an iota comparison inside the
    elementwise expression, not a scatter into an array of zeros."""
    cfg = _cfg(True)
    params, x, targets = _head_inputs(cfg, (5, 13), 1.0)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda x: lm_head_loss(params, x, targets, cfg)))(x)
    names = {e.primitive.name for e in harness.eqns_of(jaxpr.jaxpr)}
    assert not {n for n in names if n.startswith("scatter")}, names
    assert "iota" in names


# --- (c) the benchmark's precision check sees into the custom_vjp -----------

def _tiny_bert():
    return transformer_family, {
        "family": "transformer", "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "intermediate_size": 128, "vocab_size": 256,
        "max_position_embeddings": 64, "param_dtype": "float32",
        "head_dtype": "float32", "compute_dtype": "bfloat16"}


def _tiny_olmoe():
    real = mf.cell(mf.load(), "olmoe_1b_7b.ssgd_seq4096_1chip")["config"]
    return olmoe_family, {
        **real, "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "intermediate_size": 32, "num_experts": 8, "num_experts_per_tok": 3,
        "vocab_size": 256, "max_position_embeddings": 64,
        "flash_blocks": [32, 32], "flash_interpret": True}


def _faults(family, config):
    state = family.init(config, 5)
    sample = family.host_batch(config, 5, 0, 2)
    traced = family.program_loss_and_grads(config).trace(state, sample)
    return harness.precision_faults(config, family.head_width(config),
                                    traced.jaxpr, state, state)


@pytest.mark.parametrize("tiny", [_tiny_bert, _tiny_olmoe],
                         ids=["bert_base", "olmoe"])
def test_the_program_holds_to_its_declared_precision(tiny):
    assert _faults(*tiny()) == []


@pytest.mark.parametrize("tiny", [_tiny_bert, _tiny_olmoe],
                         ids=["bert_base", "olmoe"])
def test_bfloat16_logits_fail_the_precision_check(tiny, monkeypatch):
    """The row maxima and the sum of exponentials sit inside the
    `custom_vjp`; the check reads them there."""
    real = transformer._head_logits
    monkeypatch.setattr(
        transformer, "_head_logits",
        lambda *args: real(*args).astype(jnp.bfloat16))
    faults = _faults(*tiny())
    assert any(f.startswith("reduce_") and "bfloat16" in f for f in faults), faults


# --- (d) the paths that share the loss --------------------------------------

PATH_CFG = dataclasses.replace(_cfg(True), n_layers=4)  # two stages of two


def _path(name):
    if name == "pipeline":
        mesh = make_mesh({"pp": 2}, devices=jax.devices()[:2])
        return make_pp_transformer_loss(PATH_CFG, mesh, n_micro=2)
    mesh = make_mesh({"dp": 1, "sp": 2}, devices=jax.devices()[:2])
    return make_ring_transformer_loss(PATH_CFG, mesh)


@pytest.mark.parametrize("name", ["ring", "pipeline"])
def test_the_sharded_paths_read_the_dense_paths_values(name):
    params = init_transformer(jax.random.PRNGKey(0), PATH_CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (4, 16), 0, V)
    targets = jax.random.randint(jax.random.PRNGKey(8), (4, 16), 0, V)
    want_loss, want = jax.value_and_grad(functools.partial(
        transformer_loss, cfg=PATH_CFG))(params, (tokens, targets))
    loss, got = jax.jit(jax.value_and_grad(_path(name)))(
        params, (tokens, targets))
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-6,
                                   err_msg=jax.tree_util.keystr(path))
