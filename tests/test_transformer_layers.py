"""The layer of `models/transformer.py` as data (PR 27): rotary positions,
q/k norm, a gated-silu feed-forward or routed experts in its place, an
untied head and the flash core by configuration, against the plain float32
reference `benchmark/reference/olmoe.py` at a small size on the CPU; and the
defaults still the block the repo always had, value for value."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from benchmark import harness
from benchmark.families import olmoe as family
from kungfu_tpu.models import transformer
from kungfu_tpu.models.mixers import attention
from kungfu_tpu.models.transformer import (TransformerConfig, init_transformer,
                                           param_pspecs, transformer_loss)
from kungfu_tpu.ops import moe
from kungfu_tpu.telemetry import device, metrics

# every mechanism on: 2 layers, hidden 64, 4 heads x 16, 8 experts of width
# 32, 3 a token, vocabulary 256, 64 positions, flash in interpret mode
CONFIG = {
    "family": "olmoe", "attention_bias": False, "clip_qkv": None,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 32,
    "max_position_embeddings": 64, "norm_topk_prob": False,
    "num_attention_heads": 4, "num_experts": 8, "num_experts_per_tok": 3,
    "num_hidden_layers": 2, "num_key_value_heads": 4, "rms_norm_eps": 1e-5,
    "rope_scaling": None, "rope_theta": 10000, "tie_word_embeddings": False,
    "vocab_size": 256, "router_aux_loss_coef": 0.01,
    "router_z_loss_coef": 0.001, "attention_core": "flash",
    "flash_blocks": [32, 32], "flash_interpret": True,
    "param_dtype": "float32", "compute_dtype": "float32",
    "head_dtype": "float32",
}
SEED = 5


def _state(seed=SEED):
    state = family.init(CONFIG, seed)
    # a state as after some training, so that no fault can hide behind the
    # initial values: norms' scales off one, a router with preferences, and
    # experts whose output weighs what the attention's does (at normal(0,
    # 0.02) three matrices in a row put out a thousandth of their input)
    key = jax.random.PRNGKey(seed + 100)
    layers = dict(state["layers"])
    for i, name in enumerate(("q_norm_scale", "k_norm_scale", "ln1_scale",
                              "ln2_scale")):
        layers[name] = 1.0 + 0.3 * jax.random.normal(
            jax.random.fold_in(key, i), layers[name].shape)
    layers["router"] = layers["router"] * 20.0
    for name in ("w_gate", "w_up", "w_down"):
        layers[name] = layers[name] * 5.0
    return {**state, "layers": layers}


def _sample(n=2):
    return family.host_batch(CONFIG, SEED, 0, n)


def _errors(config, state, sample):
    loss, grads = family.program_loss_and_grads(config)(state, sample)
    want_loss, want = family.reference_loss_and_grads(CONFIG, state, sample)
    return (abs(float(loss) - float(want_loss)) / abs(float(want_loss)),
            harness.relative_error(grads, want), grads, want)


def test_float32_program_equals_the_reference():
    state, sample = _state(), _sample()
    loss_error, grad_error, grads, want = _errors(CONFIG, state, sample)
    assert loss_error <= 1e-5 and grad_error <= 1e-5, (loss_error, grad_error)
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        assert float(jnp.abs(g).max()) > 0, jax.tree_util.keystr(path)
    assert family.differing_choices(CONFIG, state, sample) == 0


def test_bfloat16_program_is_within_the_familys_tolerances():
    config = dict(CONFIG, compute_dtype="bfloat16")
    state, sample = family.init(config, SEED), _sample()
    loss_error, grad_error, _, _ = _errors(config, state, sample)
    assert loss_error <= family.LOSS_RTOL, loss_error
    assert 1e-4 < grad_error <= family.GRAD_RTOL, grad_error
    assert 0 < family.LOSS_RTOL < family.GRAD_RTOL < 0.1


def _eight_bit(state):
    """Every matrix rounded to float8_e4m3 (3 mantissa bits): what 8-bit
    operands do to the matmuls."""
    return jax.tree.map(
        lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype) if w.ndim >= 2 else w,
        state)


def _capacity(factor):
    """The expert function behind a capacity: a token-choice beyond
    `factor` x the mean load of its expert is dropped."""
    swiglu_experts = moe.swiglu_experts

    def experts(rows, weights, group_sizes):
        limit = int(factor * rows.shape[0] / group_sizes.shape[0])
        starts = jnp.cumsum(group_sizes) - group_sizes
        expert = jnp.repeat(jnp.arange(group_sizes.shape[0]), group_sizes,
                            total_repeat_length=rows.shape[0])
        kept = jnp.arange(rows.shape[0]) - starts[expert] < limit
        return jnp.where(kept[:, None],
                         swiglu_experts(rows, weights, group_sizes), 0)
    return experts


def _no_rope(q, k, *rule):
    return q, k


FAULTS = {
    "eight_bit_operands": lambda m: None,
    "renormalised_gate": lambda m: m.setattr(moe, "raw_gates", moe.switch_gates),
    "missing_qk_norm": lambda m: m.setattr(
        family, "model_config", functools.partial(_changed, qk_norm=False)),
    "dropped_token_choices": lambda m: m.setattr(
        moe, "swiglu_experts", _capacity(1.25)),
    "missing_rope": lambda m: m.setattr(attention, "_rope", _no_rope),
}
_model_config = family.model_config


def _changed(cfg, **changes):
    return dataclasses.replace(_model_config(cfg), **changes)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_fails_the_familys_tolerance(fault, monkeypatch):
    """Each in float32 compute, so that nothing but the fault is in the
    error: it has to exceed what the bfloat16 program is allowed."""
    state, sample = _state(), _sample()
    FAULTS[fault](monkeypatch)
    program_state = _eight_bit(state) if fault == "eight_bit_operands" else state
    loss, grads = family.program_loss_and_grads(CONFIG)(program_state, sample)
    want_loss, want = family.reference_loss_and_grads(CONFIG, state, sample)
    error = harness.relative_error(grads, want)
    assert error > family.GRAD_RTOL, (fault, error)


def test_an_adversarial_router_loses_no_token_choice(monkeypatch):
    """Every token to the same 3 experts: 8/3 of the mean load on each and
    nothing on the other 5. No token-choice is dropped, and loss and
    gradients are still the reference's."""
    state, sample = _state(), _sample()
    router = jnp.zeros_like(state["layers"]["router"])
    # after the norm every token has mean square 1: a bias-like column of
    # ln2's scale would need a constant feature, so skew through the scale
    layers = dict(state["layers"])
    layers["ln2_scale"] = jnp.zeros_like(layers["ln2_scale"]).at[:, 0].set(8.0)
    layers["router"] = router.at[:, 0, :3].set(jnp.array([3.0, 2.0, 1.0]))
    state = {**state, "layers": layers}
    # feature 0 of the normed token keeps its sign a token, so the same
    # three lead by |x| or trail by it; count rather than assume
    stats = family.routing_stats(CONFIG, state, sample)
    tokens = sample[:, :-1].size
    assert stats["dropped"] == [0, 0]
    counts = np.asarray(stats["counts"])
    assert counts.sum(axis=1).tolist() == [tokens * 3] * 2
    assert (np.sort(counts, axis=1)[:, -1] >= tokens // 2).all(), counts
    loss_error, grad_error, _, _ = _errors(CONFIG, state, sample)
    assert loss_error <= 1e-5 and grad_error <= 2e-5, (loss_error, grad_error)


def test_routing_counters_reach_the_metrics_registry():
    state, sample = _state(), _sample()
    mc = family.model_config(CONFIG)
    stats = jax.jit(lambda p, t: transformer.routing_stats(p, t, mc))(
        state, sample[:, :-1])
    assert stats["counts"].shape == (2, 8)
    assert stats["dropped"].tolist() == [0, 0]
    assert (np.asarray(stats["max_over_mean"]) >= 1.0).all()
    registry = metrics.Registry()
    transformer.record_routing(stats, registry)
    text = registry.render()
    assert 'kungfu_moe_dropped_token_choices{layer="1"} 0' in text
    assert 'kungfu_moe_max_over_mean_load{layer="0"}' in text
    assert 'kungfu_moe_expert_token_choices{layer="0",expert="7"}' in text
    with pytest.raises(ValueError, match="no expert layer"):
        transformer.routing_stats(
            init_transformer(jax.random.PRNGKey(0), TransformerConfig.tiny()),
            sample[:, :-1], TransformerConfig.tiny())


def test_the_flash_core_runs_once_a_layer_under_value_and_grad(monkeypatch):
    """`tests/test_transformer_remat.py`'s method: counted where the program
    runs. The flash core keeps (q, k, v, o, lse) and no checkpoint around
    it runs its forward kernel a second time."""
    import importlib

    fa = importlib.import_module("kungfu_tpu.ops.flash_attention")

    calls = {}

    def counted(key, fn):
        def run(*args, **kwargs):
            jax.debug.callback(
                lambda _: calls.update({key: calls.get(key, 0) + 1}),
                args[0].ravel()[0])
            return fn(*args, **kwargs)
        return run

    monkeypatch.setattr(fa, "_forward", counted("fwd", fa._forward))
    monkeypatch.setattr(fa, "_backward_kernels",
                        counted("bwd", fa._backward_kernels))
    mc = family.model_config(CONFIG)
    value, grads = jax.jit(jax.value_and_grad(
        functools.partial(transformer_loss, cfg=mc)))(_state(), _sample())
    jax.block_until_ready(grads)
    jax.effects_barrier()
    assert np.isfinite(float(value))
    assert calls == {"fwd": mc.n_layers, "bwd": mc.n_layers}


def test_what_the_expert_layer_saves_for_the_backward_pass():
    """Rows, gate and up pre-activations, the expert outputs before the
    gates weigh them, the routing's small arrays: no silu, no product, no
    float32 (tokens x features) array."""
    mc = family.model_config(dict(CONFIG, compute_dtype="bfloat16"))
    params = jax.eval_shape(lambda: init_transformer(jax.random.PRNGKey(0), mc))
    batch = jax.ShapeDtypeStruct((2, 65), jnp.int32)
    saved = device.saved_bytes(functools.partial(transformer_loss, cfg=mc),
                               params, batch)
    choices, d, f = 2 * 64 * 3, 64, 32
    wide = [s for s in saved if s[0][-2:] in ((choices, d), (choices, f))]
    assert wide and all(s[1] == "bfloat16" for s in wide), saved
    # rows, y and the two pre-activations: at most four of them
    assert len(wide) <= 4, wide


KINDS = {
    "gelu": TransformerConfig.tiny(),
    "swiglu": TransformerConfig(vocab_size=256, d_model=64, n_heads=4,
                                n_layers=2, d_ff=128, max_seq=64, ffn="swiglu",
                                positions="rope", tied_head=False),
    "moe": TransformerConfig.tiny_moe(),
    "qk_norm_learned": TransformerConfig(vocab_size=256, d_model=64, n_heads=4,
                                         n_layers=2, d_ff=128, max_seq=64,
                                         qk_norm=True),
}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_param_pspecs_match_the_parameter_tree(kind):
    cfg = KINDS[kind]
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    specs = param_pspecs(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda s: isinstance(s, PartitionSpec))
    flat = dict(jax.tree_util.tree_leaves_with_path(
        specs, is_leaf=lambda s: isinstance(s, PartitionSpec)))
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        assert len(flat[path]) <= leaf.ndim, jax.tree_util.keystr(path)
    if kind == "moe":
        assert specs["layers"]["w_gate"] == PartitionSpec(None, "ep", None, "tp")
        assert "pos_embed" not in specs and "lm_head" in specs


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_every_layer_kind_trains(kind):
    cfg = KINDS[kind]
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    batch = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0, 256)
    loss, grads = jax.jit(jax.value_and_grad(
        functools.partial(transformer_loss, cfg=cfg)))(params, batch)
    assert np.isfinite(float(loss))
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        assert np.isfinite(np.asarray(g)).all() and float(jnp.abs(g).max()) > 0, \
            jax.tree_util.keystr(path)


@pytest.mark.parametrize("field,value", [("positions", "alibi"), ("ffn", "relu"),
                                         ("attn_core", "sparse")])
def test_an_unknown_layer_kind_is_refused(field, value):
    with pytest.raises(ValueError, match=field):
        TransformerConfig(**{field: value})


def test_an_expert_layer_needs_its_experts():
    with pytest.raises(ValueError, match="top_k"):
        TransformerConfig(ffn="moe", n_experts=4, top_k=5)


def test_the_ring_path_refuses_a_rotary_configuration():
    cfg = KINDS["swiglu"]
    with pytest.raises(NotImplementedError, match="rotary"):
        transformer.ring_transformer_apply_shard(
            {}, jnp.zeros((1, 8), jnp.int32), cfg, "sp", 2)


def test_olmoe_preset_is_the_published_configuration():
    cfg = TransformerConfig.olmoe_1b_7b()
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.n_layers) == (2048, 16, 128, 16)
    assert (cfg.n_experts, cfg.top_k, cfg.d_ff) == (64, 8, 1024)
    assert (cfg.vocab_size, cfg.max_seq, cfg.norm_eps) == (50304, 4096, 1e-5)
    params = jax.eval_shape(lambda: init_transformer(
        jax.random.PRNGKey(0), TransformerConfig.olmoe_1b_7b(n_layers=1)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) == 625_616_896


# --- the defaults are the block the repo always had ------------------------

# `tiny()` at PRNGKey(0) on a PRNGKey(1) batch of (2, 65) ids, read off the
# parent commit (0c23b51): the loss, and sum |.| of every leaf and gradient
PARENT_LOSS = 5.546051502227783
PARENT_TINY = {
    "embed": ((256, 64), 264.0554504394531, 60.943580627441406),
    "layers/ln1_scale": ((2, 64), 128.0, 0.039275169372558594),
    "layers/ln2_scale": ((2, 64), 128.0, 0.0847632884979248),
    "layers/w_in": ((2, 64, 128), 261.6441650390625, 49.44009780883789),
    "layers/w_out": ((2, 128, 64), 259.341064453125, 49.57410430908203),
    "layers/wo": ((2, 64, 64), 131.84141540527344, 16.94567108154297),
    "layers/wqkv": ((2, 64, 192), 391.47833251953125, 17.34610366821289),
    "ln_f_scale": ((64,), 64.0, 0.08687973022460938),
    "pos_embed": ((64, 64), 66.59134674072266, 25.29524040222168),
}


def test_tiny_is_value_for_value_what_the_parent_computed():
    cfg = TransformerConfig.tiny()
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    batch = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0, 256)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: transformer_loss(p, batch, cfg)))(params)
    assert float(loss) == pytest.approx(PARENT_LOSS, rel=1e-6)
    named = lambda tree: {"/".join(k.key for k in path): leaf for path, leaf
                          in jax.tree_util.tree_leaves_with_path(tree)}
    params, grads = named(params), named(grads)
    assert sorted(params) == sorted(PARENT_TINY)
    for name, (shape, weight, grad) in PARENT_TINY.items():
        assert params[name].shape == shape
        assert float(jnp.sum(jnp.abs(params[name]))) == pytest.approx(weight, rel=1e-6)
        assert float(jnp.sum(jnp.abs(grads[name]))) == pytest.approx(grad, rel=1e-4)


def test_bert_base_keeps_its_parameter_tree():
    cfg = TransformerConfig.bert_base()
    assert dataclasses_defaults(cfg)
    params = jax.eval_shape(lambda: init_transformer(jax.random.PRNGKey(0), cfg))
    shapes = {"/".join(k.key for k in path): leaf.shape for path, leaf
              in jax.tree_util.tree_leaves_with_path(params)}
    assert shapes == {
        "embed": (30522, 768), "pos_embed": (512, 768), "ln_f_scale": (768,),
        "layers/ln1_scale": (12, 768), "layers/ln2_scale": (12, 768),
        "layers/wqkv": (12, 768, 2304), "layers/wo": (12, 768, 768),
        "layers/w_in": (12, 768, 3072), "layers/w_out": (12, 3072, 768)}


def dataclasses_defaults(cfg) -> bool:
    """`bert_base()` sets the seven fields it always set and leaves what
    the layer is at the defaults."""
    plain = TransformerConfig()
    return all(getattr(cfg, f) == getattr(plain, f) for f in (
        "positions", "qk_norm", "norm_eps", "ffn", "n_experts", "top_k",
        "tied_head", "attn_core", "router_aux_coef", "router_z_coef"))
