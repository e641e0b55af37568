"""Layers that differ in kind in `models/transformer.py` (PR 33): full and
sliding-window attention with their own head counts over grouped key/value
heads, a per-head output gate, rotary over part of the head with YaRN, a
dense first layer, expert layers that hold a share of the experts their
router sees, renormalised and scaled gates, a shared expert; against the
plain float32 reference `benchmark/reference/laguna.py` at a small size on
the CPU, each mechanism knocked out in turn."""

import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from benchmark import harness, manifest as mf
from benchmark.families import laguna as family
from kungfu_tpu.models import transformer
from kungfu_tpu.models.transformer import init_transformer, param_pspecs
from kungfu_tpu.telemetry import metrics

# five layers as the cell's: full + dense, three sliding + experts, full +
# experts; hidden 64, head size 16, 4 and 6 query heads on 2 key/value heads,
# window 16 of 64 positions, 16 experts of width 32 of which numbers 4 to 7
# are held, 3 a token, a shared expert, vocabulary 256; flash in interpret mode
TINY = dict(hidden_size=64, intermediate_size=96, head_dim=16,
            num_attention_heads=4, num_key_value_heads=2,
            num_attention_heads_per_layer=[4, 6, 6, 6] * 12, sliding_window=16,
            num_experts=4, first_expert_held=4, num_experts_per_tok=3,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            published={"num_experts": 16}, vocab_size=256, sequence_length=64,
            flash_blocks=[16, 16], flash_interpret=True,
            compute_dtype="float32")
SEED = 5


def tiny_config(**changes):
    config = copy.deepcopy(
        mf.cell(mf.load(), "laguna_s_2_1.ssgd_1seq_1chip")["config"])
    config.update(TINY)
    # YaRN over 8 rotated features of 16: the pairs blend between 0 and 1
    config["rope_parameters"]["full_attention"].update(
        original_max_position_embeddings=32, factor=8)
    config.update(changes)
    return config


CONFIG = tiny_config()


def _state(seed=SEED):
    """A state as after some training, so that no fault can hide behind the
    initial values: norms' scales off one, sharp attention (q, k), gates off
    one half, a router with preferences, experts that weigh."""
    state = family.init(CONFIG, seed)
    key = jax.random.PRNGKey(seed + 100)
    scale = {"wq": 6.0, "wk": 6.0, "wv": 3.0, "w_head_gate": 30.0,
             "router": 20.0, "w_gate": 5.0, "w_up": 5.0, "w_down": 5.0,
             "shared_gate": 5.0, "shared_up": 5.0, "shared_down": 5.0}
    stacks = []
    for s, stack in enumerate(state["layers"]):
        stack = {name: leaf * scale.get(name, 1.0) for name, leaf in stack.items()}
        for i, name in enumerate(("ln1_scale", "ln2_scale")):
            stack[name] = 1.0 + 0.3 * jax.random.normal(
                jax.random.fold_in(key, 2 * s + i), stack[name].shape)
        stacks.append(stack)
    return {**state, "layers": tuple(stacks)}


def _sample(n=2):
    return family.host_batch(CONFIG, SEED, 0, n)


def _errors(config, state, sample):
    loss, grads = family.program_loss_and_grads(config)(state, sample)
    want_loss, want = family.reference_loss_and_grads(CONFIG, state, sample)
    return (abs(float(loss) - float(want_loss)) / abs(float(want_loss)),
            harness.relative_error(grads, want), grads, want)


def test_the_stacks_are_the_models_layers_in_order():
    mc = family.model_config(CONFIG)
    kinds = [(kind.n_heads, kind.window, kind.ffn, kind.d_ff, n)
             for kind, n in mc.stacks]
    assert kinds == [(4, 0, "swiglu", 96, 1), (6, 16, "moe", 32, 3),
                     (4, 0, "moe", 32, 1)]
    state = jax.eval_shape(lambda: family.init(CONFIG, 0))
    shapes = [{k: v.shape for k, v in stack.items()} for stack in state["layers"]]
    assert shapes[0]["wq"] == (1, 64, 64) and shapes[1]["wq"] == (3, 64, 96)
    assert shapes[1]["wk"] == (3, 64, 32) and shapes[1]["wo"] == (3, 96, 64)
    assert shapes[1]["w_head_gate"] == (3, 64, 6)
    assert shapes[1]["router"] == (3, 64, 16) and shapes[1]["w_gate"] == (3, 4, 64, 32)
    assert shapes[0]["w_gate"] == (1, 64, 96) and "router" not in shapes[0]
    assert shapes[2]["shared_down"] == (1, 32, 64)
    # the sharding plan names every leaf, stack by stack
    specs = param_pspecs(mc)
    assert jax.tree.structure(
        jax.tree.map(lambda s: 0, specs,
                     is_leaf=lambda s: isinstance(s, PartitionSpec))
    ) == jax.tree.structure(jax.tree.map(lambda s: 0, state))
    assert specs["layers"][1]["wq"] == PartitionSpec(None, None, "tp")
    assert specs["layers"][1]["w_gate"] == PartitionSpec(None, "ep", None, "tp")


def test_float32_program_equals_the_reference():
    state, sample = _state(), _sample()
    loss_error, grad_error, grads, want = _errors(CONFIG, state, sample)
    assert loss_error <= 1e-5 and grad_error <= 2e-5, (loss_error, grad_error)
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        assert float(jnp.abs(g).max()) > 0, jax.tree_util.keystr(path)
    assert family.differing_choices(CONFIG, state, sample) == 0


def test_bfloat16_program_is_within_the_familys_tolerances():
    config = tiny_config(compute_dtype="bfloat16")
    state, sample = family.init(config, SEED), _sample()
    loss_error, grad_error, _, _ = _errors(config, state, sample)
    assert loss_error <= family.LOSS_RTOL, loss_error
    assert 1e-4 < grad_error <= family.GRAD_RTOL, grad_error
    assert 0 < family.LOSS_RTOL < family.GRAD_RTOL < 0.1


def test_the_recomputed_layers_change_no_number():
    """`recomputed_layer_types` says what the backward pass keeps, not what
    it computes."""
    state, sample = _state(), _sample()
    kept = tiny_config(recomputed_layer_types=[])
    loss, grads = family.program_loss_and_grads(CONFIG)(state, sample)
    want_loss, want = family.program_loss_and_grads(kept)(state, sample)
    assert float(loss) == float(want_loss)
    assert harness.relative_error(grads, want) <= 1e-6
    assert [k.layer_remat for k, _ in family.model_config(CONFIG).stacks] == [
        False, True, False]


_model_config = family.model_config


def _changed(cfg, **changes):
    """The family's model configuration with fields replaced: in every
    layer kind that sets them, and in the configuration's own."""
    mc = _model_config(cfg)
    kinds = tuple(tuple((k, changes.get(k, v)) for k, v in kind)
                  for kind in mc.layer_kinds)
    own = {k: v for k, v in changes.items()
           if k not in {name for name, _ in mc.layer_kinds[0]}}
    return dataclasses.replace(mc, layer_kinds=kinds, **own)


def _no_yarn_factor(cfg):
    mc = _model_config(cfg)
    kinds = tuple(tuple((k, v[:4] + (1.0,) if k == "yarn" and v else v)
                        for k, v in kind) for kind in mc.layer_kinds)
    return dataclasses.replace(mc, layer_kinds=kinds)


def _wrong_group(m):
    core_of = transformer.attention_core_of

    def reversed_groups(cfg):
        core = core_of(cfg)
        return lambda q, k, v: core(q, k[:, ::-1], v[:, ::-1])

    m.setattr(transformer, "attention_core_of", reversed_groups)


def _as(make):
    return lambda m: m.setattr(family, "model_config", make)


FAULTS = {
    "eight_bit_operands": lambda m: None,
    "no_window": _as(lambda cfg: _changed(cfg, window=0)),
    "wrong_group": _wrong_group,
    "no_head_gate": _as(lambda cfg: _changed(cfg, head_gate=False)),
    "rotary_over_the_whole_head": _as(lambda cfg: _changed(cfg, rotary_share=1.0)),
    "no_yarn_factor": _as(_no_yarn_factor),
    "gates_not_renormalised": _as(lambda cfg: _changed(cfg, gates="raw")),
    "no_routed_scale": _as(lambda cfg: _changed(cfg, routed_scale=1.0)),
    "no_shared_expert": _as(lambda cfg: _changed(cfg, shared_ff=0)),
}


def _eight_bit(state):
    """Every matrix rounded to float8_e4m3 (3 mantissa bits): what 8-bit
    operands do to the matmuls."""
    return jax.tree.map(
        lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype) if w.ndim >= 2 else w,
        state)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_fails_the_familys_tolerance(fault, monkeypatch):
    """Each in float32 compute, so that nothing but the fault is in the
    error: it has to be far over what the bfloat16 program is allowed."""
    state, sample = _state(), _sample()
    FAULTS[fault](monkeypatch)
    program_state = _eight_bit(state) if fault == "eight_bit_operands" else state
    loss, grads = family.program_loss_and_grads(CONFIG)(program_state, sample)
    want_loss, want = family.reference_loss_and_grads(CONFIG, state, sample)
    error = harness.relative_error(grads, want)
    assert error > 2 * family.GRAD_RTOL, (fault, error)


def test_the_share_routes_over_all_experts_and_drops_nothing():
    """Every token to experts 4, 5 and 6, all of them held: 3 of the
    share's T x min(3, 4) buffer rows a token, none dropped, and loss and
    gradients still the reference's."""
    state, sample = _state(), _sample()
    stacks = []
    for stack in state["layers"]:
        if "router" in stack:
            stack = dict(stack)
            stack["ln2_scale"] = jnp.zeros_like(stack["ln2_scale"]).at[:, 0].set(8.0)
            stack["router"] = jnp.zeros_like(stack["router"]).at[:, 0, 4:7].set(
                jnp.array([3.0, 2.0, 1.0]))
        stacks.append(stack)
    state = {**state, "layers": tuple(stacks)}
    stats = family.routing_stats(CONFIG, state, sample)
    tokens = sample[:, :-1].size
    assert stats["dropped"] == [0, 0, 0, 0] and stats["layer"] == [1, 2, 3, 4]
    counts = np.asarray(stats["counts"])
    assert counts.shape == (4, 4)
    # feature 0 keeps its sign a token: the three lead (all three rows of
    # the token's buffer share are taken), or trail behind the ties; count
    assert (counts[:, :3] == counts[:, :1]).all() and (counts[:, 3] == 0).all()
    assert (counts[:, 0] >= tokens // 4).all(), counts
    assert stats["held_rows"] == counts.sum(axis=1).tolist()
    loss_error, grad_error, _, _ = _errors(CONFIG, state, sample)
    assert loss_error <= 1e-5 and grad_error <= 5e-5, (loss_error, grad_error)


def test_the_shares_counters_reach_the_metrics_registry():
    state, sample = _state(), _sample()
    mc = family.model_config(CONFIG)
    stats = jax.jit(lambda p, t: transformer.routing_stats(p, t, mc))(
        state, sample[:, :-1])
    assert stats["counts"].shape == (4, 4) and stats["chosen"].shape == (4, 128, 3)
    assert stats["dropped"].tolist() == [0, 0, 0, 0]
    # 3 of 16 experts a token, 4 held: a quarter of the choices, about
    assert 0.1 < float(stats["held_rows"].sum()) / (4 * 128 * 3) < 0.4
    registry = metrics.Registry()
    transformer.record_routing(stats, registry)
    text = registry.render()
    assert 'kungfu_moe_dropped_token_choices{layer="4"} 0' in text
    assert 'kungfu_moe_held_rows{layer="1"}' in text
    assert 'kungfu_moe_held_share{layer="2"} 0.' in text
    assert 'kungfu_moe_max_over_mean_load{layer="3"}' in text
    assert 'kungfu_moe_expert_token_choices{layer="1",expert="3"}' in text
    assert 'layer="0"' not in text  # the dense layer routes nothing


def test_a_head_size_of_its_own_needs_no_divisible_width():
    """`head_dim` is the configuration's where it gives one: 6 heads of 16
    on a width of 64."""
    mc = family.model_config(CONFIG)
    kind = mc.stacks[1][0]
    assert kind.head_dim == 16 and kind.n_heads * kind.head_dim == 96 != kind.d_model
    assert kind.kv_heads == 2 and kind.split_qkv
    assert transformer.TransformerConfig.tiny().head_dim == 16
    with pytest.raises(ValueError, match="flash"):
        transformer.TransformerConfig(n_heads=4, n_kv_heads=2)
    with pytest.raises(ValueError, match="no multiple"):
        transformer.TransformerConfig(n_heads=4, n_kv_heads=3, attn_core="flash")
    with pytest.raises(ValueError, match="layer kinds"):
        transformer.TransformerConfig(n_layers=2, layer_kinds=((("window", 0),),))
    with pytest.raises(TypeError):
        transformer.TransformerConfig(n_layers=1, layer_kinds=((("no_such", 0),),))


def test_the_kind_scopes_are_in_the_program():
    """`attn_window` and `attn_full` around `attn_core`, `attn_gate`,
    `moe_shared`: what the cell's per-layer metrics read."""
    state = jax.eval_shape(lambda: family.init(CONFIG, 0))
    text = family.program_loss_and_grads(CONFIG).lower(
        state, _sample()).as_text(debug_info=True)
    for scope in ("attn/attn_window/attn_core", "attn/attn_full/attn_core",
                  "attn/attn_gate", "rope/", "moe/moe_shared",
                  "moe/moe_dispatch", "moe/moe_router", "moe_experts/",
                  "moe_combine/", "cond/", "ffn"):
        assert scope in text, scope


def test_a_layer_run_again_runs_its_forward_kernel_once():
    """`layer_remat` keeps the flash core's output and row sums by name, so
    the program holds a forward kernel for each stack and two backward
    ones, and no fourth for the sliding stack; with nothing kept by name
    the scan's backward pass holds the forward kernel again."""
    state = jax.eval_shape(lambda: family.init(CONFIG, 0))

    def kernels(mc):
        loss = lambda p, b: transformer.transformer_loss(p, b, mc)
        text = str(jax.make_jaxpr(jax.grad(loss))(state, _sample()))
        # the flash kernels: the rotary passes (PR 35) are kernels too
        return text.count("pallas_call") - text.count("name=rotary")

    assert kernels(family.model_config(CONFIG)) == 3 * 3
    plain = jax.checkpoint(transformer._layer, static_argnums=(2,))
    with pytest.MonkeyPatch.context() as m:
        m.setattr(transformer, "_layer_again", plain)
        assert kernels(family.model_config(CONFIG)) == 3 * 3 + 1
