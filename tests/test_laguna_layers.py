"""Layers that differ in kind in `models/transformer.py` (PR 33): full and
sliding-window attention with their own head counts over grouped key/value
heads, a per-head output gate, rotary over part of the head with YaRN, a
dense first layer, expert layers that hold a share of the experts their
router sees, renormalised and scaled gates, a shared expert; against the
plain float32 reference `benchmark/reference/laguna.py` at a small size on
the CPU, each mechanism knocked out in turn."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

import family_cases as fc
from benchmark import harness
from family_cases import *  # noqa: F401,F403  the shared cases
from family_cases import test_a_fault_fails_the_familys_tolerance  # noqa: F401
from kungfu_tpu.models import transformer
from kungfu_tpu.models.mixers import attention

family = fc.LAGUNA.module
_as = lambda **changes: fc.model_changed(family, **changes)


def _no_yarn_factor(cfg, model_config=family.model_config):
    mc = model_config(cfg)
    kinds = tuple(tuple((k, v[:4] + (1.0,) if k == "yarn" and v else v)
                        for k, v in kind) for kind in mc.layer_kinds)
    return dataclasses.replace(mc, layer_kinds=kinds)


def _wrong_group(m):
    core_of = attention.attention_core_of

    def reversed_groups(cfg):
        core = core_of(cfg)
        return lambda q, k, v: core(q, k[:, ::-1], v[:, ::-1])

    m.setattr(attention, "attention_core_of", reversed_groups)


FAULTS = {
    "no_window": _as(window=0),
    "wrong_group": _wrong_group,
    "no_head_gate": _as(head_gate=False),
    "rotary_over_the_whole_head": _as(rotary_share=1.0),
    "no_yarn_factor": lambda m: m.setattr(family, "model_config", _no_yarn_factor),
    "gates_not_renormalised": _as(gates="raw"),
    "no_routed_scale": _as(routed_scale=1.0),
    "no_shared_expert": _as(shared_ff=0),
}


def _named_specs(specs):
    assert specs["layers"][1]["wq"] == PartitionSpec(None, None, "tp")
    assert specs["layers"][1]["w_gate"] == PartitionSpec(None, "ep", None, "tp")


FAMILY = fc.LAGUNA.with_cases(named_specs=_named_specs,
                              tp_leaf=("layers", 1, "wq"), faults=FAULTS)
CONFIG = FAMILY.config


def test_the_stacks_are_the_models_layers_in_order():
    mc = family.model_config(CONFIG)
    kinds = [(kind.n_heads, kind.window, kind.ffn, kind.d_ff, kind.layer_remat, n)
             for kind, n in mc.stacks]
    assert kinds == [(4, 0, "swiglu", 96, False, 1), (6, 16, "moe", 32, True, 3),
                     (4, 0, "moe", 32, False, 1)]
    state = jax.eval_shape(lambda: family.init(CONFIG, 0))
    shapes = [{k: v.shape for k, v in stack.items()} for stack in state["layers"]]
    assert shapes[0]["wq"] == (1, 64, 64) and shapes[1]["wq"] == (3, 64, 96)
    assert shapes[1]["wk"] == (3, 64, 32) and shapes[1]["wo"] == (3, 96, 64)
    assert shapes[1]["w_head_gate"] == (3, 64, 6)
    assert shapes[1]["router"] == (3, 64, 16) and shapes[1]["w_gate"] == (3, 4, 64, 32)
    assert shapes[0]["w_gate"] == (1, 64, 96) and "router" not in shapes[0]
    assert shapes[2]["shared_down"] == (1, 32, 64)


def test_the_share_routes_over_all_experts_and_drops_nothing():
    """Every token to experts 4, 5 and 6, all of them held: 3 of the
    share's T x min(3, 4) buffer rows a token, none dropped, and loss and
    gradients still the reference's."""
    state, sample = FAMILY.state(), FAMILY.sample()
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
    loss, grads = family.program_loss_and_grads(CONFIG)(state, sample)
    want_loss, want = family.reference_loss_and_grads(CONFIG, state, sample)
    assert fc.off(loss, want_loss) <= 1e-5
    assert harness.relative_error(grads, want) <= 5e-5


def test_a_head_size_of_its_own_needs_no_divisible_width():
    """`head_dim` is the configuration's where it gives one: 6 heads of 16
    on a width of 64."""
    mc = family.model_config(CONFIG)
    kind = mc.stacks[1][0]
    assert kind.head_dim == 16 and kind.n_heads * kind.head_dim == 96 != kind.d_model
    assert kind.kv_heads == 2 and kind.split_qkv
    assert transformer.TransformerConfig.tiny().head_dim == 16
    fc.refused("flash", n_heads=4, n_kv_heads=2)
    fc.refused("no multiple", n_heads=4, n_kv_heads=3, attn_core="flash")
    with pytest.raises(ValueError, match="layer kinds"):
        transformer.TransformerConfig(n_layers=2, layer_kinds=((("window", 0),),))
    with pytest.raises(TypeError):
        transformer.TransformerConfig(n_layers=1, layer_kinds=((("no_such", 0),),))


def test_a_layer_run_again_runs_its_forward_kernel_once():
    """`layer_remat` keeps the flash core's output and row sums by name, so
    the program holds a forward kernel for each stack and two backward
    ones, and no fourth for the sliding stack; with nothing kept by name
    the scan's backward pass holds the forward kernel again."""
    state = jax.eval_shape(lambda: family.init(CONFIG, 0))

    def kernels(mc):
        loss = lambda p, b: transformer.transformer_loss(p, b, mc)
        text = str(jax.make_jaxpr(jax.grad(loss))(state, FAMILY.sample()))
        # the flash kernels: the rotary passes (PR 35) are kernels too
        return text.count("pallas_call") - text.count("name=rotary")

    assert kernels(family.model_config(CONFIG)) == 3 * 3
    plain = jax.checkpoint(transformer._layer, static_argnums=(2,))
    with pytest.MonkeyPatch.context() as m:
        m.setattr(transformer, "_layer_again", plain)
        assert kernels(family.model_config(CONFIG)) == 3 * 3 + 1
