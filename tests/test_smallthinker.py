"""SmallThinker-21BA3B-Instruct's layers in `models/transformer.py` (PR 65):
an expert layer routed from the layer's own input, ahead of the mixer, with
relu-gated experts over a share of the experts; full-attention layers without
any position signal under window layers with rotary positions, in one stack;
an untied head; against the plain float32 reference
`benchmark/reference/smallthinker.py` at a small size on the CPU (two periods,
so that a full layer follows window layers), the four shares of one layer
added up; each mechanism knocked out in turn in
`tests/test_smallthinker_faults.py`."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

import family_cases as fc
from family_cases import *  # noqa: F401,F403  the shared cases
from kungfu_tpu.models import transformer
from kungfu_tpu.models.transformer import TransformerConfig
from kungfu_tpu.telemetry import metrics


def _named_specs(specs):
    assert len(specs["layers"]) == 4  # full, three window, full, three window
    for layers in specs["layers"]:
        assert layers["wq"] == layers["wk"] == PartitionSpec(None, None, "tp")
        assert layers["wo"] == PartitionSpec(None, "tp", None)
        assert layers["router"] == PartitionSpec(None, None, None)  # whole
        assert layers["w_gate"] == layers["w_up"] == PartitionSpec(
            None, "ep", None, "tp")
        assert layers["w_down"] == PartitionSpec(None, "ep", "tp", None)
        assert "q_norm_scale" not in layers and "pos_embed" not in specs
    assert specs["lm_head"] == specs["embed"] == PartitionSpec("tp", None)


FAMILY = fc.SMALLTHINKER.with_cases(named_specs=_named_specs,
                                    tp_leaf=("layers", 1, "wq"))
family, tiny_config, CONFIG = FAMILY.module, FAMILY.tiny_config, FAMILY.config


def test_the_model_is_the_files():
    mc = family.model_config(CONFIG)
    assert (mc.router_input, mc.expert_act, mc.gates, mc.router_scores) == (
        "layer", "reglu", "renorm", "softmax")
    assert (mc.ffn, mc.top_k, mc.n_experts, mc.experts_held) == ("moe", 3, 8, (2, 4))
    assert (mc.split_qkv, mc.head_dim, mc.kv_heads, mc.qk_norm, mc.tied_head) == (
        True, 16, 2, False, False)
    kinds = [(kind.positions, kind.window, n) for kind, n in mc.stacks]
    assert kinds == [("none", 0, 1), ("rope", 16, 3)] * 2
    assert mc.rotary and mc.positions == "none" and mc.rope_theta == 1.5e6
    state = jax.eval_shape(lambda: family.init(CONFIG, 0))
    assert set(state) == {"embed", "lm_head", "ln_f_scale", "layers"}
    for stack, n in zip(state["layers"], (1, 3, 1, 3), strict=True):
        assert {k: v.shape for k, v in stack.items()} == {
            "ln1_scale": (n, 64), "ln2_scale": (n, 64), "wq": (n, 64, 64),
            "wk": (n, 64, 32), "wv": (n, 64, 32), "wo": (n, 64, 64),
            "router": (n, 64, 8), "w_gate": (n, 4, 64, 32),
            "w_up": (n, 4, 64, 32), "w_down": (n, 4, 32, 64)}
    real = family.model_config(fc.mf.cell(fc.mf.load(), FAMILY.cell)["config"])
    # the file's model is the classmethod's, cut as the file says
    published = TransformerConfig.smallthinker_21b_a3b()
    assert published.n_layers == 52 and len(published.stacks) == 26
    cut = TransformerConfig.smallthinker_21b_a3b(
        n_layers=4, vocab_size=18992, experts_held=(0, 16))
    remat = tuple(kind + (("layer_remat", True),) for kind in cut.layer_kinds)
    assert real == dataclasses.replace(cut, layer_kinds=remat)
    assert [(k.positions, k.window, k.layer_remat, n) for k, n in real.stacks] == [
        ("none", 0, True, 1), ("rope", 4096, True, 3)]
    assert (real.n_heads, real.kv_heads, real.head_dim, real.d_ff) == (28, 4, 128, 768)
