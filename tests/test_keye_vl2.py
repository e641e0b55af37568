"""Keye-VL-2.0-30B-A3B's layers in `models/transformer.py` (PR 61): grouped
attention over keys that a lightning indexer chooses, the indexer's own KL
loss beside the cross-entropy, and softmax top-k experts renormalised over a
share of the experts, an untied head; against the plain float32 reference
`benchmark/reference/keye_vl2.py` at a small size on the CPU, the shares of
one expert layer added up; each mechanism knocked out in turn in
`tests/test_keye_vl2_faults.py`."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

import family_cases as fc
from benchmark import harness
from family_cases import *  # noqa: F401,F403  the shared cases
from jaxprs import pallas_calls
from kungfu_tpu.models import transformer
from kungfu_tpu.models.transformer import TransformerConfig
from kungfu_tpu.telemetry import metrics


def _named_specs(specs):
    layers = specs["layers"]
    assert layers["wq"] == layers["wk"] == PartitionSpec(None, None, "tp")
    assert layers["wo"] == PartitionSpec(None, "tp", None)
    assert layers["q_norm_scale"] == PartitionSpec(None, None)  # a head's
    # the indexer whole on every chip: one choice for every shard of the heads
    for name in ("index_wq", "index_wk", "index_w"):
        assert layers[name] == PartitionSpec(None, None, None)
    assert layers["index_ln_scale"] == layers["index_ln_bias"] == PartitionSpec(
        None, None)
    assert layers["w_gate"] == PartitionSpec(None, "ep", None, "tp")
    assert specs["lm_head"] == specs["embed"] == PartitionSpec("tp", None)


FAMILY = fc.KEYE_VL2.with_cases(named_specs=_named_specs, tp_leaf=("layers", "wq"))
family, tiny_config, CONFIG = FAMILY.module, FAMILY.tiny_config, FAMILY.config


def _main(tree):
    """A gradient tree without the indexer's leaves."""
    return {**tree, "layers": {k: v for k, v in tree["layers"].items()
                               if k not in family.INDEX_LEAVES}}


def test_the_model_is_the_files():
    mc = family.model_config(CONFIG)
    assert (mc.sparse_index, mc.indexer_loss_weight) == ((2, 8, 16), 1.0)
    assert (mc.mixer, mc.split_qkv, mc.head_dim, mc.kv_heads, mc.qk_norm) == (
        "attention", True, 16, 2, True)
    assert (mc.positions, mc.rope_theta, mc.tied_head) == ("rope", 1e7, False)
    assert (mc.ffn, mc.gates, mc.router_scores, mc.top_k, mc.n_experts,
            mc.experts_held) == ("moe", "renorm", "softmax", 3, 8, (2, 4))
    assert not mc.layer_kinds and mc.stacks == ((mc, 2),)
    state = jax.eval_shape(lambda: family.init(CONFIG, 0))
    assert set(state) == {"embed", "lm_head", "ln_f_scale", "layers"}
    assert {k: v.shape for k, v in state["layers"].items()} == {
        "ln1_scale": (2, 64), "ln2_scale": (2, 64), "wq": (2, 64, 64),
        "wk": (2, 64, 32), "wv": (2, 64, 32), "wo": (2, 64, 64),
        "q_norm_scale": (2, 16), "k_norm_scale": (2, 16),
        "index_wq": (2, 64, 16), "index_wk": (2, 64, 8), "index_w": (2, 64, 2),
        "index_ln_scale": (2, 8), "index_ln_bias": (2, 8),
        "router": (2, 64, 8), "w_gate": (2, 4, 64, 32), "w_up": (2, 4, 64, 32),
        "w_down": (2, 4, 32, 64)}
    real = family.model_config(fc.mf.cell(fc.mf.load(), FAMILY.cell)["config"])
    assert (real.sparse_index, real.n_heads, real.kv_heads, real.head_dim) == (
        (16, 64, 2048), 32, 4, 128)
    assert (real.attn_core, real.flash_blocks, real.layer_remat) == (
        "flash", (512, 512), True)


def test_the_kernels_interpreted_are_the_plain_forms():
    """`attention_core` flash: the model on `ops.sparse_attention`'s six
    kernels at blocks of 32, so that a row's keys cross blocks, against the
    plain forms the other cases run; a layer that is run again keeps the
    core's output and does not run its forward kernel twice."""
    config = tiny_config(attention_core="flash")
    loss, grads = family.program_loss_and_grads(config)(
        FAMILY.state(), FAMILY.sample())
    want_loss, want = FAMILY.baseline()
    assert fc.off(loss, want_loss) <= 1e-5
    assert harness.relative_error(grads, want) <= 1e-4
    state = jax.eval_shape(lambda: family.init(config, 0))
    run_again = tiny_config(attention_core="flash", recomputed_layer_types=["sparse"])
    names = lambda cfg: sorted(pallas_calls(jax.make_jaxpr(jax.grad(
        family.loss_fn(cfg)))(state, FAMILY.sample()).jaxpr))
    kernels = {"dsa_core_forward", "dsa_core_dkv", "dsa_core_dq",
               "dsa_index_scores", "dsa_index_scores_bwd", "dsa_head_mean_probs"}
    assert {kernel for kernel, _ in names(config)} == kernels | {"rotary"}
    again = names(run_again)
    for kernel in ("dsa_index_scores", "dsa_head_mean_probs"):
        assert (kernel, True) in again  # made again in the backward pass...
    # ... but for the core's forward kernel, whose output and row sums are kept
    # (`flash_out`, `flash_lse`): it runs once a step
    assert [inside for kernel, inside in again if kernel == "dsa_core_forward"] == [False]
    assert [inside for kernel, inside in again if kernel == "dsa_core_dq"] == [True]


def test_the_choice_lowers_as_one_kernel_under_its_scope():
    """`attention_core` flash at positions that the kernel's blocks tile: the
    step's program for the chip holds the call `dsa_select` under the scope
    `dsa_select`, once (a layer run again keeps the bits), and none of the
    plain form's counting there; on "dense" the plain form's `while`s."""
    sample = jax.ShapeDtypeStruct((1, 129), jnp.int32)

    def lowered(**changes):
        config = tiny_config(sequence_length=128, flash_blocks=[128, 128],
                             recomputed_layer_types=["sparse"], **changes)
        state = jax.eval_shape(lambda: family.init(config, 0))
        return jax.jit(jax.grad(family.loss_fn(config))).trace(
            state, sample).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)

    flash = lowered(attention_core="flash", flash_interpret=False)
    assert flash.count('attn/dsa_select/dsa_select/pallas_call"') == 1
    assert "dsa_select/reduce_sum" not in flash
    assert "rematted_computation/attn/dsa_select/jit(unpackbits)" in flash
    dense = lowered(attention_core="dense")
    assert "dsa_select/reduce_sum" in dense and "dsa_select/pallas_call" not in dense
    assert dense.count("stablehlo.while") > flash.count("stablehlo.while")


def test_a_full_choice_is_the_model_without_an_index():
    """With as many keys a query as positions every query chooses every
    earlier key: loss and gradients in the main leaves are those of the same
    model without `sparse_index` (on the flash core, which runs grouped
    heads), whatever the indexer holds."""
    config = tiny_config()
    config["sa_config"] = {**config["sa_config"], "topk": 64}
    state, sample = FAMILY.state(), FAMILY.sample()
    mc = family.model_config(config)
    full = jax.jit(jax.value_and_grad(lambda p: transformer.transformer_loss(
        p, sample, dataclasses.replace(mc, indexer_loss_weight=0.0))))(state)
    without = jax.jit(jax.value_and_grad(lambda p: transformer.transformer_loss(
        p, sample, dataclasses.replace(mc, sparse_index=(), attn_core="flash"))))(
            _main(state))
    assert float(full[0]) == pytest.approx(float(without[0]), rel=1e-6)
    assert harness.relative_error(_main(full[1]), without[1]) <= 1e-5


def test_the_two_losses_are_the_references_and_share_no_leaf():
    """Both parts of the loss against the reference's (which ran in four
    blocks of rows and of positions); the cross-entropy gives the indexer's
    five leaves no gradient, and the indexer's loss gives no other leaf any."""
    mc = family.model_config(CONFIG)
    state, sample = FAMILY.state(), FAMILY.sample()

    def both(p):
        losses = transformer.transformer_losses(p, sample, mc)
        return losses["main"], losses["indexer_kl"]

    def pulled(p):
        values, pull = jax.vjp(both, p)
        return values, pull((1.0, 0.0))[0], pull((0.0, 1.0))[0]

    (ce, kl_sum), main, kl = jax.jit(pulled)(state)
    want_loss, want = FAMILY.reference()
    want_kl = float(want["indexer_kl"]) / CONFIG["compared_weights"]["indexer_kl"]
    assert float(kl_sum) == pytest.approx(want_kl, rel=1e-4) and want_kl > 2e-3
    assert float(ce) == pytest.approx(float(want_loss) - want_kl, rel=1e-5)
    assert float(FAMILY.baseline()[0]) == pytest.approx(float(ce + kl_sum), rel=1e-6)
    assert family.chosen_pairs(CONFIG) == 136 + 48 * 16  # three rows in four choose
    assert family._hyper(CONFIG)["row_block"] == 16 < CONFIG["sequence_length"]
    for name, leaf in main["layers"].items():
        assert bool(np.asarray(leaf).any()) == (name not in family.INDEX_LEAVES), name
    for name, leaf in kl["layers"].items():
        assert bool(np.asarray(leaf).any()) == (name in family.INDEX_LEAVES), name
    assert not any(np.asarray(kl[name]).any() for name in ("embed", "lm_head",
                                                           "ln_f_scale"))
    # which is what the comparison's two groups of gradients are
    base = FAMILY.baseline()[1]["state"]
    assert harness.relative_error(_main(base), _main(main)) <= 1e-5
    weight = CONFIG["compared_weights"]["indexer_grads"]
    assert harness.relative_error(base["layers"]["index_wq"],
                                  weight * kl["layers"]["index_wq"]) <= 1e-5


@pytest.mark.parametrize("load", fc.HELD_LOADS)
def test_the_model_is_the_reference_whatever_the_held_experts_get(load):
    fc.held_load_is_the_references(FAMILY, load)


def test_the_losses_reach_their_gauges():
    registry = metrics.Registry()
    transformer.record_losses({"main": 5.5, "indexer_kl": 0.25}, registry)
    text = registry.render()
    assert "kungfu_lm_loss 5.5" in text and "kungfu_indexer_kl 0.25" in text
    assert "kungfu_mtp_loss" not in text
    plain = metrics.Registry()
    transformer.record_losses({"main": 1.0}, plain)
    assert "kungfu_indexer_kl" not in plain.render()


def test_what_is_not_built_is_refused_with_a_sentence():
    sparse = dict(sparse_index=(2, 8, 16), head_size=16, n_kv_heads=2,
                  positions="rope")
    fc.refused("keys a query", **{**sparse, "sparse_index": (2, 8)})
    fc.refused("even indexer head size", **{**sparse, "sparse_index": (2, 7, 16)})
    fc.refused("projections of its own", sparse_index=(2, 8, 16), positions="rope")
    fc.refused("rotary positions", **{**sparse, "positions": "learned"})
    fc.refused("a window beside the choice", **sparse, window=8, attn_core="flash")
    fc.refused("packed documents", **sparse, end_of_document=0, attn_core="flash")
    fc.refused("multi-token-prediction", **sparse, mtp_depth=1, mtp_weight=0.3)
    fc.refused("under a loop", **sparse, loop_steps=2)
    fc.refused("layers that differ in kind", **sparse, n_layers=2, layer_kinds=(
        (("ffn", "swiglu"),), (("ffn", "gelu"),)))
    fc.refused("a gate a head", **sparse, head_gate=True)
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=1,
                            max_seq=16, **sparse)
    with pytest.raises(NotImplementedError, match="normal path"):  # ring, pipeline
        transformer._block(jnp.zeros((1, 16, 32)), {}, cfg)
    with pytest.raises(ValueError, match="no sparse_index"):
        transformer.sparse_choices({}, jnp.zeros((1, 16), jnp.int32),
                                   dataclasses.replace(cfg, sparse_index=()))


def test_without_an_index_a_lowered_step_is_what_it_was():
    """`sparse_index=()` is a Python branch: an OLMoE layer's program holds
    none of the mechanism's scopes, leaves or kernels."""
    cfg = TransformerConfig.tiny_moe()
    params = jax.eval_shape(lambda: transformer.init_transformer(
        jax.random.PRNGKey(0), cfg))
    assert not [name for name in params["layers"] if name.startswith("index_")]
    batch = jnp.zeros((2, 65), jnp.int32)
    text = jax.jit(jax.grad(lambda p: transformer.transformer_loss(
        p, batch, cfg))).lower(params).as_text(debug_info=True)
    assert "attn/attn_core" in text
    for scope in ("dsa_index", "dsa_select", "attn_sparse", "dsa_kl", "attn_proj"):
        assert scope not in text, scope
