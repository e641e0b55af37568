"""LFM2-24B-A2B's layers in `models/transformer.py` (PR 57): a gated short
convolution as the mixer of three layers in four beside grouped attention
behind a q/k norm a head and a rotary pass, two leading dense feed-forwards
and expert layers after them whose router scores are sigmoids chosen under a
selection bias and renormalised, over a share of the experts, a tied head;
against the plain float32 reference `benchmark/reference/lfm2_moe.py` at a
small size on the CPU, the shares of one expert layer added up; each
mechanism knocked out in turn in `tests/test_lfm2_moe_faults.py`."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

import family_cases as fc
from benchmark import harness, manifest as mf
from benchmark.reference import lfm2_moe as ref
from family_cases import *  # noqa: F401,F403  the shared cases
from jaxprs import pallas_calls
from kungfu_tpu.models import transformer
from kungfu_tpu.models.mixers import short_conv as short_conv_mixer
from kungfu_tpu.models.transformer import TransformerConfig
from kungfu_tpu.ops import short_conv
from kungfu_tpu.telemetry import metrics


def _named_specs(specs):
    conv_dense, attention, conv_sparse = specs["layers"]
    for conv in (conv_dense, conv_sparse):
        # the projection and the taps with the channels, W_out's rows
        assert conv["conv_in"] == conv["conv_w"] == PartitionSpec(None, None, "tp")
        assert conv["conv_out"] == PartitionSpec(None, "tp", None)
        assert not {"wo", "wq", "wqkv"} & set(conv)
    assert conv_dense["w_gate"] == PartitionSpec(None, None, "tp")
    assert attention["wq"] == attention["wk"] == PartitionSpec(None, None, "tp")
    assert attention["wo"] == PartitionSpec(None, "tp", None)
    assert attention["q_norm_scale"] == PartitionSpec(None, None)  # a head's
    assert conv_sparse["w_gate"] == PartitionSpec(None, "ep", None, "tp")
    assert conv_sparse["router_bias"] == PartitionSpec(None, None)
    assert "lm_head" not in specs and specs["embed"] == PartitionSpec("tp", None)


FAMILY = fc.LFM2_MOE.with_cases(
    named_specs=_named_specs, tp_leaf=("layers", 0, "conv_in"))
family, tiny_config, CONFIG = FAMILY.module, FAMILY.tiny_config, FAMILY.config


def test_the_stacks_are_the_models_layers_in_order():
    assert family.layer_types(CONFIG) == [
        ("conv", "dense"), ("full_attention", "sparse"), ("conv", "sparse")]
    mc = family.model_config(CONFIG)
    assert [(kind.mixer, kind.ffn, kind.d_ff, kind.layer_remat, n)
            for kind, n in mc.stacks] == [
        ("short_conv", "swiglu", 192, False, 1), ("attention", "moe", 32, True, 1),
        ("short_conv", "moe", 32, True, 1)]
    assert (mc.router_scores, mc.router_bias, mc.gates, mc.routed_scale) == (
        "sigmoid", True, "renorm", 1.0)
    assert (mc.conv_taps, mc.tied_head, mc.positions, mc.rope_theta) == (
        3, True, "rope", 1e6)
    assert (mc.head_dim, mc.kv_heads, mc.qk_norm, mc.shared_ff) == (32, 2, True, 0)
    state = jax.eval_shape(lambda: family.init(CONFIG, 0))
    assert set(state) == {"embed", "ln_f_scale", "layers"}  # tied
    shapes = [{k: v.shape for k, v in stack.items()} for stack in state["layers"]]
    conv = {"ln1_scale": (1, 128), "conv_in": (1, 128, 384),
            "conv_w": (1, 3, 128), "conv_out": (1, 128, 128)}
    experts = {"ln2_scale": (1, 128), "router": (1, 128, 16),
               "router_bias": (1, 16), "w_gate": (1, 8, 128, 32),
               "w_up": (1, 8, 128, 32), "w_down": (1, 8, 32, 128)}
    assert shapes == [
        {**conv, "ln2_scale": (1, 128), "w_gate": (1, 128, 192),
         "w_up": (1, 128, 192), "w_down": (1, 192, 128)},
        {"ln1_scale": (1, 128), "wq": (1, 128, 128), "wk": (1, 128, 64),
         "wv": (1, 128, 64), "wo": (1, 128, 128), "q_norm_scale": (1, 32),
         "k_norm_scale": (1, 32), **experts},
        {**conv, **experts}]
    # the cell's own: layers 0 to 7 in five stacks
    real = family.model_config(mf.cell(mf.load(), FAMILY.cell)["config"])
    assert [(kind.mixer, kind.ffn, n) for kind, n in real.stacks] == [
        ("short_conv", "swiglu", 2), ("attention", "moe", 1),
        ("short_conv", "moe", 3), ("attention", "moe", 1), ("short_conv", "moe", 1)]


def test_the_published_preset_is_the_whole_model():
    """`TransformerConfig.lfm2_24b_a2b()`: 40 layers, 30 of them convolutions,
    23.8 B parameters with the head tied."""
    mc = TransformerConfig.lfm2_24b_a2b()
    kinds = [dict(kind) for kind in mc.layer_kinds]
    assert [k["mixer"] for k in kinds].count("short_conv") == 30
    assert [l for l, k in enumerate(kinds) if k["mixer"] == "attention"] == list(
        range(2, 40, 4))
    assert [k["ffn"] for k in kinds] == ["swiglu"] * 2 + ["moe"] * 38
    state = jax.eval_shape(lambda: transformer.init_transformer(
        jax.random.PRNGKey(0), mc))
    assert sum(x.size for x in jax.tree.leaves(state)) == pytest.approx(
        23.84e9, rel=1e-3)
    cut = family.model_config(mf.cell(mf.load(), FAMILY.cell)["config"])
    whole = TransformerConfig.lfm2_24b_a2b(n_layers=8)
    for mine, published in zip(cut.stacks, whole.stacks, strict=True):
        assert dataclasses.replace(
            mine[0], layer_remat=False, experts_held=(), vocab_size=65536,
            max_seq=128000, flash_blocks=published[0].flash_blocks) == published[0]


def test_the_logits_are_the_references_on_the_tied_embedding():
    mc = family.model_config(CONFIG)
    state, sample = FAMILY.state(), FAMILY.sample()
    got = jax.jit(lambda p, t: transformer.transformer_apply(p, t, mc))(
        state, sample[:, :-1])
    want = ref.logits(state, sample, **family._hyper(CONFIG))
    assert got.shape == want.shape == (2, 64, 320)
    assert harness.relative_error(got, want) <= 1e-5


def test_the_mixer_alone_is_the_references_and_runs_the_kernels():
    """The convolution mixer by itself on hidden states and weights that
    matter, against the reference's shifted products; its op is the Pallas
    kernels at the tests' 128 channels, one forward and one backward call."""
    mc = family.model_config(CONFIG).stacks[0][0]
    layer = jax.tree.map(lambda a: a[0], FAMILY.state()["layers"][0])
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 128))
    mixer = lambda h, w: short_conv_mixer._short_conv_mixer(h, w, mc)
    got = jax.jit(mixer)(h, layer)
    with jax.default_matmul_precision("highest"):
        want = ref.short_conv(h, layer)
    assert harness.relative_error(got, want) <= 1e-5
    assert short_conv.tiles(64, 128, 3)
    jaxpr = jax.make_jaxpr(jax.grad(lambda h, w: jnp.sum(mixer(h, w))))(
        h, layer).jaxpr
    # each under `platform_dependent`: Mosaic's and the interpreted one
    assert sorted(kernel for kernel, _ in pallas_calls(jaxpr)) == [
        "short_conv_backward"] * 2 + ["short_conv_forward"] * 2


def test_a_packed_row_is_its_documents_run_one_at_a_time():
    """With an end-of-document id named, the segments reach the convolution
    through the layer scan and a layer that is run again alike: every
    document of a packed row reads what it reads run as a row of its own."""
    mc = dataclasses.replace(family.model_config(CONFIG), end_of_document=0)
    assert {kind.layer_remat for kind, _ in mc.stacks} == {False, True}
    state = FAMILY.state()
    tokens = np.asarray(FAMILY.sample()[:1, :-1]).copy()
    tokens[tokens == 0] = 1
    tokens[0, [9, 31, 32]] = 0
    tokens = jnp.asarray(tokens)
    hidden = jax.jit(lambda p, t: transformer.transformer_hidden(p, t, mc))
    packed = hidden(state, tokens)
    starts = [0, 10, 32, 33, 64]
    for lo, hi in zip(starts, starts[1:]):
        alone = jnp.concatenate([tokens[:, lo:hi],
                                 jnp.full((1, 64 - hi + lo), 7, tokens.dtype)], axis=1)
        got = hidden(state, alone)[:, :hi - lo]
        assert harness.relative_error(got, packed[:, lo:hi]) <= 2e-6
    one = jax.jit(lambda p, t: transformer.transformer_hidden(
        p, t, dataclasses.replace(mc, end_of_document=None)))(state, tokens)
    assert harness.relative_error(one[:, 10:], packed[:, 10:]) > 1e-2


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One expert layer of 64 experts, 4 a token by sigmoid scores and a
    selection bias, renormalised at a scale of 1, cut into 8 shares of 8, no
    shared expert (`fc.shares_add_up`)."""
    E, D, F, T = 64, 64, 32, 96
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    n = jax.random.normal(ks[0], (T, D))
    w = {"router": 0.5 * jax.random.normal(ks[1], (D, E)),
         "router_bias": 0.3 * jax.random.normal(ks[5], (E,)),
         "w_gate": 0.3 * jax.random.normal(ks[2], (E, D, F)),
         "w_up": 0.3 * jax.random.normal(ks[3], (E, D, F)),
         "w_down": 0.3 * jax.random.normal(ks[4], (E, F, D))}
    want, chosen = ref.experts(n, w, dict(top_k=4, routed_scale=1.0, first_held=0))
    cfg = TransformerConfig(
        d_model=D, d_ff=F, dtype=jnp.float32, ffn="moe", n_experts=E, top_k=4,
        gates="renorm", routed_scale=1.0, router_scores="sigmoid",
        router_bias=True)
    assert fc.shares_add_up(n, w, cfg, 8, want, chosen, 0.0) == 8


def test_the_loss_reaches_its_gauge_and_no_modules():
    """`kungfu_lm_loss` as for the GLM cell's model, with no multi-token
    gauge beside it; the routing gauges, `kungfu_moe_bias_moved_token_choices`
    among them, are the shared case's (`fc.test_the_share_drops_nothing...`)."""
    registry = metrics.Registry()
    transformer.record_losses({"main": FAMILY.baseline()[0]}, registry)
    text = registry.render()
    assert "kungfu_lm_loss " in text and "kungfu_mtp_loss" not in text
    assert family.program_losses(CONFIG, FAMILY.state(), FAMILY.sample())[
        "main"] == pytest.approx(float(FAMILY.baseline()[0]), rel=1e-6)


def test_what_is_not_built_is_refused_with_a_sentence():
    conv = dict(mixer="short_conv", ffn="swiglu", conv_taps=3)
    fc.refused("3 taps, what `ops/short_conv.py`'s kernels", **{**conv, "conv_taps": 4})
    fc.refused("under a loop", **conv, loop_steps=2)
    fc.refused("multi-token-prediction", **conv, mtp_depth=1, mtp_weight=0.3)
    fc.refused("under a loop", loop_steps=2, n_layers=2, conv_taps=3, layer_kinds=(
        (("mixer", "short_conv"), ("ffn", "swiglu")), (("ffn", "swiglu"),)))
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=1,
                            max_seq=16, **conv)
    params = transformer.init_transformer(jax.random.PRNGKey(0), cfg)
    layer = jax.tree.map(lambda a: a[0], params["layers"])
    with pytest.raises(NotImplementedError, match="normal path"):  # ring, pipeline
        transformer._block(jnp.zeros((1, 16, 32)), layer, cfg)
    # packed rows are kept apart by it: the configuration is taken
    assert dataclasses.replace(cfg, end_of_document=0).end_of_document == 0
