"""Kimi Linear's layers in `models/transformer.py` (PR 69): Kimi Delta
Attention (q, k, v through convolutions, a delta rule whose decay is a number
a key feature, a norm a head under a sigmoid gate) three layers to one of
latent attention with no q latent, nothing turned by position and q/k heads
larger than the value heads, a dense first layer and expert layers after it
whose router scores are sigmoids chosen under a selection bias, renormalised
and scaled, over a share of the experts beside a shared expert; against the
plain float32 reference `benchmark/reference/kimi_linear.py` at a small size
on the CPU, the shares of one expert layer added up; each mechanism knocked
out in turn in `tests/test_kimi_linear_faults.py`."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec

import family_cases as fc
from benchmark import harness
from benchmark.reference import kimi_linear as ref
from family_cases import *  # noqa: F401,F403  the shared cases
from jaxprs import pallas_calls
from kungfu_tpu.models import transformer
from kungfu_tpu.models.mixers import MIXERS, kda, latent
from kungfu_tpu.models.transformer import TransformerConfig


def _named_specs(specs):
    dense, sparse, full = specs["layers"]
    # the projections' and the taps' channels and W_o over tp, as wq and wo
    # are; the low ranks' first halves, beta's matrix, a number a head or a
    # feature and the norm's scale whole
    for name in ("w_q", "w_k", "w_v", "conv_q", "conv_k", "conv_v", "w_f_b", "w_g_b"):
        assert sparse[name] == dense[name] == PartitionSpec(None, None, "tp"), name
    assert sparse["wo"] == full["wo"] == PartitionSpec(None, "tp", None)
    assert sparse["w_f_a"] == sparse["w_g_a"] == sparse["w_beta"] == (
        PartitionSpec(None, None, None))
    assert sparse["A_log"] == sparse["dt_bias"] == sparse["kda_norm_scale"] == (
        PartitionSpec(None, None))
    # no q latent: W_q's columns a head at a time, and no leaf of a latent's
    assert full["w_q_up"] == full["w_kv_up"] == PartitionSpec(None, None, "tp")
    assert not {"w_q_down", "q_latent_norm"} & set(full)
    assert full["router_bias"] == PartitionSpec(None, None)
    assert dense["w_gate"] == PartitionSpec(None, None, "tp")
    assert sparse["w_gate"] == PartitionSpec(None, "ep", None, "tp")


FAMILY = fc.KIMI_LINEAR.with_cases(
    named_specs=_named_specs, tp_leaf=("layers", 1, "w_q"))
family, tiny_config, CONFIG = FAMILY.module, FAMILY.tiny_config, FAMILY.config


def test_the_stacks_are_the_models_layers_in_order():
    assert family.layer_types(CONFIG) == [
        "kda_dense", "kda_sparse", "kda_sparse", "mla_sparse"]
    # the cell's five: the period's three KDA layers to one latent, and on
    assert family.layer_types(tiny_config(num_hidden_layers=5)) == [
        "kda_dense", "kda_sparse", "kda_sparse", "mla_sparse", "kda_sparse"]
    whole = family.layer_types(tiny_config(num_hidden_layers=27))
    assert [l + 1 for l, t in enumerate(whole) if t.startswith("mla")] == [
        4, 8, 12, 16, 20, 24, 27] == CONFIG["linear_attn_config"]["full_attn_layers"]
    mc = family.model_config(CONFIG)
    assert [(kind.mixer, kind.ffn, kind.layer_remat, n) for kind, n in mc.stacks] == [
        ("kda", "swiglu", True, 1), ("kda", "moe", True, 2), ("latent", "moe", True, 1)]
    assert (mc.positions, mc.latent_dims, mc.kda_heads, mc.conv_taps) == (
        "none", (0, 16, 16, 8, 8), (4, 16), 4)
    assert (mc.router_scores, mc.router_bias, mc.gates, mc.routed_scale,
            mc.shared_ff, mc.shared_gate, mc.mtp_depth) == (
        "sigmoid", True, "renorm", 2.446, 32, False, 0)
    state = jax.eval_shape(lambda: family.init(CONFIG, 0))
    dense, sparse, full = ({k: v.shape for k, v in stack.items()}
                           for stack in state["layers"])
    for shapes, lead in ((dense, (1,)), (sparse, (2,))):
        for name in ("w_q", "w_k", "w_v"):
            assert shapes[name] == lead + (64, 4 * 16)
            assert shapes["conv_" + name[-1]] == lead + (4, 4 * 16)
        assert shapes["w_f_a"] == shapes["w_g_a"] == lead + (64, 16)
        assert shapes["w_f_b"] == shapes["w_g_b"] == lead + (16, 4 * 16)
        assert shapes["A_log"] == lead + (4,)  # a number a head
        assert shapes["dt_bias"] == lead + (4 * 16,)  # a number a key feature
        assert shapes["w_beta"] == lead + (64, 4)
        assert shapes["kda_norm_scale"] == lead + (16,)
        assert shapes["wo"] == lead + (4 * 16, 64)
    # a head's q of 16 + 8 features straight from the hidden states; the
    # latent and the one shared key; a head's 16 key and 8 value features
    assert full["w_q_up"] == (1, 64, 4 * 24)
    assert full["w_kv_down"] == (1, 64, 16 + 8)
    assert full["w_kv_up"] == (1, 16, 4 * (16 + 8))
    assert full["wo"] == (1, 4 * 8, 64)
    assert dense["w_gate"] == (1, 64, 128) and "router" not in dense
    for shapes, lead in ((sparse, (2,)), (full, (1,))):
        assert shapes["router"] == lead + (64, 16)
        assert shapes["router_bias"] == lead + (16,)
        assert shapes["w_gate"] == lead + (8, 64, 32)
        assert shapes["shared_gate"] == lead + (64, 32)
        assert "w_shared_gate" not in shapes
    assert "lm_head" in state and "mtp" not in state and "pos_embed" not in state


@pytest.mark.parametrize("core", ["flash", "dense"])
def test_latent_attention_without_a_q_latent_and_positions(core):
    """The mixer by itself: q = h W_q, [own 16 | shared 8] features against
    value heads of 8, nothing turned, the scores over sqrt(24), on the
    core the configuration names, against the reference's softmax over the
    full score matrix."""
    config = tiny_config(attention_core=core)
    mc = family.model_config(config).stacks[2][0]
    layer = jax.tree.map(lambda a: a[0], FAMILY.state()["layers"][2])
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 128, 64))
    got = jax.jit(lambda h, w: latent._latent_attention(h, w, mc))(h, layer)
    with jax.default_matmul_precision("highest"):
        want = ref.latent_attention(h, layer, family._hyper(config))
    assert got.shape == want.shape == (2, 128, 64)
    assert harness.relative_error(got, want) <= 1e-5
    # no rotary pass at all: the one kernel is the flash core's, or none
    jaxpr = jax.make_jaxpr(lambda h, w: latent._latent_attention(h, w, mc))(h, layer)
    assert "rotary" not in str(jaxpr)
    assert len(pallas_calls(jaxpr.jaxpr)) == (core == "flash")


def test_the_kda_mixer_alone_against_the_recurrence(monkeypatch):
    """The mixer by itself on hidden states and weights that matter: the
    program's (head blocks, the rule's kernels interpreted) against the
    reference's (a position at a time); two blocks of two heads."""
    config = CONFIG
    mc = family.model_config(config).stacks[1][0]
    layer = jax.tree.map(lambda a: a[0], FAMILY.state()["layers"][1])
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 128, 64))
    want = ref.kda_mixer(h, layer, {**family._hyper(config), "head_block": 4})
    for block in (8, 2):
        monkeypatch.setattr(kda, "KDA_HEAD_BLOCK", block)
        got = jax.jit(lambda h, w: kda._kda_mixer(h, w, mc))(h, layer)
        assert got.shape == want.shape == (2, 128, 64)
        assert harness.relative_error(got, want) <= 2e-5, block
    # the decays differ feature by feature in the state the cases run on
    g = kda._log_decay(jnp.zeros((1, 1, 4, 16)), layer["A_log"], layer["dt_bias"])
    assert float(jnp.std(g, axis=-1).min()) > 0.05


def test_the_thirty_two_shares_add_up_to_the_uncut_layer():
    """One expert layer of 256 experts, 8 a token by sigmoid scores and a
    selection bias, renormalised and scaled by 2.446, cut into 32 shares of
    8 that each compute the shared expert (`fc.shares_add_up`)."""
    E, D, F, T = 256, 32, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 10)
    n = jax.random.normal(ks[0], (T, D))
    w = {"router": 0.5 * jax.random.normal(ks[1], (D, E)),
         "router_bias": 0.3 * jax.random.normal(ks[8], (E,)),
         "w_gate": 0.3 * jax.random.normal(ks[2], (E, D, F)),
         "w_up": 0.3 * jax.random.normal(ks[3], (E, D, F)),
         "w_down": 0.3 * jax.random.normal(ks[4], (E, F, D)),
         "shared_gate": 0.1 * jax.random.normal(ks[5], (D, F)),
         "shared_up": 0.1 * jax.random.normal(ks[6], (D, F)),
         "shared_down": 0.1 * jax.random.normal(ks[7], (F, D))}
    want, chosen = ref.experts(n, w, dict(top_k=8, routed_scale=2.446, first_held=0))
    shared = ref._swiglu(n, w["shared_gate"], w["shared_up"], w["shared_down"])
    cfg = TransformerConfig(
        d_model=D, d_ff=F, dtype=jnp.float32, ffn="moe", n_experts=E, top_k=8,
        gates="renorm", routed_scale=2.446, shared_ff=F, router_scores="sigmoid",
        router_bias=True)
    assert fc.shares_add_up(n, w, cfg, 8, want, chosen, shared) == 32


def test_the_new_fields_refuse_what_they_cannot_mean():
    assert sorted(MIXERS) == ["attention", "gated_delta", "kda", "latent",
                              "mamba2", "short_conv"]
    fc.refused("kda_heads", mixer="kda")
    fc.refused("kda_heads", mixer="kda", kda_heads=(4, 16, 2))
    fc.refused("latent_dims", mixer="latent", positions="none",
               latent_dims=(-1, 8, 8, 4, 8))
    fc.refused("turns nothing", mixer="latent", latent_dims=(0, 8, 8, 4, 8))
    # no q latent, no positions, value heads of their own size on either core
    for core in ("dense", "flash"):
        TransformerConfig(mixer="latent", positions="none", attn_core=core,
                          latent_dims=(0, 8, 8, 4, 16))
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=1,
                            mixer="kda", kda_heads=(2, 16), positions="none",
                            max_seq=64)
    params = transformer.init_transformer(jax.random.PRNGKey(0), cfg)
    with pytest.raises(NotImplementedError, match="normal path"):
        transformer._block(jnp.zeros((1, 64, 32)),
                           jax.tree.map(lambda a: a[0], params["layers"]), cfg)


def test_no_position_reads_a_later_one():
    """Both mixers are causal, the convolutions and the rule's chunks among
    them: another id at position 70 (the second chunk, the third flash
    block) moves no logit before it and moves those from it on."""
    mc = family.model_config(CONFIG)
    state, tokens = FAMILY.state(), jnp.asarray(FAMILY.sample()[:1, :-1])
    apply = jax.jit(lambda p, t: transformer.transformer_apply(p, t, mc))
    was = apply(state, tokens)
    now = apply(state, tokens.at[0, 70].set((tokens[0, 70] + 1) % 256))
    assert bool(jnp.array_equal(was[:, :70], now[:, :70]))
    assert float(jnp.abs(was[:, 70:] - now[:, 70:]).max()) > 1e-3
