"""Xing4.0's layers in `models/transformer.py` (PR 71): four residual streams
a position, mixed around every branch by maps the layer computes from them
(manifold-constrained hyper-connections, `ops/hyper_connections.py`), over
latent attention under YaRN with the softmax's scale times mscale^2, a dense
first layer and expert layers after it whose router scores are sigmoids chosen
under a selection bias, renormalised and scaled, over a share of the experts
beside a shared expert, and the multi-token-prediction module under streams
of its own; against the plain float32 reference
`benchmark/reference/xing4_0.py` at a small size on the CPU, the shares of
one expert layer under streams added up; each mechanism knocked out in turn
in `tests/test_xing4_0_faults.py`."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

import family_cases as fc
from benchmark import harness
from benchmark.reference import xing4_0 as ref
from family_cases import *  # noqa: F401,F403  the shared cases
from jaxprs import pallas_calls
from kungfu_tpu.models import transformer
from kungfu_tpu.models.mixers import latent
from kungfu_tpu.models.transformer import TransformerConfig
from kungfu_tpu.ops import hyper_connections as hc
from kungfu_tpu.telemetry import metrics


def _named_specs(specs):
    dense, sparse = specs["layers"]
    whole = PartitionSpec(None, None, None)
    for stack in (dense, sparse, {k: PartitionSpec(None, *v)
                                  for k, v in specs["mtp"]["layer"].items()}):
        # a branch's maps whole on every chip
        for branch in ("hc1", "hc2"):
            assert stack[f"{branch}_phi"] == whole
            assert stack[f"{branch}_a"] == stack[f"{branch}_b"] == (
                PartitionSpec(None, None))
        assert stack["w_q_up"] == stack["w_kv_up"] == PartitionSpec(None, None, "tp")
        assert stack["w_q_down"] == stack["w_kv_down"] == whole
        assert stack["wo"] == PartitionSpec(None, "tp", None)
    assert sparse["router_bias"] == PartitionSpec(None, None)
    assert dense["w_gate"] == PartitionSpec(None, None, "tp")
    assert sparse["w_gate"] == PartitionSpec(None, "ep", None, "tp")


FAMILY = fc.XING4_0.with_cases(
    named_specs=_named_specs, tp_leaf=("layers", 1, "w_q_up"))
family, tiny_config, CONFIG = FAMILY.module, FAMILY.tiny_config, FAMILY.config


def test_the_stacks_are_the_models_layers_in_order():
    assert family.layer_types(CONFIG) == ["dense", "sparse"]
    # the cell's five: one of the two leading dense layers, four expert layers
    assert family.layer_types(tiny_config(num_hidden_layers=5)) == [
        "dense", "sparse", "sparse", "sparse", "sparse"]
    assert family.layer_types(tiny_config(num_hidden_layers=5, dense_layers_run=2)
                              ) == ["dense", "dense", "sparse", "sparse", "sparse"]
    assert family.blocks(CONFIG) == ["dense", "sparse", "sparse"]  # the module's
    mc = family.model_config(CONFIG)
    assert [(kind.mixer, kind.ffn, kind.layer_remat, kind.streams, n)
            for kind, n in mc.stacks] == [("latent", "swiglu", True, 4, 1),
                                          ("latent", "moe", True, 4, 1)]
    assert (mc.streams, mc.hc_sinkhorn_iters, mc.hc_eps, mc.hc_clamp) == (
        4, 20, 1e-6, (-30.0, 30.0))
    # YaRN by 8 over 64 positions: the factor on cos and sin 1, the scores
    # times (0.1 ln 8 + 1)^2 / sqrt(32)
    assert mc.yarn == (8.0, 64, 32, 1, 1.0)
    assert mc.attention_multiplier == pytest.approx(
        (0.1 * np.log(8) + 1) ** 2 / np.sqrt(32))
    assert (mc.positions, mc.latent_dims, mc.attn_core) == (
        "rope", (24, 16, 24, 8, 16), "flash")
    assert (mc.router_scores, mc.router_bias, mc.gates, mc.routed_scale,
            mc.shared_ff, mc.mtp_depth, mc.mtp_weight) == (
        "sigmoid", True, "renorm", 2.0, 32, 1, 0.3)
    state = jax.eval_shape(lambda: family.init(CONFIG, 0))
    dense, sparse = ({k: v.shape for k, v in stack.items()}
                     for stack in state["layers"])
    module = {k: (1, *v.shape) for k, v in state["mtp"]["layer"].items()}
    for shapes in (dense, sparse, module):
        for branch in ("hc1", "hc2"):  # Phi (n C, 2 n + n^2), three gains, the biases
            assert shapes[f"{branch}_phi"] == (1, 4 * 64, 24)
            assert shapes[f"{branch}_a"] == (1, 3)
            assert shapes[f"{branch}_b"] == (1, 24)
        assert shapes["w_q_up"] == (1, 24, 4 * 32)
        assert shapes["w_kv_up"] == (1, 16, 4 * (24 + 16))
        assert shapes["wo"] == (1, 4 * 16, 64)
    assert dense["w_gate"] == (1, 64, 128) and "router" not in dense
    assert sparse["router"] == module["router"] == (1, 64, 16)
    assert sparse["w_gate"] == (1, 8, 64, 32)
    assert "lm_head" in state and "pos_embed" not in state
    # without the module, as the cell's file: no `mtp`, and S + 1 ids
    cut = tiny_config(num_nextn_predict_layers=0)
    assert "mtp" not in jax.eval_shape(lambda: family.init(cut, 0))
    assert family.host_batch(cut, 1, 0, 2).shape == (2, 65)
    assert FAMILY.sample().shape == (2, 66)


# --- the residual path by itself ---------------------------------------------

def _layer_and_streams(stack=1):
    mc = family.model_config(CONFIG).stacks[stack][0]
    layer = jax.tree.map(lambda a: a[0], FAMILY.state()["layers"][stack])
    X = jax.random.normal(jax.random.PRNGKey(4), (2, 64, 4, 64))
    return mc, layer, X


@pytest.mark.parametrize("branch", ["hc1", "hc2"])
def test_the_maps_and_the_mixings_against_a_position_at_a_time(branch):
    """The op on streams side by side, (B, S, n C), against the reference's
    maps a position at a time on (b, s, n, c), and the two mixings written as
    sums over the streams."""
    mc, layer, X = _layer_and_streams()
    flat = X.reshape(2, 64, 256)
    maps = jax.jit(lambda X, w: hc.maps(
        X, w[f"{branch}_phi"], w[f"{branch}_a"], w[f"{branch}_b"], 4, 20, 1e-6,
        (-30.0, 30.0)))(flat, layer)
    pre, post, res = ref.residual_maps(X, layer, branch, **family._hyper(CONFIG))
    np.testing.assert_allclose(maps.pre.transpose(1, 2, 0), pre, rtol=2e-5)
    np.testing.assert_allclose(maps.post.transpose(1, 2, 0), post, rtol=2e-5)
    np.testing.assert_allclose(maps.res.transpose(2, 3, 0, 1), res, rtol=1e-4,
                               atol=1e-6)
    # both parts of every map weigh in the state the cases run on: a
    # position's maps differ from the batch's mean, and H_res is neither
    # uniform nor the identity
    assert float(jnp.std(pre, axis=(0, 1)).min()) > 0.02
    assert float(jnp.std(res, axis=(0, 1)).max()) > 0.05
    assert 0.02 < float(jnp.mean(jnp.diagonal(res, axis1=2, axis2=3))) < 0.9
    assert float(jnp.std(jnp.mean(res, axis=(0, 1)))) > 0.1
    y = jax.random.normal(jax.random.PRNGKey(5), (2, 64, 64))
    u = hc.read(flat, maps.pre)
    np.testing.assert_allclose(u, jnp.einsum("bsj,bsjc->bsc", pre, X),
                               rtol=1e-4, atol=1e-5)
    out = hc.write(flat, y, maps.res, maps.post).reshape(2, 64, 4, 64)
    np.testing.assert_allclose(
        out, jnp.einsum("bsij,bsjc->bsic", res, X) + post[..., None] * y[:, :, None],
        rtol=1e-4, atol=1e-5)
    # entry by copies, exit by the sum
    np.testing.assert_array_equal(hc.enter(y, 4).reshape(2, 64, 4, 64),
                                  jnp.broadcast_to(y[:, :, None], (2, 64, 4, 64)))
    np.testing.assert_allclose(hc.leave(flat, 4), X.sum(axis=2), rtol=1e-6,
                               atol=1e-6)


def test_h_res_is_doubly_stochastic_after_twenty_passes_and_the_clamp_holds():
    """Logits of +-30 on and off a permutation's places, as they are and
    with noise of +-8: the rows sum to one (they are normalised last) and the
    columns within 1e-4; logits beyond the clamp are the clamp's."""
    key = jax.random.PRNGKey(6)
    perm = jnp.asarray([[1, 0, 3, 2], [2, 3, 0, 1], [0, 1, 2, 3], [3, 2, 1, 0]])
    hot = jax.nn.one_hot(perm, 4).transpose(1, 2, 0)  # (4, 4, 4 positions)
    logits = jnp.concatenate([
        60.0 * hot - 30.0,  # +30 on a permutation, -30 off it
        60.0 * hot - 30.0 + jax.random.uniform(key, (4, 4, 4), minval=-8, maxval=8),
        jax.random.uniform(jax.random.fold_in(key, 1), (4, 4, 56), minval=-3,
                           maxval=3)], axis=-1).reshape(4, 4, 8, 8)
    M = hc.sinkhorn(logits, 20, 1e-6, (-30.0, 30.0))
    assert float(M.min()) >= 0
    np.testing.assert_allclose(M.sum(axis=1), 1.0, atol=1e-5)  # rows
    columns = M.sum(axis=0).reshape(4, 64)
    np.testing.assert_allclose(columns[:, :8], 1.0, atol=1e-4)
    # logits of no permutation's own converge more slowly: 20 passes leave
    # 1e-3 at +-3, which `residual_stats` reads as `res_sum_error`
    np.testing.assert_allclose(columns[:, 8:], 1.0, atol=5e-3)
    # one pass leaves the columns far from one somewhere
    once = hc.sinkhorn(logits, 1, 1e-6, (-30.0, 30.0))
    assert float(jnp.abs(once.sum(axis=0) - 1).max()) > 0.05
    # beyond the clamp nothing moves: +-1000 is +-30, and nothing overflows
    far = jnp.where(jnp.abs(logits) >= 22.0, jnp.sign(logits) * 1000.0, logits)
    held = jnp.where(jnp.abs(logits) >= 22.0, jnp.sign(logits) * 30.0, logits)
    np.testing.assert_array_equal(hc.sinkhorn(far, 20, 1e-6, (-30.0, 30.0)),
                                  hc.sinkhorn(held, 20, 1e-6, (-30.0, 30.0)))
    assert bool(jnp.isfinite(hc.sinkhorn(far, 20, 1e-6, (-30.0, 30.0))).all())
    assert not bool(jnp.isfinite(hc.sinkhorn(far, 20, 1e-6, (-2e3, 2e3))).all())


def test_one_stream_builds_no_map_and_is_the_program_it_was():
    """`streams` 1: no map leaf, no `hc` scope and a (B, S, D) scan carry in
    the GLM-4.7-Flash small configuration's loss; the same configuration
    under two streams has all three."""
    glm = fc.GLM_4_7_FLASH
    for streams, carried in ((1, (2, 64, 64)), (2, (2, 64, 128))):
        mc = dataclasses.replace(glm.module.model_config(glm.config),
                                 streams=streams)
        state = jax.eval_shape(
            lambda: transformer.init_transformer(jax.random.PRNGKey(0), mc))
        names = {jax.tree_util.keystr(path)
                 for path, _ in jax.tree_util.tree_leaves_with_path(state)}
        assert any("hc1_phi" in name for name in names) == (streams > 1)
        jaxpr = jax.make_jaxpr(
            lambda p, b: transformer.transformer_loss(p, b, mc))(state, glm.sample())
        scopes = {str(eqn.source_info.name_stack)
                  for eqn in harness.eqns_of(jaxpr.jaxpr)}
        assert any("hc" in scope.split("/") for scope in scopes) == (streams > 1)
        carries = {eqn.outvars[0].aval.shape for eqn in jaxpr.jaxpr.eqns
                   if eqn.primitive.name == "scan"}
        assert carries == {carried}


def test_the_eight_shares_of_a_layer_under_streams_add_up():
    """Model-configs guide, section 4, on a layer under streams: the expert
    branch's input is u = sum_j H_pre[j] X[j] of four streams, its normed
    rows go through 64 experts, 4 a token by sigmoid scores and a selection
    bias, renormalised and scaled by 2, cut into 8 shares of 8 that each
    compute the shared expert (`fc.shares_add_up`); and what the streams take
    of the sum is the uncut layer's X'."""
    E, D, F, T, n = 64, 32, 16, 64, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 14)
    w = {"router": 0.5 * jax.random.normal(ks[1], (D, E)),
         "router_bias": 0.3 * jax.random.normal(ks[8], (E,)),
         "w_gate": 0.3 * jax.random.normal(ks[2], (E, D, F)),
         "w_up": 0.3 * jax.random.normal(ks[3], (E, D, F)),
         "w_down": 0.3 * jax.random.normal(ks[4], (E, F, D)),
         "shared_gate": 0.1 * jax.random.normal(ks[5], (D, F)),
         "shared_up": 0.1 * jax.random.normal(ks[6], (D, F)),
         "shared_down": 0.1 * jax.random.normal(ks[7], (F, D)),
         "ln2_scale": 1.0 + 0.3 * jax.random.normal(ks[9], (D,)),
         "hc2_phi": 0.2 * jax.random.normal(ks[10], (n * D, 24)),
         "hc2_a": jnp.asarray([0.7, 0.5, 0.9]),
         "hc2_b": jax.random.normal(ks[11], (24,))}
    X = jax.random.normal(ks[0], (1, T, n, D))
    hyper = dict(top_k=4, routed_scale=2.0, first_held=0, eps=1e-6,
                 sinkhorn_iters=20, hc_eps=1e-6, clamp=(-30.0, 30.0))
    cfg = TransformerConfig(
        d_model=D, d_ff=F, dtype=jnp.float32, ffn="moe", n_experts=E, top_k=4,
        gates="renorm", routed_scale=2.0, shared_ff=F, router_scores="sigmoid",
        router_bias=True, streams=n, mixer="none")
    u, maps = transformer._read(X.reshape(1, T, n * D), w, "hc2", cfg)
    pre, post, res = ref.residual_maps(X, w, "hc2", **hyper)
    np.testing.assert_allclose(u, jnp.einsum("bsj,bsjc->bsc", pre, X), rtol=1e-4,
                               atol=1e-5)
    rows = ref._rms(u[0], w["ln2_scale"], 1e-6)
    want, chosen = ref.experts(rows, w, hyper)
    shared = ref._swiglu(rows, w["shared_gate"], w["shared_up"], w["shared_down"])
    assert fc.shares_add_up(rows, w, cfg, 8, want, chosen, shared) == 8
    # the whole branch of the uncut layer, through the program's own path
    whole, _ = transformer._feed_forward(X.reshape(1, T, n * D), w, cfg)
    np.testing.assert_allclose(
        whole.reshape(1, T, n, D),
        jnp.einsum("bsij,bsjc->bsic", res, X) + post[..., None] * want[None, :, None],
        rtol=2e-4, atol=2e-5)


# --- the mixer by itself ------------------------------------------------------

def test_latent_attention_under_yarn_against_the_reference():
    """The mixer by itself: the rotated features at YaRN's blended
    frequencies, the scores times mscale^2 / sqrt(32), on the flash core
    (interpreted), against the reference's softmax over the full score
    matrix; and the same weights without YaRN give another answer."""
    mc, layer, _ = _layer_and_streams()
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 64))
    got = jax.jit(lambda h, w: latent._latent_attention(h, w, mc))(h, layer)
    with jax.default_matmul_precision("highest"):
        want = ref.latent_attention(h, layer, family._hyper(CONFIG))
        plain = ref.latent_attention(h, layer, {**family._hyper(CONFIG), "yarn": None})
    assert got.shape == want.shape == (2, 64, 64)
    assert harness.relative_error(got, want) <= 1e-5
    assert harness.relative_error(plain, want) > 0.05
    # the ramp: of the 4 frequencies the first keeps its own, the second
    # blends and the last two are their own over 8
    freq, factor = ref.yarn_frequencies(8, 1e4, family.yarn_of(CONFIG))
    ratio = np.asarray(freq) / 1e4 ** (-np.arange(0, 8, 2) / 8)
    assert factor == 1.0
    np.testing.assert_allclose(ratio, [1, (1 + 1 / 8) / 2, 1 / 8, 1 / 8], rtol=1e-6)
    assert ref.softmax_scale(32, family.yarn_of(CONFIG)) == pytest.approx(
        mc.attention_multiplier)
    jaxpr = jax.make_jaxpr(lambda h, w: latent._latent_attention(h, w, mc))(h, layer)
    # q's and k's rotary pass and the flash core, and no other kernel
    assert {name for name, _ in pallas_calls(jaxpr.jaxpr)} == {"rotary", "_kernel"}


# --- beside the step ----------------------------------------------------------

def test_the_residual_counters_are_read_back_from_the_registry():
    """`residual_stats` on the trained-like state, a row a layer and branch,
    the module's block last, and `record_residual`'s four gauges."""
    mc = family.model_config(CONFIG)
    stats = family.residual_stats(CONFIG, FAMILY.state(), FAMILY.sample())
    assert stats["layer"] == [0, 0, 1, 1, 2, 2] and stats["branch"] == [1, 2] * 3
    assert all(0.02 < d < 0.9 for d in stats["res_diagonal"])
    assert all(0 < e < 0.2 for e in stats["res_sum_error"])
    assert all(0.1 < p < 0.9 for p in stats["pre_mean"])
    assert all(0.3 < p < 1.7 for p in stats["post_mean"])
    full = jax.jit(lambda p, t: transformer.residual_stats(p, t, mc))(
        FAMILY.state(), FAMILY.sample()[:, :-1])
    registry = metrics.Registry()
    transformer.record_residual(full, registry)
    text = registry.render()
    for layer in range(3):
        for branch in ("mixer", "ffn"):
            for gauge in ("res_diagonal", "res_sum_error", "pre_mean", "post_mean"):
                assert (f'kungfu_hc_{gauge}{{layer="{layer}",branch="{branch}"}}'
                        in text)
    at = stats["res_diagonal"][3]
    assert f'kungfu_hc_res_diagonal{{layer="1",branch="ffn"}} {at:.4f}'[:-1] in text
    one = dataclasses.replace(mc, streams=1)
    with pytest.raises(ValueError, match="one residual stream"):
        transformer.residual_stats(FAMILY.state(), FAMILY.sample()[:, :-1], one)


def test_the_new_fields_refuse_what_they_cannot_mean():
    fc.refused("one residual stream or more", streams=0)
    fc.refused("built for the layer scan", streams=4, loop_steps=2)
    fc.refused("built for the layer scan", streams=4, ffn="moe", n_experts=4,
               top_k=2, router_input="layer")
    fc.refused("built for the layer scan", streams=4, sparse_index=(2, 8, 16),
               positions="rope", attn_core="flash", head_size=16, n_kv_heads=2,
               qk_norm=True)
    # a scale of the scores' own is the flash core's
    fc.refused("flash", mixer="latent", positions="rope",
               latent_dims=(8, 8, 8, 4, 8), attention_multiplier=0.2)
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=1,
                            max_seq=64, streams=2)
    params = transformer.init_transformer(jax.random.PRNGKey(0), cfg)
    with pytest.raises(NotImplementedError, match="normal path"):
        transformer._block(jnp.zeros((1, 64, 64)),
                           jax.tree.map(lambda a: a[0], params["layers"]), cfg)
    with pytest.raises(ValueError, match="as published"):
        family.model_config(tiny_config(hc_mult=1))
    with pytest.raises(ValueError, match="as published"):
        family.model_config(tiny_config(dense_layers_run=3))


def test_no_position_reads_a_later_one():
    """The maps are a position's own and the mixer is causal: another id at
    position 40 (the second flash block) moves no logit before it and moves
    those from it on, in the stack and in the module's block alike."""
    mc = family.model_config(CONFIG)
    state, tokens = FAMILY.state(), jnp.asarray(FAMILY.sample()[:1, :-2])
    apply = jax.jit(lambda p, t: transformer.transformer_apply(p, t, mc))
    was = apply(state, tokens)
    now = apply(state, tokens.at[0, 40].set((tokens[0, 40] + 1) % 256))
    assert bool(jnp.array_equal(was[:, :40], now[:, :40]))
    assert float(jnp.abs(was[:, 40:] - now[:, 40:]).max()) > 1e-3
