"""GLM-4.7-Flash's layers in `models/transformer.py` (PR 41): latent
attention (a q latent and a key/value latent with their norms, one rotary key
shared by all heads), a dense first layer and expert layers after it whose
router scores are sigmoids chosen under a selection bias, renormalised and
scaled, over a share of the experts beside a shared expert, and a
multi-token-prediction module with a second loss on the shared embedding and
head; against the plain float32 reference `benchmark/reference/
glm_4_7_flash.py` at a small size on the CPU, the shares of one expert layer
added up; each mechanism knocked out in turn in
`tests/test_glm_4_7_flash_faults.py`."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from benchmark import harness, manifest as mf
from benchmark.families import glm4_moe_lite as family
from benchmark.reference import glm_4_7_flash as ref
from kungfu_tpu.models import transformer
from kungfu_tpu.models.transformer import (TransformerConfig, init_transformer,
                                           param_pspecs)
from kungfu_tpu.ops import moe
from kungfu_tpu.telemetry import metrics
from test_flash_attention import _pallas_calls

# the cell's stack in small: a dense layer and two expert layers, then the
# multi-token-prediction module; hidden 64; 4 heads of 24 unrotated + 8
# rotated q/k features and 32 value features, latents of 24 and 16; 16
# experts of width 32 of which numbers 4 to 11 are held, 4 a token; vocabulary
# 256; 64 positions (66 ids); flash in interpret mode; the routers trained,
# so that every leaf but the bias has a gradient to compare
TINY = dict(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
            q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=24,
            qk_rope_head_dim=8, v_head_dim=32, n_routed_experts=8,
            first_expert_held=4, published={"n_routed_experts": 16},
            vocab_size=256, sequence_length=64, flash_blocks=[32, 32],
            flash_interpret=True, compute_dtype="float32", routers_trained=True)
SEED = 5


def real_config():
    """The configuration file as it is."""
    with open(os.path.join(mf.BENCH_DIR, "configs", "glm_4_7_flash.json")) as f:
        return json.load(f)


def tiny_config(**changes):
    config = real_config()
    config.update(TINY)
    config.update(changes)
    return config


CONFIG = tiny_config()

_SCALES = {"w_q_down": 6.0, "w_q_up": 6.0, "w_kv_down": 6.0, "w_kv_up": 6.0,
           "router": 20.0, "router_bias": 40.0, "w_gate": 8.0, "w_up": 8.0,
           "w_down": 8.0, "shared_gate": 3.0, "shared_up": 3.0,
           "shared_down": 3.0}
_NORMS = ("ln1_scale", "ln2_scale", "q_latent_norm", "kv_latent_norm")


def _trained(layer, key):
    layer = {name: leaf * _SCALES.get(name, 1.0) for name, leaf in layer.items()}
    for i, name in enumerate(_NORMS):
        layer[name] = layer[name] + 0.4 * jax.random.normal(
            jax.random.fold_in(key, i), layer[name].shape)
    return layer


def _state(seed=SEED, config=CONFIG):
    """A state as after some training, so that no fault can hide behind the
    initial values: norm scales off one, sharp attention, a router with
    preferences and a bias that moves choices, experts that weigh, a
    projection of the module that mixes both of its halves."""
    state = family.init(config, seed)
    key = jax.random.PRNGKey(seed + 100)
    stacks = tuple(_trained(stack, jax.random.fold_in(key, 10 + s))
                   for s, stack in enumerate(state["layers"]))
    mtp = state["mtp"]
    mtp = {**mtp, "eh_proj": 4.0 * mtp["eh_proj"],
           "layer": _trained(mtp["layer"], jax.random.fold_in(key, 20)),
           **{name: mtp[name] + 0.4 * jax.random.normal(
               jax.random.fold_in(key, 30 + i), mtp[name].shape)
              for i, name in enumerate(("enorm_scale", "hnorm_scale", "ln_f_scale"))}}
    return {**state, "layers": stacks, "mtp": mtp,
            "ln_f_scale": state["ln_f_scale"] + 0.3 * jax.random.normal(
                key, state["ln_f_scale"].shape)}


@pytest.fixture
def fresh_traces():
    """`jax.jit` and `jax.checkpoint` keep the traces of the functions a
    test patches: none from before it, and none of its own after it."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _sample(n=2):
    return family.host_batch(CONFIG, SEED, 0, n)


@functools.lru_cache(maxsize=None)
def _reference():
    """The reference's loss and gradients on `_state()` and `_sample()`,
    computed once for the tests of this module and of the faults'."""
    return family.reference_loss_and_grads(CONFIG, _state(), _sample())


def _reference_parts(config, state, sample):
    return [float(x) for x in ref.losses(state, sample, **family._hyper(config))]


def test_the_stacks_are_the_models_layers_in_order():
    assert family.layer_types(CONFIG) == [family.DENSE, family.SPARSE, family.SPARSE]
    assert family.blocks(CONFIG) == [family.DENSE] + [family.SPARSE] * 3
    mc = family.model_config(CONFIG)
    assert [(kind.mixer, kind.ffn, kind.layer_remat, n) for kind, n in mc.stacks] == [
        ("latent", "swiglu", False, 1), ("latent", "moe", True, 2)]
    assert (mc.mtp_kind.ffn, mc.mtp_kind.n_layers, mc.mtp_depth, mc.mtp_weight) == (
        "moe", 1, 1, 0.3)
    assert (mc.router_scores, mc.router_bias, mc.gates, mc.routed_scale) == (
        "sigmoid", True, "renorm", 1.8)
    state = jax.eval_shape(lambda: family.init(CONFIG, 0))
    dense, sparse = ({k: v.shape for k, v in stack.items()}
                     for stack in state["layers"])
    module = {k: v.shape for k, v in state["mtp"]["layer"].items()}
    for shapes, lead in ((dense, (1,)), (sparse, (2,)), (module, ())):
        # a head's q: 24 unrotated and 8 rotated; the latent and the one
        # rotary key; a head's 24 key features and 32 value features
        assert shapes["w_q_down"] == lead + (64, 24)
        assert shapes["w_q_up"] == lead + (24, 4 * 32)
        assert shapes["w_kv_down"] == lead + (64, 16 + 8)
        assert shapes["w_kv_up"] == lead + (16, 4 * (24 + 32))
        assert shapes["wo"] == lead + (4 * 32, 64)
        assert shapes["q_latent_norm"] == lead + (24,)
        assert shapes["kv_latent_norm"] == lead + (16,)
        assert not {"wq", "wk", "wv", "wqkv"} & set(shapes)
    assert dense["w_gate"] == (1, 64, 128) and "router" not in dense
    for shapes, lead in ((sparse, (2,)), (module, ())):
        assert shapes["router"] == lead + (64, 16)
        assert shapes["router_bias"] == lead + (16,)
        assert shapes["w_gate"] == lead + (8, 64, 32)
        assert shapes["shared_gate"] == lead + (64, 32)
    assert state["mtp"]["eh_proj"].shape == (128, 64)
    assert {k for k in state["mtp"]} == {"enorm_scale", "hnorm_scale", "eh_proj",
                                         "layer", "ln_f_scale"}
    assert "lm_head" in state and "lm_head" not in state["mtp"]


def test_param_pspecs_cover_every_leaf():
    mc = family.model_config(CONFIG)
    state = jax.eval_shape(lambda: family.init(CONFIG, 0))
    specs = param_pspecs(mc)
    assert jax.tree.structure(
        jax.tree.map(lambda s: 0, specs,
                     is_leaf=lambda s: isinstance(s, PartitionSpec))
    ) == jax.tree.structure(jax.tree.map(lambda s: 0, state))
    for spec, leaf in zip(
            jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, PartitionSpec)),
            jax.tree.leaves(state)):
        assert len(spec) <= leaf.ndim, (spec, leaf.shape)
    sparse, module = specs["layers"][1], specs["mtp"]["layer"]
    # up-projections a head at a time and W_o over tp, as wq and wo are; the
    # down-projections, the latents' norms and the bias whole
    assert sparse["w_q_up"] == sparse["w_kv_up"] == PartitionSpec(None, None, "tp")
    assert sparse["wo"] == PartitionSpec(None, "tp", None)
    assert sparse["w_q_down"] == sparse["w_kv_down"] == PartitionSpec(None, None, None)
    assert sparse["q_latent_norm"] == sparse["router_bias"] == PartitionSpec(None, None)
    assert module["w_q_up"] == PartitionSpec(None, "tp")
    assert module["wo"] == PartitionSpec("tp", None)
    assert module["w_gate"] == PartitionSpec("ep", None, "tp")
    assert specs["mtp"]["eh_proj"] == PartitionSpec(None, None)


def test_a_tp_mesh_of_two_gives_the_same_loss():
    from kungfu_tpu.parallel import make_mesh
    from kungfu_tpu.parallel.sharded import shard_params

    config = tiny_config(attention_core="dense")
    mc = family.model_config(config)
    state, sample = _state(config=config), _sample()
    loss = family.loss_fn(config)
    want = float(jax.jit(loss)(state, sample))
    mesh = make_mesh({"dp": 1, "tp": 2, "ep": 1}, devices=jax.devices()[:2])
    placed = shard_params(state, mesh, param_pspecs(mc))
    assert len(placed["layers"][1]["w_q_up"].sharding.device_set) == 2
    with mesh:
        got = float(jax.jit(loss)(placed, sample))
    assert got == pytest.approx(want, rel=1e-5)


def test_float32_program_equals_the_reference():
    state, sample = _state(), _sample()
    loss, grads = family.program_loss_and_grads(CONFIG)(state, sample)
    want_loss, want = _reference()
    assert abs(float(loss) - float(want_loss)) <= 1e-5 * abs(float(want_loss))
    assert harness.relative_error(grads, want) <= 1e-4
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:  # a constant of the loss, in both
            assert not np.asarray(g).any() and not np.asarray(w).any(), name
            continue
        assert float(jnp.abs(g).max()) > 0, name
        assert harness.relative_error(g, w) <= 1e-3, name
    assert family.differing_choices(CONFIG, state, sample) == 0


def test_main_and_mtp_losses_equal_the_references():
    state, sample = _state(), _sample()
    got = family.program_losses(CONFIG, state, sample)
    main, mtp = _reference_parts(CONFIG, state, sample)
    assert got["main"] == pytest.approx(main, rel=1e-5)
    assert got["mtp"] == pytest.approx(mtp, rel=1e-5)
    assert abs(main - mtp) > 1e-3  # two losses, not one twice
    whole = float(jax.jit(family.loss_fn(CONFIG))(state, sample))
    assert whole == pytest.approx(main + 0.3 * mtp, rel=1e-6)


def test_the_losses_reach_the_metrics_registry():
    state, sample = _state(), _sample()
    mc = family.model_config(CONFIG)
    losses = jax.jit(lambda p, b: transformer.transformer_losses(p, b, mc))(
        state, sample)
    registry = metrics.Registry()
    transformer.record_losses(losses, registry)
    text = registry.render()
    assert f"kungfu_lm_loss {float(losses['main'])}"[:20] in text
    assert "kungfu_mtp_loss " in text
    plain = TransformerConfig.tiny()
    only = transformer.transformer_losses(
        init_transformer(jax.random.PRNGKey(0), plain),
        jnp.zeros((2, 17), jnp.int32), plain)
    assert set(only) == {"main"}


def test_bfloat16_program_is_within_the_familys_tolerances():
    config = tiny_config(compute_dtype="bfloat16")
    state, sample = family.init(config, SEED), _sample()
    loss, grads = family.program_loss_and_grads(config)(state, sample)
    want_loss, want = family.reference_loss_and_grads(CONFIG, state, sample)
    assert abs(float(loss) - float(want_loss)) <= family.LOSS_RTOL * abs(float(want_loss))
    error = harness.relative_error(grads, want)
    assert 1e-4 < error <= family.GRAD_RTOL, error
    assert 0 < family.LOSS_RTOL < family.GRAD_RTOL < 0.1


@pytest.mark.parametrize("recomputed", [[], [family.DENSE, family.SPARSE]])
def test_the_recomputed_layers_change_no_number(recomputed):
    """`recomputed_layer_types` says what the backward pass keeps, not what
    it computes, in the stack and in the module's block alike."""
    state, sample = _state(), _sample()
    other = tiny_config(recomputed_layer_types=recomputed)
    assert family.model_config(other).mtp_kind.layer_remat == bool(recomputed)
    loss, grads = family.program_loss_and_grads(CONFIG)(state, sample)
    want_loss, want = family.program_loss_and_grads(other)(state, sample)
    assert abs(float(loss) - float(want_loss)) <= 1e-6 * abs(float(want_loss))
    assert harness.relative_error(grads, want) <= 1e-5


def test_no_layer_that_is_run_again_runs_its_forward_kernel_again():
    """The cell's own setting, the expert layers and the module's block run
    again in the backward pass: latent attention is the flash core's plain
    call, 4 heads on 4 with no window, and its output and row sums are kept
    under `_layer_again`'s two names. One forward kernel a scan body and
    one of the module's block, none in a recomputed part, and every number
    of the step is the number of the step that keeps its layers."""
    assert CONFIG["recomputed_layer_types"] == [family.SPARSE]
    state, sample = _state(), _sample()
    jaxpr = jax.make_jaxpr(jax.value_and_grad(family.loss_fn(CONFIG)))(
        state, sample).jaxpr
    # the dense scan's, the expert scan's and the module's
    assert [where for kernel, where in _pallas_calls(jaxpr)
            if kernel == "_kernel"] == [False] * 3
    kept = tiny_config(recomputed_layer_types=[])
    loss, grads = family.program_loss_and_grads(CONFIG)(state, sample)
    want_loss, want = family.program_loss_and_grads(kept)(state, sample)
    assert float(loss) == float(want_loss)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want), strict=True):
        assert bool(jnp.array_equal(g, w)), jax.tree_util.keystr(path)


@pytest.mark.parametrize("core", ["flash", "dense"])
def test_latent_attention_alone_against_a_plain_softmax_over_materialised_heads(core):
    """The mixer by itself, on hidden states and weights that matter: the
    program's (the rotated features first inside a head, one rotary pass
    that lays q and k out a head, the core the configuration names) against
    the reference's (the published order, every head's k and v written out,
    a softmax over the full score matrix)."""
    config = tiny_config(attention_core=core)
    mc = family.model_config(config).stacks[1][0]
    layer = jax.tree.map(lambda a: a[0], _state()["layers"][1])
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 64))
    got = jax.jit(lambda h, w: transformer._latent_attention(h, w, mc))(h, layer)
    with jax.default_matmul_precision("highest"):
        want = ref.latent_attention(h, layer, family._hyper(config))
    assert got.shape == want.shape == (2, 64, 64)
    assert harness.relative_error(got, want) <= 1e-5
    # the one rotary key is every head's: another key for head 3 is seen
    w_q = layer["w_q_up"].reshape(24, 4, 32)
    assert float(jnp.abs(w_q[..., 24:]).max()) > 0


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Model-configs guide, section 4: one expert layer of 64 experts, 4 a
    token by sigmoid scores and a selection bias, renormalised and scaled by
    1.8, cut into 8 shares of 8. Each share routes over all 64 and computes
    its own experts' part and the shared expert, which every chip computes
    alike; the parts of all 8, the shared expert counted once, are what the
    uncut reference gives for the whole layer."""
    E, held, D, F, T = 64, 8, 64, 32, 96
    ks = jax.random.split(jax.random.PRNGKey(3), 10)
    n = jax.random.normal(ks[0], (T, D))
    w = {"router": 0.5 * jax.random.normal(ks[1], (D, E)),
         "router_bias": 0.3 * jax.random.normal(ks[8], (E,)),
         "w_gate": 0.3 * jax.random.normal(ks[2], (E, D, F)),
         "w_up": 0.3 * jax.random.normal(ks[3], (E, D, F)),
         "w_down": 0.3 * jax.random.normal(ks[4], (E, F, D)),
         "shared_gate": 0.3 * jax.random.normal(ks[5], (D, F)),
         "shared_up": 0.3 * jax.random.normal(ks[6], (D, F)),
         "shared_down": 0.3 * jax.random.normal(ks[7], (F, D))}
    want, chosen = ref.experts(n, w, dict(top_k=4, routed_scale=1.8, first_held=0))
    shared = ref._swiglu(n, w["shared_gate"], w["shared_up"], w["shared_down"])

    def share(first):
        cfg = TransformerConfig(
            d_model=D, d_ff=F, dtype=jnp.float32, ffn="moe", n_experts=E,
            top_k=4, gates="renorm", routed_scale=1.8,
            experts_held=(first, held), shared_ff=F, router_scores="sigmoid",
            router_bias=True)
        mine = {**w, **{name: w[name][first:first + held]
                        for name in ("w_gate", "w_up", "w_down")}}
        return transformer._expert_layer(n, mine, cfg)

    parts = [share(first) for first in range(0, E, held)]
    assert len(parts) == 8
    total = sum(y for y, _ in parts) - 7 * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    counts = np.concatenate([np.asarray(aux.counts) for _, aux in parts])
    assert counts.tolist() == np.bincount(np.asarray(chosen).ravel(),
                                          minlength=E).tolist()
    assert counts.sum() == 4 * T
    # every share sees the same router: the bias moved the same choices
    moved = {int(aux.bias_moved) for _, aux in parts}
    assert len(moved) == 1 and 0 < moved.pop() < 4 * T
    # one share alone is not the layer: the cut is real
    assert not np.allclose(np.asarray(parts[0][0]), np.asarray(want), atol=1e-2)


def test_the_bias_moves_the_choice_and_never_the_weight():
    T, D, E, k = 128, 32, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(ks[0], (T, D))
    router = 0.3 * jax.random.normal(ks[1], (D, E))
    bias = 0.2 * jax.random.normal(ks[2], (E,))
    _, scores, plain_top, plain_idx = moe.route(x, router, k, "sigmoid")
    _, same_scores, top, idx = moe.route(x, router, k, "sigmoid", bias)
    np.testing.assert_array_equal(scores, same_scores)
    np.testing.assert_array_equal(scores, jax.nn.sigmoid(x @ router))
    # the choice is the top k of scores + bias
    want_idx = np.argsort(-np.asarray(scores + bias), axis=-1)[:, :k]
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                  np.sort(want_idx, -1))
    assert (np.sort(np.asarray(idx), -1) != np.sort(np.asarray(plain_idx), -1)).any()
    # the weights are the chosen experts' scores, without the bias
    np.testing.assert_array_equal(top, np.take_along_axis(np.asarray(scores),
                                                          np.asarray(idx), -1))
    moved = int(moe.bias_moved(scores, idx))
    by_hand = sum(len(set(a) - set(b)) for a, b in
                  zip(np.asarray(idx).tolist(), np.asarray(plain_idx).tolist()))
    assert moved == by_hand > 0
    assert int(moe.bias_moved(scores, plain_idx)) == 0

    # the loss is constant in the bias where the choice stands, and its
    # gradient in it is zero everywhere
    experts = tuple(0.3 * jax.random.normal(jax.random.fold_in(ks[0], i), s)
                    for i, s in enumerate([(E, D, 8), (E, D, 8), (E, 8, D)]))

    def out(bias):
        y, _ = moe.moe_ffn(x, router, experts, top_k=k,
                           gates=moe.scaled(moe.renormalised_gates, 1.8),
                           expert_fn=moe.swiglu_experts, scores="sigmoid",
                           bias=bias)
        return jnp.sum(jnp.square(y))

    assert not np.asarray(jax.grad(out)(bias)).any()
    assert float(out(bias)) == pytest.approx(float(out(bias + 7.0)), rel=1e-6)
    assert float(out(bias)) != pytest.approx(float(out(jnp.zeros(E))), rel=1e-3)


def test_the_share_drops_nothing_and_counts_what_the_bias_moved():
    state, sample = _state(), _sample()
    stats = family.routing_stats(CONFIG, state, sample)
    # two expert layers and the module's, the last row
    assert stats["dropped"] == [0, 0, 0] and stats["layer"] == [1, 2, 3]
    counts = np.asarray(stats["counts"])
    assert counts.shape == (3, 8)
    assert stats["held_rows"] == counts.sum(axis=1).tolist()
    # 4 of 16 experts a token, 8 held: half of the choices, about
    assert 0.3 < counts.sum() / (3 * 128 * 4) < 0.7
    assert len(stats["bias_moved"]) == 3 and all(
        0 < n < 128 * 4 for n in stats["bias_moved"])
    mc = family.model_config(CONFIG)
    full = jax.jit(lambda p, t: transformer.routing_stats(p, t, mc))(
        state, sample[:, :-1])
    registry = metrics.Registry()
    transformer.record_routing(full, registry)
    text = registry.render()
    assert 'kungfu_moe_bias_moved_token_choices{layer="3"}' in text
    assert 'kungfu_moe_dropped_token_choices{layer="1"} 0' in text
    assert 'kungfu_moe_held_rows{layer="2"}' in text


def test_the_initial_bias_is_small_and_moves_some_choices():
    config = tiny_config(hidden_size=256, q_lora_rank=32)
    state = family.init(config, SEED)
    bias = np.asarray(state["layers"][1]["router_bias"])
    assert bias.shape == (2, 16) and 0.002 < np.abs(bias).mean() < 0.03
    stats = family.routing_stats(config, state, family.host_batch(config, SEED, 0, 2))
    assert all(0 < n < 0.5 * 128 * 4 for n in stats["bias_moved"]), stats["bias_moved"]


def test_the_new_fields_refuse_what_they_cannot_mean():
    with pytest.raises(ValueError, match="mixer"):
        TransformerConfig(mixer="mla")
    with pytest.raises(ValueError, match="latent_dims"):
        TransformerConfig(mixer="latent", positions="rope")
    with pytest.raises(ValueError, match="latent_dims"):
        TransformerConfig(mixer="latent", positions="rope",
                          latent_dims=(8, 8, 8, 3, 8))
    with pytest.raises(ValueError, match="rope"):
        TransformerConfig(mixer="latent", latent_dims=(8, 8, 8, 4, 12))
    with pytest.raises(ValueError, match="rounds down"):  # 44 * (30 / 44) < 30
        TransformerConfig(mixer="latent", positions="rope",
                          latent_dims=(8, 8, 14, 30, 44))
    with pytest.raises(ValueError, match="one head size"):
        TransformerConfig(mixer="latent", positions="rope", attn_core="flash",
                          latent_dims=(8, 8, 8, 4, 16))
    with pytest.raises(ValueError, match="router_scores"):
        TransformerConfig(router_scores="tanh")
    with pytest.raises(ValueError, match="mtp_depth"):
        TransformerConfig(mtp_depth=2)
    with pytest.raises(ValueError, match="scores"):
        moe.route(jnp.zeros((4, 8)), jnp.zeros((8, 4)), 2, "tanh")
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=1,
                            mtp_depth=1, mtp_weight=0.5, max_seq=16)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="ids"):
        transformer.transformer_loss(
            params, (jnp.zeros((1, 8), jnp.int32), jnp.zeros((1, 8), jnp.int32)), cfg)
    # the module stands on the repo's own block too: 10 ids, 8 positions
    loss = transformer.transformer_loss(params, jnp.zeros((1, 10), jnp.int32), cfg)
    assert np.isfinite(float(loss))


def test_the_new_scopes_are_in_the_program():
    """`attn` with `mla_down`, `mla_norm`, `mla_up`, `rope` and `attn_latent`
    > `attn_core` inside it; `mtp` with `mtp_proj`, the block's own scopes
    and `head_loss`; `moe` > `moe_router` as it was: what the cell's
    per-layer metrics read."""
    state = jax.eval_shape(lambda: family.init(CONFIG, 0))
    text = family.program_loss_and_grads(CONFIG).lower(
        state, _sample()).as_text(debug_info=True)
    for scope in ("attn/mla_down", "attn/mla_norm", "attn/mla_up", "attn/rope",
                  "attn/attn_latent/attn_core", "moe/moe_router",
                  "moe/moe_shared", "moe/moe_dispatch", "moe_experts/",
                  "moe_combine/", "head_loss", "mtp_proj/"):
        assert scope in text, scope
    # a scope at the top of the differentiated function is written
    # `jvp(mtp)`, and `transpose(jvp(mtp))` in the backward pass
    lines = [line for line in text.splitlines() if "(mtp)" in line]
    assert any("transpose(jvp(mtp))" in line for line in lines)
    for scope in ("mtp_proj/", "attn/mla_up", "attn/attn_latent/attn_core",
                  "moe/moe_router", "head_loss"):
        assert any(scope in line for line in lines), scope


def test_routers_that_are_not_trained_get_no_gradient_and_change_no_other():
    """The cell's own setting: the routers' matrices are constants of the
    loss, in the program and in the reference alike, the module's among
    them; every other leaf's gradient is what it is with the routers
    trained."""
    config = tiny_config(routers_trained=False)
    assert real_config()["routers_trained"] is False
    state, sample = _state(), _sample()
    loss, grads = family.program_loss_and_grads(config)(state, sample)
    want_loss, want = family.reference_loss_and_grads(config, state, sample)
    trained_loss, trained = _reference()
    assert float(want_loss) == float(trained_loss)
    assert abs(float(loss) - float(want_loss)) <= 1e-5 * abs(float(want_loss))
    assert harness.relative_error(grads, want) <= 1e-4
    layers = lambda tree: [tree["layers"][1], tree["mtp"]["layer"]]
    for got, reference, full in zip(layers(grads), layers(want), layers(trained)):
        assert not np.asarray(got["router"]).any()
        assert not np.asarray(reference["router"]).any()
        assert np.asarray(full["router"]).any()
        for name in reference:
            if name != "router":
                np.testing.assert_array_equal(reference[name], full[name])
