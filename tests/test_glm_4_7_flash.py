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

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

import family_cases as fc
from benchmark import harness
from benchmark.reference import glm_4_7_flash as ref
from family_cases import *  # noqa: F401,F403  the shared cases
from jaxprs import pallas_calls
from kungfu_tpu.models import transformer
from kungfu_tpu.models.mixers import latent
from kungfu_tpu.models.transformer import TransformerConfig, init_transformer
from kungfu_tpu.ops import moe
from kungfu_tpu.telemetry import metrics


def _named_specs(specs):
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


FAMILY = fc.GLM_4_7_FLASH.with_cases(
    named_specs=_named_specs, tp_leaf=("layers", 1, "w_q_up"))
family, tiny_config, CONFIG = FAMILY.module, FAMILY.tiny_config, FAMILY.config


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


def test_main_and_mtp_losses_equal_the_references():
    state, sample = FAMILY.state(), FAMILY.sample()
    got = family.program_losses(CONFIG, state, sample)
    main, mtp = map(float, ref.losses(state, sample, **family._hyper(CONFIG)))
    assert got["main"] == pytest.approx(main, rel=1e-5)
    assert got["mtp"] == pytest.approx(mtp, rel=1e-5)
    assert abs(main - mtp) > 1e-3  # two losses, not one twice
    whole = float(jax.jit(family.loss_fn(CONFIG))(state, sample))
    assert whole == pytest.approx(main + 0.3 * mtp, rel=1e-6)


def test_the_losses_reach_the_metrics_registry():
    state, sample = FAMILY.state(), FAMILY.sample()
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


def test_no_layer_that_is_run_again_runs_its_forward_kernel_again():
    """The cell's own setting, the expert layers and the module's block run
    again in the backward pass: latent attention is the flash core's plain
    call, 4 heads on 4 with no window, and its output and row sums are kept
    under `_layer_again`'s two names. One forward kernel a scan body and
    one of the module's block, none in a recomputed part, and every number
    of the step is the number of the step that keeps its layers."""
    assert CONFIG["recomputed_layer_types"] == [family.SPARSE]
    state, sample = FAMILY.state(), FAMILY.sample()
    jaxpr = jax.make_jaxpr(jax.value_and_grad(family.loss_fn(CONFIG)))(
        state, sample).jaxpr
    # the dense scan's, the expert scan's and the module's
    assert [where for kernel, where in pallas_calls(jaxpr)
            if kernel == "_kernel"] == [False] * 3
    loss, grads = FAMILY.baseline()
    want_loss, want = FAMILY.baseline(recomputed=())
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
    layer = jax.tree.map(lambda a: a[0], FAMILY.state()["layers"][1])
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 64))
    got = jax.jit(lambda h, w: latent._latent_attention(h, w, mc))(h, layer)
    with jax.default_matmul_precision("highest"):
        want = ref.latent_attention(h, layer, family._hyper(config))
    assert got.shape == want.shape == (2, 64, 64)
    assert harness.relative_error(got, want) <= 1e-5
    # the one rotary key is every head's: another key for head 3 is seen
    w_q = layer["w_q_up"].reshape(24, 4, 32)
    assert float(jnp.abs(w_q[..., 24:]).max()) > 0


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One expert layer of 64 experts, 4 a token by sigmoid scores and a
    selection bias, renormalised and scaled by 1.8, cut into 8 shares of 8
    that each compute the shared expert (`fc.shares_add_up`)."""
    E, D, F, T = 64, 64, 32, 96
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
    cfg = TransformerConfig(
        d_model=D, d_ff=F, dtype=jnp.float32, ffn="moe", n_experts=E, top_k=4,
        gates="renorm", routed_scale=1.8, shared_ff=F, router_scores="sigmoid",
        router_bias=True)
    assert fc.shares_add_up(n, w, cfg, 8, want, chosen, shared) == 8


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


def test_the_initial_bias_is_small_and_moves_some_choices():
    config = tiny_config(hidden_size=256, q_lora_rank=32)
    state = family.init(config, FAMILY.seed)
    bias = np.asarray(state["layers"][1]["router_bias"])
    assert bias.shape == (2, 16) and 0.002 < np.abs(bias).mean() < 0.03
    stats = family.routing_stats(
        config, state, family.host_batch(config, FAMILY.seed, 0, 2))
    assert all(0 < n < 0.5 * 128 * 4 for n in stats["bias_moved"]), stats["bias_moved"]


def test_the_new_fields_refuse_what_they_cannot_mean():
    fc.refused("mixer", mixer="mla")
    fc.refused("latent_dims", mixer="latent", positions="rope")
    fc.refused("latent_dims", mixer="latent", positions="rope",
               latent_dims=(8, 8, 8, 3, 8))
    fc.refused("rope", mixer="latent", latent_dims=(8, 8, 8, 4, 12))
    fc.refused("rounds down", mixer="latent", positions="rope",
               latent_dims=(8, 8, 14, 30, 44))  # 44 * (30 / 44) < 30
    # value heads of their own size run on the flash core since PR 69
    assert TransformerConfig(mixer="latent", positions="rope", attn_core="flash",
                             latent_dims=(8, 8, 8, 4, 16)).latent_dims[4] == 16
    fc.refused("router_scores", router_scores="tanh")
    fc.refused("mtp_depth", mtp_depth=2)
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


def test_the_modules_scopes_are_under_its_own():
    """`mtp` with `mtp_proj`, the block's own scopes and `head_loss`: a
    scope at the top of the differentiated function is written `jvp(mtp)`,
    and `transpose(jvp(mtp))` in the backward pass."""
    lines = [line for line in FAMILY.lowered().splitlines() if "(mtp)" in line]
    assert any("transpose(jvp(mtp))" in line for line in lines)
    for scope in ("mtp_proj/", "attn/mla_up", "attn/attn_latent/attn_core",
                  "moe/moe_router", "head_loss"):
        assert any(scope in line for line in lines), scope
