"""Qwen3-Next's layers in `models/transformer.py` (PR 36): Gated DeltaNet
mixers and a gated softmax-attention layer in one stack (D, D, D, A), norms
with the scale 1 + w, q/k norms a head, a gate a feature from a doubled q
projection, rotary over a quarter of the head, a sigmoid gate on the shared
expert and a share of the routed experts; against the plain float32 reference
`benchmark/reference/qwen3_next.py` at a small size on the CPU, each
the shares of one expert layer added up; each mechanism knocked out in turn
in `tests/test_qwen3_next_faults.py`."""

import copy
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from benchmark import harness, manifest as mf
from benchmark.families import qwen3_next as family
from benchmark.reference import qwen3_next as ref
from kungfu_tpu.models import transformer
from kungfu_tpu.models.transformer import (TransformerConfig, init_transformer,
                                           param_pspecs)
import test_glm_4_7_flash
import test_laguna_layers

CELL = "qwen3_next_80b_a3b.ssgd_longseq_1chip"
# one period as the cell's: three Gated DeltaNet layers (2 key and 4 value
# heads of 16, 4 taps) and one gated attention layer (4 query heads on 2
# key/value heads of 32, 8 features rotated); hidden 64; 16 experts of width
# 32 of which numbers 4 to 7 are held, 3 a token, a gated shared expert;
# vocabulary 256; 128 positions, two chunks of the delta rule's 64; flash in
# interpret mode; the routers trained, so that every leaf has a gradient to
# compare (the cell does not train them: the last test)
TINY = dict(hidden_size=64, head_dim=32, num_attention_heads=4,
            num_key_value_heads=2, linear_num_key_heads=2,
            linear_num_value_heads=4, linear_key_head_dim=16,
            linear_value_head_dim=16, num_experts=4, first_expert_held=4,
            num_experts_per_tok=3, moe_intermediate_size=32,
            shared_expert_intermediate_size=32,
            published={"num_experts": 16}, vocab_size=256, sequence_length=128,
            flash_blocks=[32, 32], flash_interpret=True,
            compute_dtype="float32", routers_trained=True)
SEED = 5


def tiny_config(**changes):
    config = copy.deepcopy(mf.cell(mf.load(), CELL)["config"])
    config.update(TINY)
    config.update(changes)
    return config


CONFIG = tiny_config()


def _state(seed=SEED, config=CONFIG):
    """A state as after some training, so that no fault can hide behind the
    initial values: norm weights off zero (the scale 1 + w off one), sharp
    attention, gates off one half, a router with preferences, experts and a
    shared expert's gate that weigh, a delta rule that writes."""
    state = family.init(config, seed)
    key = jax.random.PRNGKey(seed + 100)
    scale = {"wq": 6.0, "wk": 6.0, "wv": 8.0, "w_qkvz": 4.0, "w_ba": 20.0,
             "router": 20.0, "w_gate": 5.0, "w_up": 5.0, "w_down": 5.0,
             "shared_gate": 5.0, "shared_up": 5.0, "shared_down": 5.0,
             "w_shared_gate": 40.0}
    stacks = []
    for s, stack in enumerate(state["layers"]):
        stack = {name: leaf * scale.get(name, 1.0) for name, leaf in stack.items()}
        for i, name in enumerate(("ln1_scale", "ln2_scale", "q_norm_scale",
                                  "k_norm_scale", "gdn_norm_scale")):
            if name in stack:
                stack[name] = stack[name] + 0.4 * jax.random.normal(
                    jax.random.fold_in(key, 8 * s + i), stack[name].shape)
        stacks.append(stack)
    return {**state, "layers": tuple(stacks),
            "ln_f_scale": 0.3 * jax.random.normal(key, state["ln_f_scale"].shape)}


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


def _errors(config, state, sample, want=None):
    loss, grads = family.program_loss_and_grads(config)(state, sample)
    want_loss, want = want or family.reference_loss_and_grads(CONFIG, state, sample)
    return (abs(float(loss) - float(want_loss)) / abs(float(want_loss)),
            harness.relative_error(grads, want), grads, want)


def test_the_stacks_are_the_models_layers_in_order():
    assert family.layer_types(CONFIG) == [family.LINEAR] * 3 + [family.FULL]
    mc = family.model_config(CONFIG)
    assert [(kind.mixer, kind.layer_remat, n) for kind, n in mc.stacks] == [
        ("gated_delta", True, 3), ("attention", False, 1)]
    state = jax.eval_shape(lambda: family.init(CONFIG, 0))
    delta, attention = ({k: v.shape for k, v in stack.items()}
                        for stack in state["layers"])
    # a key head's q, k, two heads of v and two of z: 6 x 16 columns each
    assert delta["w_qkvz"] == (3, 64, 2 * 96) and delta["w_ba"] == (3, 64, 8)
    assert delta["conv_w"] == (3, 4, 2 * 64) and delta["wo"] == (3, 64, 64)
    assert delta["A_log"] == delta["dt_bias"] == (3, 4)
    assert delta["gdn_norm_scale"] == (3, 16)
    assert not {"wq", "wk", "wv", "wqkv", "q_norm_scale"} & set(delta)
    # q and its gate side by side a head: 4 x 2 x 32 columns
    assert attention["wq"] == (1, 64, 256) and attention["wk"] == (1, 64, 64)
    assert attention["wo"] == (1, 128, 64)
    assert attention["q_norm_scale"] == attention["k_norm_scale"] == (1, 32)
    for shapes in (delta, attention):
        assert shapes["router"][1:] == (64, 16)
        assert shapes["w_gate"][1:] == (4, 64, 32)
        assert shapes["w_shared_gate"][1:] == (64, 1)
    specs = param_pspecs(mc)
    assert jax.tree.structure(
        jax.tree.map(lambda s: 0, specs,
                     is_leaf=lambda s: isinstance(s, PartitionSpec))
    ) == jax.tree.structure(jax.tree.map(lambda s: 0, state))
    assert specs["layers"][0]["w_qkvz"] == PartitionSpec(None, None, "tp")
    assert specs["layers"][1]["q_norm_scale"] == PartitionSpec(None, None)


def test_the_norms_start_at_scale_one_and_the_decay_spans_weak_to_strong():
    state = family.init(tiny_config(linear_num_key_heads=16,
                                    linear_num_value_heads=32), SEED)
    delta, attention = state["layers"]
    for stack, names in ((delta, ("ln1_scale", "ln2_scale")),
                         (attention, ("ln1_scale", "q_norm_scale", "k_norm_scale"))):
        for name in names:
            assert not np.asarray(stack[name]).any(), name
    assert not np.asarray(state["ln_f_scale"]).any()
    assert (np.asarray(delta["gdn_norm_scale"]) == 1).all()
    # g = -exp(A_log) softplus(dt_bias) at a = 0: a position's log decay
    g = -np.exp(delta["A_log"]) * np.log1p(np.exp(delta["dt_bias"]))
    assert g.shape == (3, 32) and (g < 0).all()
    assert g.max() > -0.02 and g.min() < -0.5, (g.max(), g.min())
    assert np.abs(np.asarray(delta["conv_w"])).max() <= 0.5


def test_float32_program_equals_the_reference():
    state, sample = _state(), _sample()
    loss_error, grad_error, grads, want = _errors(CONFIG, state, sample,
                                                  _reference())
    assert loss_error <= 1e-5 and grad_error <= 1e-4, (loss_error, grad_error)
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        assert float(jnp.abs(g).max()) > 0, jax.tree_util.keystr(path)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want)):
        assert harness.relative_error(g, w) <= 1e-3, jax.tree_util.keystr(path)
    assert family.differing_choices(CONFIG, state, sample) == 0


def test_float32_logits_equal_the_references():
    state, sample = _state(), _sample()
    mc = family.model_config(CONFIG)
    got = jax.jit(lambda p, t: transformer.transformer_apply(p, t, mc))(
        state, sample[:, :-1])
    want = ref.logits(state, sample, **family._hyper(CONFIG))
    assert got.shape == want.shape == (2, 128, 256)
    assert harness.relative_error(got, want) <= 1e-5


def test_bfloat16_program_is_within_the_familys_tolerances():
    config = tiny_config(compute_dtype="bfloat16")
    state, sample = family.init(config, SEED), _sample()
    loss_error, grad_error, _, _ = _errors(config, state, sample)
    assert loss_error <= family.LOSS_RTOL, loss_error
    assert 1e-4 < grad_error <= family.GRAD_RTOL, grad_error
    assert 0 < family.LOSS_RTOL < family.GRAD_RTOL < 0.1


@pytest.mark.parametrize("recomputed", [[], [family.LINEAR, family.FULL]])
def test_the_recomputed_layers_change_no_number(recomputed):
    """`recomputed_layer_types` says what the backward pass keeps, not what
    it computes."""
    state, sample = _state(), _sample()
    other = tiny_config(recomputed_layer_types=recomputed)
    loss, grads = family.program_loss_and_grads(CONFIG)(state, sample)
    want_loss, want = family.program_loss_and_grads(other)(state, sample)
    assert abs(float(loss) - float(want_loss)) <= 1e-6 * abs(float(want_loss))
    assert harness.relative_error(grads, want) <= 1e-5


@pytest.mark.parametrize("block", [2, 4])
def test_the_mixers_head_blocks_change_no_number(block, monkeypatch, fresh_traces):
    """The mixer takes its heads a block at a time to bound what it holds:
    two blocks of two value heads, or one of all four."""
    state, sample = _state(), _sample()
    loss, grads = family.program_loss_and_grads(CONFIG)(state, sample)
    monkeypatch.setattr(transformer, "DELTA_HEAD_BLOCK", block)
    jax.clear_caches()
    text = str(jax.make_jaxpr(family.program_loss_and_grads(CONFIG))(state, sample))
    assert ("f32[2,64,96]" in text) == (block == 2)  # two of the four key heads
    want_loss, want = family.program_loss_and_grads(CONFIG)(state, sample)
    assert abs(float(loss) - float(want_loss)) <= 1e-6 * abs(float(want_loss))
    assert harness.relative_error(grads, want) <= 1e-4


def _layer_again_keeping(*names):
    """`models/transformer._layer_again` with a policy of these names."""
    return jax.checkpoint(
        transformer._layer, static_argnums=(2,), prevent_cse=False,
        policy=jax.checkpoint_policies.save_only_these_names(*names))


def test_a_layer_run_again_runs_its_mixers_forward_twice_a_step(monkeypatch,
                                                                fresh_traces):
    """`layer_remat` keeps the mixer's output under the name `gdn_mix`, so
    the backward scan's second run of a DeltaNet layer has no reader for its
    blocks and the compiled step holds the q, k, v, z projection of a block
    twice: the forward scan's and the one a block's own checkpoint runs for
    its gradients. With the name out of `_layer_again`'s policy it holds a
    third. Two blocks of eight value heads, as the cell's four: a scan of
    one block XLA unrolls, and merges the two later runs by itself."""
    config = tiny_config(linear_num_key_heads=8, linear_num_value_heads=16)
    assert transformer.DELTA_HEAD_BLOCK == 8
    assert [kind.layer_remat for kind, _ in family.model_config(config).stacks
            ] == [True, False]
    state = jax.eval_shape(lambda: family.init(config, 0))
    # (tokens, a block's 4 key heads x (q, k, 2 v, 2 z) x 16)
    projection = re.compile(
        r'= f32\[256,384\]\S* dot\(.*op_name="[^"]*gdn_proj/dot_general"')

    def projections():
        jax.clear_caches()
        text = family.program_loss_and_grads(config).lower(
            state, _sample()).compile().as_text()
        return len(projection.findall(text))

    assert projections() == 2
    monkeypatch.setattr(transformer, "_layer_again",
                        _layer_again_keeping("flash_out", "flash_lse"))
    assert projections() == 3


@pytest.mark.parametrize("other", [test_laguna_layers, test_glm_4_7_flash],
                         ids=["laguna", "glm_4_7_flash"])
def test_a_program_with_no_delta_layer_does_not_feel_the_name(other, monkeypatch,
                                                              fresh_traces):
    """`_layer_again`'s policy names a value that only a DeltaNet mixer
    makes: a program whose layers are run again and have no such mixer
    lowers to the same text with the name and without it, and to another
    with no name at all (so the policy patched in is the one traced)."""
    config, mc = other.CONFIG, other.family.model_config(other.CONFIG)
    kinds = [kind for kind, _ in mc.stacks]
    assert any(kind.layer_remat for kind in kinds)
    assert not any(kind.mixer == "gated_delta" for kind in kinds)
    state = jax.eval_shape(lambda: other.family.init(config, 0))

    def lowered():
        jax.clear_caches()
        return other.family.program_loss_and_grads(config).lower(
            state, other._sample()).as_text()

    text = lowered()
    for names, same in ((("flash_out", "flash_lse"), True), ((), False)):
        monkeypatch.setattr(transformer, "_layer_again",
                            _layer_again_keeping(*names))
        assert (lowered() == text) == same, names


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Model-configs guide, section 4: one expert layer of 64 experts, 3 a
    token, cut into 16 shares of 4. Each share routes over all 64 and
    computes its own experts' part and the gated shared expert, which every
    chip computes alike; the parts of all 16, the shared expert counted
    once, are what the uncut reference gives for the whole layer."""
    E, held, D, F, T = 64, 4, 64, 32, 96
    ks = jax.random.split(jax.random.PRNGKey(3), 10)
    n = jax.random.normal(ks[0], (T, D))
    w = {"router": jax.random.normal(ks[1], (D, E)),
         "w_gate": 0.3 * jax.random.normal(ks[2], (E, D, F)),
         "w_up": 0.3 * jax.random.normal(ks[3], (E, D, F)),
         "w_down": 0.3 * jax.random.normal(ks[4], (E, F, D)),
         "shared_gate": 0.3 * jax.random.normal(ks[5], (D, F)),
         "shared_up": 0.3 * jax.random.normal(ks[6], (D, F)),
         "shared_down": 0.3 * jax.random.normal(ks[7], (F, D)),
         "w_shared_gate": jax.random.normal(ks[8], (D, 1))}
    want, chosen = ref.experts(n, w, dict(top_k=3, first_held=0))
    shared = ref._sigmoid(n @ w["w_shared_gate"]) * ref._swiglu(
        n, w["shared_gate"], w["shared_up"], w["shared_down"])

    def share(first):
        cfg = TransformerConfig(
            d_model=D, d_ff=F, dtype=jnp.float32, ffn="moe", n_experts=E,
            top_k=3, gates="renorm", experts_held=(first, held), shared_ff=F,
            shared_gate=True)
        mine = {**w, **{name: w[name][first:first + held]
                        for name in ("w_gate", "w_up", "w_down")}}
        return transformer._expert_layer(n, mine, cfg)

    parts = [share(first) for first in range(0, E, held)]
    assert len(parts) == 16
    total = sum(y for y, _ in parts) - 15 * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    counts = np.concatenate([np.asarray(aux.counts) for _, aux in parts])
    assert counts.tolist() == np.bincount(np.asarray(chosen).ravel(),
                                          minlength=E).tolist()
    assert counts.sum() == 3 * T
    # one share alone is not the layer: the cut is real
    assert not np.allclose(np.asarray(parts[0][0]), np.asarray(want), atol=1e-2)


def test_the_share_drops_nothing_and_counts_its_rows():
    state, sample = _state(), _sample()
    stats = family.routing_stats(CONFIG, state, sample)
    assert stats["dropped"] == [0, 0, 0, 0] and stats["layer"] == [0, 1, 2, 3]
    counts = np.asarray(stats["counts"])
    assert counts.shape == (4, 4)
    assert stats["held_rows"] == counts.sum(axis=1).tolist()
    # 3 of 16 experts a token, 4 held: a quarter of the choices, about
    assert 0.1 < counts.sum() / (4 * 256 * 3) < 0.45


def test_the_new_fields_refuse_what_they_cannot_mean():
    with pytest.raises(ValueError, match="mixer"):
        TransformerConfig(mixer="linear")
    with pytest.raises(ValueError, match="delta_heads"):
        TransformerConfig(mixer="gated_delta")
    with pytest.raises(ValueError, match="delta_heads"):
        TransformerConfig(mixer="gated_delta", delta_heads=(3, 4, 16))
    with pytest.raises(ValueError, match="q_gate"):
        TransformerConfig(q_gate=True)
    with pytest.raises(ValueError, match="shared_gate"):
        TransformerConfig(shared_gate=True)
    # a q/k norm a head with projections of their own is a layer now
    cfg = TransformerConfig(n_heads=4, n_kv_heads=2, head_size=16, qk_norm=True,
                            attn_core="flash")
    layer = jax.eval_shape(lambda: init_transformer(jax.random.PRNGKey(0), cfg))
    assert layer["layers"]["q_norm_scale"].shape == (4, 16)


def test_a_length_the_chunk_does_not_divide_is_refused_by_the_model():
    config = tiny_config(sequence_length=96)
    state = jax.eval_shape(lambda: family.init(config, 0))
    with pytest.raises(ValueError, match="multiple of the chunk"):
        jax.eval_shape(family.program_loss_and_grads(config), state,
                       family.host_batch(config, 0, 0, 1))


def test_the_mixers_scopes_are_in_the_program():
    """`gdn` with `gdn_proj`, `gdn_conv`, `gdn_core`, `gdn_norm` inside it,
    `attn_full` around the gated attention core, `attn_gate`, `moe_shared`:
    what the cell's per-layer metrics read."""
    state = jax.eval_shape(lambda: family.init(CONFIG, 0))
    text = family.program_loss_and_grads(CONFIG).lower(
        state, _sample()).as_text(debug_info=True)
    for scope in ("gdn/", "gdn_proj/", "gdn_conv/", "gdn_core/", "gdn_norm/",
                  "attn/attn_full/attn_core", "attn/attn_gate", "qk_norm/",
                  "rope/", "moe/moe_shared", "moe/moe_dispatch",
                  "moe/moe_router", "moe_experts/", "moe_combine/"):
        assert scope in text, scope


def test_routers_that_are_not_trained_get_no_gradient_and_change_no_other():
    """The cell's own setting: the routers' matrices are constants of the
    loss, in the program and in the reference alike; every other leaf's
    gradient is what it is with the routers trained."""
    config = tiny_config(routers_trained=False)
    assert mf.cell(mf.load(), CELL)["config"]["routers_trained"] is False
    state, sample = _state(), _sample()
    loss, grads = family.program_loss_and_grads(config)(state, sample)
    want_loss, want = family.reference_loss_and_grads(config, state, sample)
    trained_loss, trained = _reference()
    assert float(want_loss) == float(trained_loss)
    assert abs(float(loss) - float(want_loss)) <= 1e-5 * abs(float(want_loss))
    assert harness.relative_error(grads, want) <= 1e-4
    for got, ref, full in zip(grads["layers"], want["layers"], trained["layers"]):
        assert not np.asarray(got["router"]).any()
        assert not np.asarray(ref["router"]).any()
        assert np.asarray(full["router"]).any()
        for name in ref:
            if name != "router":
                np.testing.assert_array_equal(ref[name], full[name])
