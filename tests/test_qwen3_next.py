"""Qwen3-Next's layers in `models/transformer.py` (PR 36): Gated DeltaNet
mixers and a gated softmax-attention layer in one stack (D, D, D, A), norms
with the scale 1 + w, q/k norms a head, a gate a feature from a doubled q
projection, rotary over a quarter of the head, a sigmoid gate on the shared
expert and a share of the routed experts; against the plain float32 reference
`benchmark/reference/qwen3_next.py` at a small size on the CPU, each
the shares of one expert layer added up; each mechanism knocked out in turn
in `tests/test_qwen3_next_faults.py`."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

import family_cases as fc
from benchmark import harness
from benchmark.reference import qwen3_next as ref
from family_cases import *  # noqa: F401,F403  the shared cases
from kungfu_tpu.models import transformer
from kungfu_tpu.models.mixers import gated_delta as gated_delta_mixer
from kungfu_tpu.models.transformer import TransformerConfig, init_transformer


def _named_specs(specs):
    assert specs["layers"][0]["w_qkvz"] == PartitionSpec(None, None, "tp")
    assert specs["layers"][1]["q_norm_scale"] == PartitionSpec(None, None)


FAMILY = fc.QWEN3_NEXT.with_cases(
    named_specs=_named_specs, tp_leaf=("layers", 0, "w_qkvz"))
family, tiny_config, CONFIG = FAMILY.module, FAMILY.tiny_config, FAMILY.config


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


def test_the_norms_start_at_scale_one_and_the_decay_spans_weak_to_strong():
    state = family.init(tiny_config(linear_num_key_heads=16,
                                    linear_num_value_heads=32), FAMILY.seed)
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


def test_float32_logits_equal_the_references():
    state, sample = FAMILY.state(), FAMILY.sample()
    mc = family.model_config(CONFIG)
    got = jax.jit(lambda p, t: transformer.transformer_apply(p, t, mc))(
        state, sample[:, :-1])
    want = ref.logits(state, sample, **family._hyper(CONFIG))
    assert got.shape == want.shape == (2, 128, 256)
    assert harness.relative_error(got, want) <= 1e-5


@pytest.mark.parametrize("block", [2, 4])
def test_the_mixers_head_blocks_change_no_number(block, monkeypatch, fresh_traces):
    """The mixer takes its heads a block at a time to bound what it holds:
    two blocks of two value heads, or one of all four."""
    state, sample = FAMILY.state(), FAMILY.sample()
    loss, grads = FAMILY.baseline()
    monkeypatch.setattr(gated_delta_mixer, "DELTA_HEAD_BLOCK", block)
    jax.clear_caches()
    text = str(jax.make_jaxpr(family.program_loss_and_grads(CONFIG))(state, sample))
    assert ("f32[2,64,96]" in text) == (block == 2)  # two of the four key heads
    want_loss, want = family.program_loss_and_grads(CONFIG)(state, sample)
    assert fc.off(loss, want_loss) <= 1e-6
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
    assert gated_delta_mixer.DELTA_HEAD_BLOCK == 8
    assert [kind.layer_remat for kind, _ in family.model_config(config).stacks
            ] == [True, False]
    state = jax.eval_shape(lambda: family.init(config, 0))
    # (tokens, a block's 4 key heads x (q, k, 2 v, 2 z) x 16)
    projection = re.compile(
        r'= f32\[256,384\]\S* dot\(.*op_name="[^"]*gdn_proj/dot_general"')

    def projections():
        jax.clear_caches()
        text = family.program_loss_and_grads(config).lower(
            state, FAMILY.sample()).compile().as_text()
        return len(projection.findall(text))

    assert projections() == 2
    monkeypatch.setattr(transformer, "_layer_again",
                        _layer_again_keeping("flash_out", "flash_lse"))
    assert projections() == 3


@pytest.mark.parametrize("other", [fc.LAGUNA, fc.GLM_4_7_FLASH],
                         ids=lambda other: other.name)
def test_a_program_with_no_delta_layer_does_not_feel_the_name(other, monkeypatch,
                                                              fresh_traces):
    """`_layer_again`'s policy names a value that only a DeltaNet mixer
    makes: a program whose layers are run again and have no such mixer
    lowers to the same text with the name and without it, and to another
    with no name at all (so the policy patched in is the one traced)."""
    config, mc = other.config, other.module.model_config(other.config)
    kinds = [kind for kind, _ in mc.stacks]
    assert any(kind.layer_remat for kind in kinds)
    assert not any(kind.mixer == "gated_delta" for kind in kinds)
    state = jax.eval_shape(lambda: other.module.init(config, 0))

    def lowered():
        jax.clear_caches()
        return other.module.program_loss_and_grads(config).lower(
            state, other.sample()).as_text()

    text = lowered()
    for names, same in ((("flash_out", "flash_lse"), True), ((), False)):
        monkeypatch.setattr(transformer, "_layer_again",
                            _layer_again_keeping(*names))
        assert (lowered() == text) == same, names


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """One expert layer of 64 experts, 3 a token, cut into 16 shares of 4
    that each compute the gated shared expert (`fc.shares_add_up`)."""
    E, D, F, T = 64, 64, 32, 96
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
    cfg = TransformerConfig(
        d_model=D, d_ff=F, dtype=jnp.float32, ffn="moe", n_experts=E, top_k=3,
        gates="renorm", shared_ff=F, shared_gate=True)
    assert fc.shares_add_up(n, w, cfg, 4, want, chosen, shared) == 16


def test_the_new_fields_refuse_what_they_cannot_mean():
    fc.refused("mixer", mixer="linear")
    fc.refused("delta_heads", mixer="gated_delta")
    fc.refused("delta_heads", mixer="gated_delta", delta_heads=(3, 4, 16))
    fc.refused("q_gate", q_gate=True)
    fc.refused("shared_gate", shared_gate=True)
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
