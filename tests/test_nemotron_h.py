"""NVIDIA-Nemotron-3-Nano's layers in `models/transformer.py` (PR 43): layers
that are one residual branch behind one norm, a Mamba-2 state-space mixer
(`ops.ssm_scan`), softmax attention of grouped heads with no position signal,
and expert layers of two-matrix relu^2 experts chosen by sigmoid scores under
a selection bias, renormalised and scaled by 2.5, over a share of the experts
beside a shared expert; against the plain float32 reference
`benchmark/reference/nemotron_h.py` at a small size on the CPU, the sixteen
shares of one expert layer added up; each mechanism knocked out in turn in
`tests/test_nemotron_h_faults.py`."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

import family_cases as fc
from benchmark import harness
from benchmark.reference import nemotron_h as ref
from family_cases import *  # noqa: F401,F403  the shared cases
from kungfu_tpu.models import transformer
from kungfu_tpu.models.mixers import mamba2
from kungfu_tpu.models.transformer import TransformerConfig, init_transformer
from kungfu_tpu.telemetry import metrics


def _named_specs(specs):
    mamba, experts, _, attention, _ = specs["layers"]
    # the fused projection's, the convolution's and the gated norm's channels
    # and W_out's rows over tp; a number a head whole
    assert mamba["w_ssm_in"] == mamba["conv_w"] == PartitionSpec(None, None, "tp")
    assert mamba["conv_b"] == mamba["ssm_norm_scale"] == PartitionSpec(None, "tp")
    assert mamba["wo"] == attention["wo"] == PartitionSpec(None, "tp", None)
    assert mamba["A_log"] == mamba["dt_bias"] == mamba["D_skip"] == (
        PartitionSpec(None, None))
    assert experts["w_up"] == PartitionSpec(None, "ep", None, "tp")
    assert experts["w_down"] == PartitionSpec(None, "ep", "tp", None)
    assert experts["shared_up"] == PartitionSpec(None, None, "tp")
    assert experts["shared_down"] == PartitionSpec(None, "tp", None)
    assert experts["router_bias"] == PartitionSpec(None, None)
    assert not {"w_gate", "shared_gate", "ln1_scale", "wo"} & set(experts)
    assert "ln2_scale" not in mamba and "ln2_scale" not in attention


FAMILY = fc.NEMOTRON_H.with_cases(
    named_specs=_named_specs, tp_leaf=("layers", 0, "w_ssm_in"))
family, tiny_config, CONFIG = FAMILY.module, FAMILY.tiny_config, FAMILY.config


def test_every_layer_is_one_branch_behind_one_norm():
    assert family.layer_types(CONFIG) == list("MEM*E")
    mc = family.model_config(CONFIG)
    assert [(kind.mixer, kind.ffn, kind.layer_remat, n) for kind, n in mc.stacks] == [
        ("mamba2", "none", True, 1), ("none", "moe", True, 1),
        ("mamba2", "none", True, 1), ("attention", "none", False, 1),
        ("none", "moe", True, 1)]
    assert (mc.positions, mc.expert_act, mc.router_scores, mc.router_bias,
            mc.gates, mc.routed_scale) == ("none", "relu2", "sigmoid", True,
                                           "renorm", 2.5)
    assert mc.ssm_dims == (8, 8, 16, 2) and mc.experts_held == (4, 8)
    state = jax.eval_shape(lambda: family.init(CONFIG, 0))
    assert "pos_embed" not in state and "lm_head" in state
    shapes = [{k: v.shape for k, v in stack.items()} for stack in state["layers"]]
    mamba = {"ln1_scale": (1, 64), "w_ssm_in": (1, 64, 64 + 64 + 2 * 32 + 8),
             "conv_w": (1, 4, 64 + 2 * 32), "conv_b": (1, 64 + 2 * 32),
             "dt_bias": (1, 8), "A_log": (1, 8), "D_skip": (1, 8),
             "ssm_norm_scale": (1, 64), "wo": (1, 64, 64)}
    experts = {"ln2_scale": (1, 64), "router": (1, 64, 16),
               "router_bias": (1, 16), "w_up": (1, 8, 64, 32),
               "w_down": (1, 8, 32, 64), "shared_up": (1, 64, 64),
               "shared_down": (1, 64, 64)}
    attention = {"ln1_scale": (1, 64), "wq": (1, 64, 64), "wk": (1, 64, 32),
                 "wv": (1, 64, 32), "wo": (1, 64, 64)}
    # one norm leaf a layer, no gate matrix, nothing of the branch it lacks
    assert shapes == [mamba, experts, mamba, attention, experts]


def test_a_layer_with_neither_branch_and_sizes_that_do_not_fit_are_refused():
    fc.refused("is no layer", mixer="none", ffn="none")
    with pytest.raises(ValueError, match="is no layer"):
        TransformerConfig(n_layers=1, layer_kinds=(
            (("mixer", "none"), ("ffn", "none")),)).stacks
    fc.refused("ssm_dims", mixer="mamba2")
    fc.refused("ssm_dims", mixer="mamba2", ssm_dims=(6, 8, 16, 4))
    for field, value in (("positions", "alibi"), ("ffn", "relu2"),
                         ("expert_act", "gelu"), ("mixer", "mamba")):
        fc.refused(field, **{field: value})
    # a feed-forward alone on the repo's own block, and a mixer alone
    for changes in (dict(mixer="none"), dict(ffn="none"),
                    dict(ffn="none", positions="none")):
        cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=2, d_ff=48, max_seq=16, **changes)
        params = init_transformer(jax.random.PRNGKey(0), cfg)
        norms = {k for k in params["layers"] if k.startswith("ln")}
        assert norms == ({"ln2_scale"} if cfg.mixer == "none" else {"ln1_scale"})
        assert ("pos_embed" in params) == (cfg.positions == "learned")
        loss = transformer.transformer_loss(params, jnp.zeros((1, 9), jnp.int32), cfg)
        assert np.isfinite(float(loss))


def test_the_mamba2_mixer_alone_against_the_recurrence_a_position_at_a_time():
    """The mixer by itself, on hidden states and weights that matter, output
    and every weight's gradient: the program's (one fused projection, the
    convolution's written-out backward pass, the chunked scan in kernels with
    B and C a group, the gate and then the norm a group) against the
    reference's (the recurrence a position at a time over a (P, N) state a
    head)."""
    mc = family.model_config(CONFIG).stacks[0][0]
    layer = jax.tree.map(lambda a: a[0], FAMILY.state()["layers"][0])
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 64))
    weight = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 64))
    hyper = family._hyper(CONFIG)

    def mine(h, w):
        return jnp.sum(mamba2._mamba2_mixer(h, w, mc) * weight)

    def theirs(h, w):
        return jnp.sum(ref.mamba_mixer(h, w, hyper) * weight)

    got = jax.jit(lambda h, w: mamba2._mamba2_mixer(h, w, mc))(h, layer)
    with jax.default_matmul_precision("highest"):
        want = ref.mamba_mixer(h, layer, hyper)
        want_grads = jax.grad(theirs, (0, 1))(h, layer)
    got_grads = jax.jit(jax.grad(mine, (0, 1)))(h, layer)
    assert got.shape == want.shape == (2, 64, 64)
    assert harness.relative_error(got, want) <= 1e-5
    assert harness.relative_error(got_grads[0], want_grads[0]) <= 1e-4
    for name in want_grads[1]:
        if name == "ln1_scale":  # the layer's norm, not the mixer's
            continue
        assert float(jnp.abs(want_grads[1][name]).max()) > 0, name
        assert harness.relative_error(got_grads[1][name],
                                      want_grads[1][name]) <= 1e-3, name
    # the memory is longer than a chunk of the tests' scan would need: a
    # change at position 3 still moves position 40
    moved = jax.jit(lambda h, w: mamba2._mamba2_mixer(h, w, mc))(
        h.at[:, 3].add(1.0), layer)
    assert float(jnp.abs(moved[:, 40] - got[:, 40]).max()) > 1e-6
    assert np.array_equal(np.asarray(moved[:, :3]), np.asarray(got[:, :3]))


def test_attention_has_no_position_signal():
    """Without positions a causal softmax layer gives a position's output
    from the set of what came before it, in any order: the two first
    positions exchanged leave every later position's output as it was. With
    a rotary pass they do not."""
    plain = family.model_config(CONFIG).stacks[3][0]
    assert (plain.mixer, plain.ffn, plain.positions) == ("attention", "none", "none")
    layer = jax.tree.map(lambda a: a[0], FAMILY.state()["layers"][3])
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 64, 64))
    swapped = x.at[:, 0].set(x[:, 1]).at[:, 1].set(x[:, 0])

    def out(kind, x):
        return jax.jit(lambda x, w: transformer._layer(x, w, kind)[0])(x, layer)

    a, b = out(plain, x), out(plain, swapped)
    assert harness.relative_error(b[:, 2:], a[:, 2:]) < 1e-5
    rotary = dataclasses.replace(plain, positions="rope")
    a, b = out(rotary, x), out(rotary, swapped)
    assert harness.relative_error(b[:, 2:], a[:, 2:]) > 1e-3


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """One expert layer of 128 two-matrix relu^2 experts, 6 a token by
    sigmoid scores and a selection bias, renormalised and scaled by 2.5, cut
    into 16 shares of 8 that each compute the shared expert
    (`fc.shares_add_up`)."""
    E, D, F, T = 128, 64, 32, 96
    ks = jax.random.split(jax.random.PRNGKey(3), 8)
    n = jax.random.normal(ks[0], (T, D))
    w = {"router": 0.5 * jax.random.normal(ks[1], (D, E)),
         "router_bias": 0.3 * jax.random.normal(ks[2], (E,)),
         "w_up": 0.3 * jax.random.normal(ks[3], (E, D, F)),
         "w_down": 0.3 * jax.random.normal(ks[4], (E, F, D)),
         "shared_up": 0.3 * jax.random.normal(ks[5], (D, 2 * F)),
         "shared_down": 0.3 * jax.random.normal(ks[6], (2 * F, D))}
    want, chosen = ref.experts(n, w, dict(top_k=6, routed_scale=2.5, first_held=0))
    shared = ref._relu2(n, w["shared_up"], w["shared_down"])
    cfg = TransformerConfig(
        d_model=D, d_ff=F, dtype=jnp.float32, mixer="none", ffn="moe",
        n_experts=E, top_k=6, gates="renorm", routed_scale=2.5, shared_ff=2 * F,
        router_scores="sigmoid", router_bias=True, expert_act="relu2")
    assert fc.shares_add_up(n, w, cfg, 8, want, chosen, shared) == 16


def test_the_initial_values_are_mamba2s():
    """Matrices normal(0, 0.02); A uniform on [1, 16]; the step log-uniform
    on [time_step_min, time_step_max] = [0.001, 0.1] and at least
    time_step_floor, `dt_bias` its inverse softplus; D 1; norms 1; a small
    fixed selection bias."""
    config = tiny_config(hidden_size=256, mamba_num_heads=64, n_groups=8)
    state = family.init(config, FAMILY.seed)
    mamba, experts = state["layers"][0], state["layers"][1]
    A = np.exp(np.asarray(mamba["A_log"]))
    assert A.shape == (1, 64) and 1.0 <= A.min() < 3 and 13 < A.max() <= 16.0
    step = np.asarray(jax.nn.softplus(mamba["dt_bias"]))
    assert 0.001 <= step.min() < 0.004 and 0.03 < step.max() <= 0.1 + 1e-6
    assert np.all(np.asarray(mamba["D_skip"]) == 1)
    assert np.all(np.asarray(mamba["ssm_norm_scale"]) == 1)
    assert np.all(np.asarray(mamba["ln1_scale"]) == 1)
    assert 0.018 < float(jnp.std(mamba["w_ssm_in"])) < 0.022
    assert 0.018 < float(jnp.std(experts["w_up"])) < 0.022
    assert np.abs(np.asarray(mamba["conv_w"])).max() <= 0.5  # 1 / sqrt(4 taps)
    assert np.asarray(mamba["conv_b"]).any()
    bias = np.asarray(experts["router_bias"])
    assert bias.shape == (1, 16) and 0.002 < np.abs(bias).mean() < 0.03
    assert np.array_equal(bias, np.asarray(family.init(config, FAMILY.seed)["layers"][1]
                                           ["router_bias"]))


def test_the_new_scopes_are_in_the_programs_op_names():
    """`ssm` with `ssm_proj`, `ssm_conv`, `ssm_core` and `ssm_norm` inside
    it; `attn` > `attn_full` > `attn_core`; `moe` with its five: what the
    cell's per-layer metrics read off the compiled program's `op_name`s,
    forward and backward."""
    from benchmark import trace_reduce

    state = jax.eval_shape(lambda: family.init(CONFIG, 0))
    compiled = family.program_loss_and_grads(CONFIG).lower(
        state, FAMILY.sample()).compile()
    names = set(trace_reduce.scope_table(compiled.as_text()).values())
    backward = [name for name in names if "transpose(" in name]
    for scope in ("ssm/ssm_proj/", "ssm/ssm_conv/", "ssm/ssm_core/",
                  "ssm/ssm_norm/", "attn/attn_full/attn_core/",
                  "moe/moe_router/", "moe/moe_shared/"):
        assert any(scope in name for name in names), scope
        assert any(scope in name for name in backward), scope
    # the share's own backward pass is written out under `moe` alone
    for scope in ("moe/moe_dispatch/", "moe_experts/", "moe_combine/"):
        assert any(scope in name for name in names), scope
    assert any("/moe/" in name for name in backward)
    assert any("head_loss" in name for name in names)
    assert any("embed" in name for name in names)
    assert not any("rope" in name or "pos_embed" in name for name in names)
