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
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from benchmark import harness, manifest as mf
from benchmark.families import nemotron_h as family
from benchmark.reference import nemotron_h as ref
from kungfu_tpu.models import transformer
from kungfu_tpu.models.transformer import (TransformerConfig, init_transformer,
                                           param_pspecs)
from kungfu_tpu.telemetry import metrics

# the cell's stack in small, `M E M * E`: hidden 64; 8 Mamba-2 heads of 8 on 2
# groups' B and C of 16; 4 query heads on 2 key/value heads of 16; 16 experts
# of width 32 of which numbers 4 to 11 are held, 3 a token, a shared expert of
# 64; vocabulary 320; 64 positions; flash in interpret mode; the routers
# trained, so that every leaf but the bias has a gradient to compare
TINY = dict(hidden_size=64, moe_intermediate_size=32,
            moe_shared_expert_intermediate_size=64, num_hidden_layers=5,
            hybrid_override_pattern="MEM*E", num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
            mamba_head_dim=8, ssm_state_size=16, n_groups=2,
            n_routed_experts=8, first_expert_held=4,
            published={"n_routed_experts": 16}, num_experts_per_tok=3,
            vocab_size=320, sequence_length=64, flash_blocks=[32, 32],
            flash_interpret=True, compute_dtype="float32", routers_trained=True)
SEED = 5


def real_config():
    """The configuration file as it is."""
    with open(os.path.join(mf.BENCH_DIR, "configs",
                           "nemotron_3_nano_30b_a3b.json")) as f:
        return json.load(f)


def tiny_config(**changes):
    config = real_config()
    config.update(TINY)
    config.update(changes)
    return config


CONFIG = tiny_config()

_SCALES = {"w_ssm_in": 4.0, "wq": 8.0, "wk": 8.0, "wv": 4.0, "router": 20.0,
           "router_bias": 40.0, "w_up": 8.0, "w_down": 8.0, "shared_up": 4.0,
           "shared_down": 4.0}
_NORMS = ("ln1_scale", "ln2_scale", "ssm_norm_scale")


def _trained(layer, key):
    """One stack as after some training: matrices that weigh, norm scales off
    one, and in a Mamba-2 layer a memory of 2 to 25 positions (A in [0.05,
    0.5] under steps near 0.8) in the place of the start's few, a D off 1 and
    a convolution bias that matters."""
    layer = {name: leaf * _SCALES.get(name, 1.0) for name, leaf in layer.items()}
    for i, name in enumerate(_NORMS):
        if name in layer:
            layer[name] = layer[name] + 0.4 * jax.random.normal(
                jax.random.fold_in(key, i), layer[name].shape)
    if "A_log" in layer:
        shape = layer["A_log"].shape
        layer["A_log"] = jnp.log(jax.random.uniform(
            jax.random.fold_in(key, 7), shape, minval=0.05, maxval=0.5))
        layer["dt_bias"] = jax.random.uniform(
            jax.random.fold_in(key, 8), shape, minval=0.1, maxval=0.5)
        layer["D_skip"] = 1.0 + 0.5 * jax.random.normal(
            jax.random.fold_in(key, 9), shape)
    return layer


def _state(seed=SEED, config=CONFIG):
    """A state as after some training, so that no fault can hide behind the
    initial values."""
    state = family.init(config, seed)
    key = jax.random.PRNGKey(seed + 100)
    stacks = tuple(_trained(stack, jax.random.fold_in(key, 10 + s))
                   for s, stack in enumerate(state["layers"]))
    return {**state, "layers": stacks,
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
    with pytest.raises(ValueError, match="is no layer"):
        TransformerConfig(mixer="none", ffn="none")
    with pytest.raises(ValueError, match="is no layer"):
        TransformerConfig(n_layers=1, layer_kinds=(
            (("mixer", "none"), ("ffn", "none")),)).stacks
    with pytest.raises(ValueError, match="ssm_dims"):
        TransformerConfig(mixer="mamba2")
    with pytest.raises(ValueError, match="ssm_dims"):
        TransformerConfig(mixer="mamba2", ssm_dims=(6, 8, 16, 4))
    for field, value in (("positions", "alibi"), ("ffn", "relu2"),
                         ("expert_act", "gelu"), ("mixer", "mamba")):
        with pytest.raises(ValueError, match=field):
            TransformerConfig(**{field: value})
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


def test_a_tp_mesh_of_two_gives_the_same_loss():
    from kungfu_tpu.parallel import make_mesh
    from kungfu_tpu.parallel.sharded import shard_params

    config = CONFIG
    mc = family.model_config(config)
    state, sample = _state(), _sample()
    loss = family.loss_fn(config)
    want = float(jax.jit(loss)(state, sample))
    mesh = make_mesh({"dp": 1, "tp": 2, "ep": 1}, devices=jax.devices()[:2])
    placed = shard_params(state, mesh, param_pspecs(mc))
    assert len(placed["layers"][0]["w_ssm_in"].sharding.device_set) == 2
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


def test_bfloat16_program_is_within_the_familys_tolerances():
    config = tiny_config(compute_dtype="bfloat16")
    state, sample = family.init(config, SEED), _sample()
    loss, grads = family.program_loss_and_grads(config)(state, sample)
    want_loss, want = family.reference_loss_and_grads(CONFIG, state, sample)
    assert abs(float(loss) - float(want_loss)) <= family.LOSS_RTOL * abs(float(want_loss))
    error = harness.relative_error(grads, want)
    assert 1e-4 < error <= family.GRAD_RTOL, error
    assert 0 < family.LOSS_RTOL < family.GRAD_RTOL < 0.1


@pytest.mark.parametrize("recomputed", [[], ["mamba", "moe", "attention"]])
def test_the_recomputed_layers_change_no_number(recomputed):
    """`recomputed_layer_types` says what the backward pass keeps, not what
    it computes."""
    state, sample = _state(), _sample()
    other = tiny_config(recomputed_layer_types=recomputed)
    assert [k.layer_remat for k, _ in family.model_config(other).stacks] == (
        [bool(recomputed)] * 5)
    loss, grads = family.program_loss_and_grads(CONFIG)(state, sample)
    want_loss, want = family.program_loss_and_grads(other)(state, sample)
    assert abs(float(loss) - float(want_loss)) <= 1e-6 * abs(float(want_loss))
    assert harness.relative_error(grads, want) <= 1e-5


def test_the_mamba2_mixer_alone_against_the_recurrence_a_position_at_a_time():
    """The mixer by itself, on hidden states and weights that matter, output
    and every weight's gradient: the program's (one fused projection, the
    convolution's written-out backward pass, the chunked scan in kernels with
    B and C a group, the gate and then the norm a group) against the
    reference's (the recurrence a position at a time over a (P, N) state a
    head)."""
    mc = family.model_config(CONFIG).stacks[0][0]
    layer = jax.tree.map(lambda a: a[0], _state()["layers"][0])
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 64))
    weight = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 64))
    hyper = family._hyper(CONFIG)

    def mine(h, w):
        return jnp.sum(transformer._mamba2_mixer(h, w, mc) * weight)

    def theirs(h, w):
        return jnp.sum(ref.mamba_mixer(h, w, hyper) * weight)

    got = jax.jit(lambda h, w: transformer._mamba2_mixer(h, w, mc))(h, layer)
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
    moved = jax.jit(lambda h, w: transformer._mamba2_mixer(h, w, mc))(
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
    layer = jax.tree.map(lambda a: a[0], _state()["layers"][3])
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
    """Model-configs guide, section 4: one expert layer of 128 two-matrix
    relu^2 experts, 6 a token by sigmoid scores and a selection bias,
    renormalised and scaled by 2.5, cut into 16 shares of 8. Each share
    routes over all 128 and computes its own experts' part and the shared
    expert, which every chip computes alike; the parts of all 16, the shared
    expert counted once, are what the uncut reference gives for the whole
    layer."""
    E, held, D, F, T = 128, 8, 64, 32, 96
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

    def share(first):
        cfg = TransformerConfig(
            d_model=D, d_ff=F, dtype=jnp.float32, mixer="none", ffn="moe",
            n_experts=E, top_k=6, gates="renorm", routed_scale=2.5,
            experts_held=(first, held), shared_ff=2 * F,
            router_scores="sigmoid", router_bias=True, expert_act="relu2")
        mine = {**w, **{name: w[name][first:first + held]
                        for name in ("w_up", "w_down")}}
        return transformer._expert_layer(n, mine, cfg)

    parts = [share(first) for first in range(0, E, held)]
    assert len(parts) == 16
    total = sum(y for y, _ in parts) - 15 * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    counts = np.concatenate([np.asarray(aux.counts) for _, aux in parts])
    assert counts.tolist() == np.bincount(np.asarray(chosen).ravel(),
                                          minlength=E).tolist()
    assert counts.sum() == 6 * T
    # every share sees the same router: the bias moved the same choices
    moved = {int(aux.bias_moved) for _, aux in parts}
    assert len(moved) == 1 and 0 < moved.pop() < 6 * T
    # one share alone is not the layer: the cut is real
    assert not np.allclose(np.asarray(parts[0][0]), np.asarray(want), atol=1e-2)


def test_the_share_drops_nothing_and_counts_what_the_bias_moved():
    state, sample = _state(), _sample()
    stats = family.routing_stats(CONFIG, state, sample)
    # the expert layers are the model's layers 1 and 4
    assert stats["dropped"] == [0, 0] and stats["layer"] == [1, 4]
    counts = np.asarray(stats["counts"])
    assert counts.shape == (2, 8)
    assert stats["held_rows"] == counts.sum(axis=1).tolist()
    # 3 of 16 experts a token, 8 held: half of the choices, about
    assert 0.3 < counts.sum() / (2 * 128 * 3) < 0.7
    assert len(stats["bias_moved"]) == 2 and all(
        0 < n < 128 * 3 for n in stats["bias_moved"])
    mc = family.model_config(CONFIG)
    full = jax.jit(lambda p, t: transformer.routing_stats(p, t, mc))(
        state, sample[:, :-1])
    registry = metrics.Registry()
    transformer.record_routing(full, registry)
    text = registry.render()
    assert 'kungfu_moe_bias_moved_token_choices{layer="4"}' in text
    assert 'kungfu_moe_dropped_token_choices{layer="1"} 0' in text
    assert 'kungfu_moe_held_rows{layer="4"}' in text
    assert 'kungfu_moe_max_over_mean_load{layer="1"}' in text


def test_the_initial_values_are_mamba2s():
    """Matrices normal(0, 0.02); A uniform on [1, 16]; the step log-uniform
    on [time_step_min, time_step_max] = [0.001, 0.1] and at least
    time_step_floor, `dt_bias` its inverse softplus; D 1; norms 1; a small
    fixed selection bias."""
    config = tiny_config(hidden_size=256, mamba_num_heads=64, n_groups=8)
    state = family.init(config, SEED)
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
    assert np.array_equal(bias, np.asarray(family.init(config, SEED)["layers"][1]
                                           ["router_bias"]))


def test_the_new_scopes_are_in_the_programs_op_names():
    """`ssm` with `ssm_proj`, `ssm_conv`, `ssm_core` and `ssm_norm` inside
    it; `attn` > `attn_full` > `attn_core`; `moe` with its five: what the
    cell's per-layer metrics read off the compiled program's `op_name`s,
    forward and backward."""
    from benchmark import trace_reduce

    state = jax.eval_shape(lambda: family.init(CONFIG, 0))
    compiled = family.program_loss_and_grads(CONFIG).lower(
        state, _sample()).compile()
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


def test_routers_that_are_not_trained_get_no_gradient_and_change_no_other():
    """The cell's own setting: the routers' matrices are constants of the
    loss, in the program and in the reference alike; every other leaf's
    gradient is what it is with the routers trained."""
    config = tiny_config(routers_trained=False)
    assert real_config()["routers_trained"] is False
    state, sample = _state(), _sample()
    loss, grads = family.program_loss_and_grads(config)(state, sample)
    want_loss, want = family.reference_loss_and_grads(config, state, sample)
    trained_loss, trained = _reference()
    assert float(want_loss) == float(trained_loss)
    assert abs(float(loss) - float(want_loss)) <= 1e-5 * abs(float(want_loss))
    assert harness.relative_error(grads, want) <= 1e-4
    for at in (1, 4):
        got, reference, full = (tree["layers"][at] for tree in (grads, want, trained))
        assert not np.asarray(got["router"]).any()
        assert not np.asarray(reference["router"]).any()
        assert np.asarray(full["router"]).any()
        for name in reference:
            if name != "router":
                np.testing.assert_array_equal(reference[name], full[name])
