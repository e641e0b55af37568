"""Ouro-2.6B's model in `models/transformer.py` (PR 48): one stack of layers
run four times on one set of weights inside an outer scan, a second norm
behind each branch of a layer, the final norm at the end of every loop step,
an exit gate on the normed state, and the expected cross-entropy over four
head passes less an entropy term; against the plain float32 reference
`benchmark/reference/ouro.py` at a small size on the CPU, a shared leaf's
gradient summed from four copies of the weights, the loop written out in
Python, two data members that average each leaf once; each mechanism knocked
out in turn in `tests/test_ouro_faults.py`."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec

import family_cases as fc
from benchmark import harness
from benchmark.reference import ouro as ref
from family_cases import *  # noqa: F401,F403  the shared cases
from kungfu_tpu.models import transformer
from kungfu_tpu.models.transformer import TransformerConfig, init_transformer
from kungfu_tpu.ops import collective
from kungfu_tpu.optimizers import synchronous_sgd
from kungfu_tpu.parallel import make_mesh, make_train_step
from kungfu_tpu.parallel.dp import replicate, shard_batch
from kungfu_tpu.telemetry import metrics


def _named_specs(specs):
    layers = specs["layers"]
    # the second norms as the first, whole a layer; the gate whole on every chip
    for norm in ("ln1_scale", "ln1_post_scale", "ln2_scale", "ln2_post_scale"):
        assert layers[norm] == PartitionSpec(None)
    assert layers["wq"] == layers["wk"] == layers["wv"] == layers["w_gate"] == (
        PartitionSpec(None, None, "tp"))
    assert layers["wo"] == layers["w_down"] == PartitionSpec(None, "tp", None)
    assert specs["exit_gate_w"] == PartitionSpec(None, None)
    assert specs["exit_gate_b"] == specs["ln_f_scale"] == PartitionSpec()
    assert specs["lm_head"] == specs["embed"] == PartitionSpec("tp", None)


FAMILY = fc.OURO.with_cases(named_specs=_named_specs, tp_leaf=("layers", "wq"))
family, tiny_config, CONFIG = FAMILY.module, FAMILY.tiny_config, FAMILY.config
T = CONFIG["total_ut_steps"]


def test_the_model_is_one_stack_run_four_times_with_four_norms_a_layer():
    mc = family.model_config(CONFIG)
    assert (mc.loop_steps, mc.post_norms, mc.exit_entropy_coef) == (4, True, 2.0)
    assert (mc.positions, mc.rope_theta, mc.ffn, mc.tied_head, mc.split_qkv) == (
        "rope", 1e6, "swiglu", False, True)
    assert [(kind.layer_remat, n) for kind, n in mc.stacks] == [(True, 2)]
    state = jax.eval_shape(lambda: family.init(CONFIG, 0))
    assert {k: v.shape for k, v in state["layers"].items()} == {
        "ln1_scale": (2, 64), "ln1_post_scale": (2, 64), "ln2_scale": (2, 64),
        "ln2_post_scale": (2, 64), "wq": (2, 64, 64), "wk": (2, 64, 64),
        "wv": (2, 64, 64), "wo": (2, 64, 64), "w_gate": (2, 64, 96),
        "w_up": (2, 64, 96), "w_down": (2, 96, 64)}
    assert {k: v.shape for k, v in state.items() if k != "layers"} == {
        "embed": (256, 64), "lm_head": (256, 64), "ln_f_scale": (64,),
        "exit_gate_w": (64, 1), "exit_gate_b": ()}
    start = family.init(CONFIG, 3)
    assert not np.asarray(start["exit_gate_b"]).any()
    assert 0.01 < float(jnp.std(start["exit_gate_w"])) < 0.03
    for norm in ("ln1_post_scale", "ln2_post_scale"):
        assert np.all(np.asarray(start["layers"][norm]) == 1)


def test_a_loop_of_no_step_over_experts_or_under_a_module_is_refused():
    fc.refused("run once or more", loop_steps=0)
    fc.refused("exit distribution", exit_entropy_coef=0.1)
    fc.refused("no place for an expert", loop_steps=2, ffn="moe", n_experts=4,
               top_k=2)
    fc.refused("no place for an expert", loop_steps=2, mtp_depth=1)


# -- (a) the exit distribution ------------------------------------------------

def test_the_four_exit_shares_sum_to_one_and_the_last_is_the_remainder():
    gates = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (T - 1, 2, 64))
    logp = transformer._exit_log_shares(gates)
    p = np.exp(np.asarray(logp, np.float64))
    assert p.shape == (T, 2, 64)
    np.testing.assert_allclose(p.sum(axis=0), 1.0, atol=1e-6)
    lam = 1.0 / (1.0 + np.exp(-np.asarray(gates, np.float64)))
    np.testing.assert_allclose(p[-1], np.prod(1.0 - lam, axis=0), rtol=1e-5)
    np.testing.assert_allclose(p[1], lam[1] * (1.0 - lam[0]), rtol=1e-5)
    np.testing.assert_allclose(p, np.asarray(ref.exit_shares(list(gates))),
                               rtol=1e-5, atol=1e-7)
    # a gate far from 0 loses nothing in the logarithm
    far = transformer._exit_log_shares(jnp.full((T - 1, 1), 60.0))
    assert np.isfinite(np.asarray(far)).all() and float(far[-1, 0]) < -150
    # on the trained-like state the shares differ a position and a loop step
    parts = family.loop_losses(CONFIG, FAMILY.state(), FAMILY.sample())
    assert sum(parts["exit_share"]) == pytest.approx(1.0, abs=1e-5)
    assert 0.02 < min(parts["exit_share"]) and max(parts["exit_share"]) < 0.9
    assert 0.3 < parts["exit_entropy"] < np.log(T)


def test_the_parts_of_the_loss_are_the_references():
    state, sample = FAMILY.state(), FAMILY.sample()
    parts = family.loop_losses(CONFIG, state, sample)
    with jax.default_matmul_precision("highest"):
        want = ref.forward(state, sample, **family._hyper(CONFIG))
    for name in ("loop", "exit_share", "exit_entropy"):
        np.testing.assert_allclose(parts[name], np.asarray(want[name]), rtol=2e-5)
    assert parts["main"] == parts["loop"][-1]
    loss = float(jax.jit(family.loss_fn(CONFIG))(state, sample))
    beta = CONFIG["exit_entropy_coef"]
    # the loop steps' losses differ, and the loss is no mean of them
    assert max(parts["loop"]) - min(parts["loop"]) > 1e-3
    assert loss == pytest.approx(float(want["loss"]), rel=1e-5)
    assert loss != pytest.approx(np.mean(parts["loop"]) - beta * parts["exit_entropy"],
                                 rel=1e-4)
    # `transformer_apply`: the last loop step's logits, no early exit
    mc = family.model_config(CONFIG)
    logits = jax.jit(lambda p, t: transformer.transformer_apply(p, t, mc))(
        state, sample[:, :-1])
    assert logits.dtype == jnp.float32
    assert harness.relative_error(logits, want["logits"]) <= 1e-5


def test_record_losses_sets_the_loops_gauges():
    parts = family.loop_losses(CONFIG, FAMILY.state(), FAMILY.sample())
    registry = metrics.Registry()
    transformer.record_losses(parts, registry)
    text = registry.render()
    assert f"kungfu_lm_loss {parts['main']}" in text
    for t in range(1, T + 1):
        assert f'kungfu_loop_loss{{step="{t}"}} {parts["loop"][t - 1]}' in text
        assert f'kungfu_exit_share{{step="{t}"}} {parts["exit_share"][t - 1]}' in text
    assert f"kungfu_exit_entropy {parts['exit_entropy']}" in text
    assert "kungfu_mtp_loss" not in text
    plain = metrics.Registry()
    transformer.record_losses({"main": 1.5}, plain)
    assert "kungfu_loop_loss" not in plain.render()


# -- (b) a shared leaf's gradient is the sum over its four uses ----------------

def test_a_shared_leafs_gradient_is_the_sum_over_four_copies_of_the_weights():
    state, sample = FAMILY.state(), FAMILY.sample()
    hyper = family._hyper(CONFIG)
    copies = [jax.tree.map(jnp.copy, state["layers"]) for _ in range(T)]
    with jax.default_matmul_precision("highest"):
        apart = jax.jit(jax.grad(lambda copies: ref.forward(
            state, sample, loop_layers=copies, **hyper)["loss"]))(copies)
    _, grads = FAMILY.baseline()
    for name, got in grads["layers"].items():
        each = [float(jnp.linalg.norm(c[name])) for c in apart]
        assert min(each) > 0, name
        summed = sum(c[name] for c in apart)
        assert harness.relative_error(got, summed) <= 1e-4, name
        # no one use is the gradient
        assert harness.relative_error(got, apart[-1][name]) > 0.3, name


# -- the loop written out in Python is the outer scan -------------------------

def unrolled_hidden(detached=None):
    """`transformer._hidden` of a loop as T scans in a Python loop, which is
    what the outer scan computes; `detached`: a loop step whose weights are
    constants of the loss (tests/test_ouro_faults.py)."""
    def hidden(params, tokens, cfg, each=None):
        x = transformer._embed(params, tokens, cfg)
        handed = []
        for t in range(cfg.loop_steps):
            stacked = params["layers"]
            if t == detached:
                stacked = jax.lax.stop_gradient(stacked)
            x, _ = jax.lax.scan(
                lambda x, layer: transformer._layer(x, layer, cfg), x, stacked)
            x, out = transformer._loop_step_end(x, params, cfg, each)
            handed.append(out)
        return jax.tree.map(lambda *a: jnp.stack(a), *handed), None

    return hidden


def test_four_scans_in_a_python_loop_are_the_outer_scan(monkeypatch, fresh_traces):
    loss, grads = FAMILY.baseline()
    monkeypatch.setattr(transformer, "_hidden", unrolled_hidden())
    got_loss, got = family.program_loss_and_grads(CONFIG)(
        FAMILY.state(), FAMILY.sample())
    assert fc.off(got_loss, loss) <= 1e-6
    assert harness.relative_error(got, grads) <= 1e-5


# -- (c) one loop step, no entropy, no second norms: the plain block ----------

def test_one_loop_step_without_second_norms_is_the_plain_blocks_program():
    looped = family.model_config(tiny_config(total_ut_steps=1, exit_entropy_coef=0.0))
    looped = dataclasses.replace(looped, post_norms=False)
    plain = TransformerConfig(
        vocab_size=256, d_model=64, n_heads=4, n_layers=2, d_ff=96,
        max_seq=looped.max_seq, dtype=jnp.float32, positions="rope",
        rope_theta=1e6, ffn="swiglu", tied_head=False, attn_core="flash",
        flash_blocks=(32, 32), flash_interpret=True, head_size=16,
        layer_remat=True)
    assert looped == plain
    params = init_transformer(jax.random.PRNGKey(1), looped)
    assert set(params) == {"embed", "lm_head", "ln_f_scale", "layers"}
    assert not [k for k in params["layers"] if "post" in k]
    # with the second norms the tree gains two leaves a layer and nothing else
    normed = init_transformer(jax.random.PRNGKey(1),
                              dataclasses.replace(plain, post_norms=True))
    assert set(normed["layers"]) - set(params["layers"]) == {
        "ln1_post_scale", "ln2_post_scale"}
    for name, leaf in params["layers"].items():
        np.testing.assert_array_equal(leaf, normed["layers"][name])
    # a second norm with weight 1 is no identity: the branch is normed
    sample = FAMILY.sample()
    loss = jax.jit(lambda p: transformer.transformer_loss(p, sample, plain))(params)
    other = jax.jit(lambda p: transformer.transformer_loss(
        p, sample, dataclasses.replace(plain, post_norms=True)))(normed)
    assert abs(float(loss) - float(other)) > 1e-4
    assert transformer.transformer_losses(params, sample, plain).keys() == {"main"}


# -- (d) two data members average each leaf once ------------------------------

def _two_member_step(optimizer):
    mc = family.model_config(CONFIG)
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    step = make_train_step(functools.partial(transformer.transformer_loss, cfg=mc),
                           optimizer, mesh, donate=False)
    return mc, mesh, step


def test_two_data_members_step_by_the_single_members_mean_gradient():
    """One S-SGD step of learning rate 1 on two members, a sequence each:
    parameters less the new parameters are the mean of the two sequences'
    gradients as one device computes them."""
    state, sample = FAMILY.state(), FAMILY.sample()
    optimizer = synchronous_sgd(optax.sgd(1.0), "dp")
    mc, mesh, step = _two_member_step(optimizer)
    new, _, loss = step(replicate(state, mesh),
                        replicate(optimizer.init(state), mesh),
                        shard_batch(sample, mesh))
    one = family.program_loss_and_grads(CONFIG)
    halves = [one(state, sample[i:i + 1]) for i in range(2)]
    assert float(loss) == pytest.approx(
        np.mean([float(l) for l, _ in halves]), rel=1e-6)
    want = jax.tree.map(lambda a, b: (a + b) / 2, halves[0][1], halves[1][1])
    moved = jax.tree.map(lambda p, q: p - q, state, new)
    assert harness.relative_error(moved, want) <= 1e-5
    for (path, m), w in zip(jax.tree_util.tree_leaves_with_path(moved),
                            jax.tree.leaves(want)):
        assert harness.relative_error(m, w) <= 1e-3, jax.tree_util.keystr(path)


def test_the_traced_step_reduces_each_leaf_once():
    """`_GradSync.reduced` lists every leaf the loss was called with, each
    once (the stacks whole, before the loop, and not a slice a loop step);
    the compiled two-member step all-reduces the parameters' bytes and the
    loss's scalar, and nothing inside a scan's body."""
    import re

    state, sample = FAMILY.state(), FAMILY.sample()
    mc = family.model_config(CONFIG)
    with collective.reducing_in_backward("dp") as sync:
        loss = sync.watching(functools.partial(transformer.transformer_loss, cfg=mc))
        jax.make_jaxpr(jax.shard_map(
            jax.grad(loss), mesh=make_mesh({"dp": 2}, devices=jax.devices()[:2]),
            in_specs=(PartitionSpec(), PartitionSpec("dp")),
            out_specs=PartitionSpec(), check_vma=False))(state, sample)
    leaves = jax.tree.leaves(state)
    assert sync.covers_all() and len(sync.reduced) == len(sync.seen) == len(leaves)
    assert sorted(map(id, sync.reduced)) == sorted(map(id, sync.seen))
    assert len(set(map(id, sync.reduced))) == len(leaves)

    optimizer = synchronous_sgd(optax.adamw(1e-3), "dp")
    _, mesh, step = _two_member_step(optimizer)
    text = step.lower(state, optimizer.init(state), sample).compile().as_text()
    reduced = re.findall(
        r"^\s*(?:ROOT )?%[\w.\-]+ = (.*?) all-reduce(?:-start)?\(", text, re.M)
    sizes = [int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
             for line in reduced for dims in re.findall(r"f32\[([\d,]*)\]", line)]
    assert sum(sizes) == sum(x.size for x in leaves) + 1
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    for body in bodies:
        block = text[text.index(f"{body} ("):]
        assert "all-reduce" not in block[:block.index("\n}\n")], body
    assert text.count("grad_allreduce") > 0


def test_the_new_scopes_are_in_the_programs_op_names():
    """`loop_norm`, `exit_gate`, `post_norm` inside `attn` and inside `ffn`,
    forward and backward; `head_loss` and the core inside the loop's body."""
    from benchmark import trace_reduce

    state = jax.eval_shape(lambda: family.init(CONFIG, 0))
    compiled = family.program_loss_and_grads(CONFIG).lower(
        state, FAMILY.sample()).compile()
    names = set(trace_reduce.scope_table(compiled.as_text()).values())
    backward = [name for name in names if "transpose(" in name]
    for scope in ("attn/post_norm/", "ffn/post_norm/", "loop_norm", "exit_gate",
                  "attn/attn_full/attn_core/", "head_loss", "ffn/"):
        assert any(scope in name for name in names), scope
        assert any(scope in name for name in backward), scope
    assert any("embed" in name for name in names)
    assert not any("moe" in name or "pos_embed" in name for name in names)
