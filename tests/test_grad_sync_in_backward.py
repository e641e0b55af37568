"""Plain S-SGD through `make_train_step` reduces each gradient inside the
backward pass (`ops.collective.reduce_in_backward`, which
`models.transformer` calls in its layer scan), exactly once, to the values
the optimizer's own `pmean` gives; and every caller that is not that (one
member on the axis, another wrapper, `synchronous_sgd.update` by hand, a
loss that reduces nothing) traces the program it traced before, text for
text. Single process, on the CPU mesh; nothing here opens a port."""

import contextlib
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from kungfu_tpu.models.transformer import (TransformerConfig, init_transformer,
                                           transformer_loss)
from kungfu_tpu.ops import collective
from kungfu_tpu.ops.hierarchical import synchronous_sgd_hierarchical
from kungfu_tpu.optimizers import (adaptive_sgd, core, synchronous_averaging,
                                   synchronous_sgd, zero_sharded)
from kungfu_tpu.parallel import make_mesh, make_train_step
from kungfu_tpu.parallel.dp import replicate, shard_batch
from kungfu_tpu.telemetry import metrics

DP = 4
CONFIGS = {
    "tiny": TransformerConfig.tiny,
    "tiny_moe": lambda: TransformerConfig.tiny_moe(flash_blocks=(16, 16)),
}
GAUGES = ("kungfu_grad_bytes_reduced_in_backward",
          "kungfu_grad_bytes_reduced_by_optimizer")


def _mesh(n):
    return make_mesh({"dp": n}, devices=jax.devices()[:n])


def _tokens(cfg, n, seed=1):
    """n sequences of 17 tokens, every row another."""
    return jax.random.randint(jax.random.PRNGKey(seed), (n, 17), 0,
                              cfg.vocab_size)


def _transformer(cfg, optimizer, n):
    step = make_train_step(functools.partial(transformer_loss, cfg=cfg),
                           optimizer, _mesh(n), donate=False)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    return step, (params, optimizer.init(params), _tokens(cfg, 2 * n))


def _mechanism_off(monkeypatch):
    """For the rest of the test no axis is ever declared and the identity
    is Python's: the code the parent had."""
    monkeypatch.setattr(collective, "reducing_in_backward", contextlib.contextmanager(
        lambda axis_name: (yield collective._GradSync(axis_name))))
    monkeypatch.setattr(collective, "reduce_in_backward", lambda params, of=None: params)


def _nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _gauges() -> tuple:
    return tuple(int(metrics.REGISTRY.get(name).value) for name in GAUGES)


# -- (a) one member on the axis: the parent's program, text for text ---------


def _by_hand_step(n):
    """A `local_step`-style body as the benchmark's ResNet family has it:
    `synchronous_sgd(...).update` called directly under a `shard_map` of
    the caller's own, around a model with no scan."""
    opt = synchronous_sgd(optax.sgd(0.1, momentum=0.9), "dp")

    def loss_of(params, batch):
        hidden = jnp.tanh(batch @ params["w_in"])
        return jnp.mean((hidden @ params["w_out"]) ** 2)

    def local_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_of)(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                lax.pmean(loss, "dp"))

    step = jax.jit(jax.shard_map(
        local_step, mesh=_mesh(n), in_specs=(P(), P(), P("dp")),
        out_specs=(P(), P(), P()), check_vma=False))
    params = {"w_in": jnp.ones((8, 16)), "w_out": jnp.ones((16, 4))}
    return step, (params, opt.init(params), jnp.ones((2 * n, 8)))


def _ssgd_transformer(model, n):
    return _transformer(CONFIGS[model](),
                        synchronous_sgd(optax.adamw(1e-3), "dp"), n)


ONE_MEMBER = {
    **{model: functools.partial(_ssgd_transformer, model) for model in CONFIGS},
    "update_by_hand": _by_hand_step,
}


@pytest.mark.parametrize("body,n", [(body, 1) for body in sorted(ONE_MEMBER)]
                         + [("update_by_hand", DP)])
def test_text_is_the_parents(body, n, monkeypatch):
    """The StableHLO text without debug info, which PERF.md hashes at real
    size for the one-chip cells: with one member on the axis nothing of the
    mechanism is traced, nor, on any mesh, by a body that calls
    `synchronous_sgd(...).update` itself."""
    step, args = ONE_MEMBER[body](n)
    text = step.lower(*args).as_text()
    _mechanism_off(monkeypatch)
    step, args = ONE_MEMBER[body](n)
    assert text == step.lower(*args).as_text()


@pytest.mark.parametrize("model", sorted(CONFIGS))
def test_the_patch_is_really_off(model, monkeypatch):
    """The comparisons here compare two different traces: on four members
    plain S-SGD's text has the all-reduce in the backward scan's body, and
    with the patch it has not."""
    in_scan = "while/body/closed_call/grad_allreduce"

    def compiled_text():
        step, args = _ssgd_transformer(model, DP)
        return step.lower(*args).compile().as_text()

    assert in_scan in compiled_text()
    _mechanism_off(monkeypatch)
    assert in_scan not in compiled_text()


# -- (b) the values are S-SGD's ----------------------------------------------


def _one_step(step, args, n):
    params, opt_state, batch = args
    mesh = _mesh(n)
    return step(replicate(params, mesh), replicate(opt_state, mesh),
                shard_batch(batch, mesh))


def _assert_close(got, want, atol=1e-6):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=0, atol=atol)


@pytest.mark.parametrize("model", sorted(CONFIGS))
def test_one_step_equals_the_post_hoc_pmean(model, monkeypatch):
    """New parameters, optimizer state and loss after one step on four
    members, different rows on each, against the step whose optimizer
    reduces after the backward pass."""
    def one_step():
        return _one_step(*_ssgd_transformer(model, DP), DP)

    got = one_step()
    assert _gauges()[0] > 0 and _gauges()[1] == 0
    _mechanism_off(monkeypatch)
    want = one_step()
    assert _gauges()[0] == 0
    _assert_close(got, want)
    params = init_transformer(jax.random.PRNGKey(0), CONFIGS[model]())
    moved = [float(jnp.max(jnp.abs(a - b))) for a, b in
             zip(jax.tree.leaves(got[0]), jax.tree.leaves(params))]
    assert min(moved) > 1e-5  # the step did update every leaf


# -- (c) every leaf is reduced exactly once ----------------------------------

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$")
_ALL_REDUCE = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = (.*?) all-reduce(?:-start)?\(")
_SHAPE = re.compile(r"(f32|bf16|s32|u32)\[([\d,]*)\]")
_ITEMSIZE = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4}


def _all_reduces(hlo_text: str) -> list:
    """[(computation, [shape, ...], bytes, op_name)] of a compiled
    program's all-reduce instructions."""
    found, computation = [], None
    for line in hlo_text.splitlines():
        header = _COMPUTATION.match(line)
        if header:
            computation = header.group(1)
        hit = _ALL_REDUCE.match(line)
        if not hit:
            continue
        shapes = [(kind, tuple(int(d) for d in dims.split(",") if d))
                  for kind, dims in _SHAPE.findall(hit.group(1))]
        nbytes = sum(_ITEMSIZE[kind] * int(np.prod(dims, dtype=np.int64))
                     for kind, dims in shapes)
        op_name = re.search(r'op_name="([^"]*)"', line)
        found.append((computation, [dims for _, dims in shapes], nbytes,
                      op_name.group(1) if op_name else ""))
    return found


@pytest.mark.parametrize("model", sorted(CONFIGS))
def test_every_leaf_is_all_reduced_exactly_once(model):
    """A second `pmean` of a mean changes no value, so only a count can see
    it. In the compiled four-member program the all-reduced bytes are the
    parameters' bytes plus the loss's scalar; the layer leaves' all-reduces
    sit in the body of the backward scan (once an iteration: a slice's
    bytes times the layers), and what the entry computation reduces after
    it is the leaves outside the stack and the loss."""
    cfg = CONFIGS[model]()
    step, args = _ssgd_transformer(model, DP)
    params = args[0]
    text = step.lower(*args).compile().as_text()
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    in_scan = [r for r in _all_reduces(text) if r[0] in bodies]
    outside = [r for r in _all_reduces(text) if r[0] not in bodies]
    assert in_scan and all("transpose(jvp" in r[3] and "grad_allreduce" in r[3]
                           for r in in_scan)
    stack = _nbytes(params["layers"])
    rest = _nbytes({k: v for k, v in params.items() if k != "layers"})
    assert sum(r[2] for r in in_scan) * cfg.n_layers == stack
    assert sum(r[2] for r in outside) == rest + 4
    slices = sorted(x.shape[1:] for x in jax.tree.leaves(params["layers"]))
    assert sorted(s for r in in_scan for s in r[1]) == slices
    stacked = {x.shape for x in jax.tree.leaves(params["layers"])}
    assert not stacked & {s for r in outside for s in r[1]}
    assert sum(r[2] for r in in_scan) * cfg.n_layers + sum(
        r[2] for r in outside) == _nbytes(params) + 4


# -- (d) the wrappers that are not plain S-SGD trace the parent's text -------

OTHERS = {
    "synchronous_averaging": lambda: synchronous_averaging(
        optax.sgd(0.1, momentum=0.9), "dp"),
    "adaptive_sgd": lambda: adaptive_sgd(optax.sgd(0.1, momentum=0.9), 3, "dp"),
    "zero_sharded": lambda: zero_sharded(optax.sgd(0.1, momentum=0.9), DP, "dp"),
    "synchronous_sgd_hierarchical": lambda: synchronous_sgd_hierarchical(
        optax.sgd(0.1, momentum=0.9), "dp"),
    # a transformation built from S-SGD's product is not S-SGD's product
    "chained": lambda: optax.chain(
        optax.clip_by_global_norm(1.0),
        synchronous_sgd(optax.sgd(0.1, momentum=0.9), "dp")),
    # plain S-SGD over another axis than the step's
    "another_axis": lambda: synchronous_sgd(optax.sgd(0.1), "tp"),
}


def _other_text(name: str) -> str:
    cfg = TransformerConfig.tiny()
    mesh = (make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:DP])
            if name == "another_axis" else _mesh(DP))
    optimizer = OTHERS[name]()
    step = make_train_step(functools.partial(transformer_loss, cfg=cfg),
                           optimizer, mesh)
    params = jax.eval_shape(
        lambda: init_transformer(jax.random.PRNGKey(0), cfg))
    # the state as one member holds it (`zero_sharded`'s is its shard's)
    opt_state = jax.eval_shape(jax.shard_map(
        optimizer.init, mesh=mesh, in_specs=P(), out_specs=P(),
        check_vma=False), params)
    batch = jax.ShapeDtypeStruct((2 * DP, 17), jnp.int32)
    if name == "synchronous_sgd_hierarchical":
        # its host callback lowers only under `make_hier_train_step`'s own
        # mesh handling: the trace is compared as the jaxpr it is
        jaxpr = str(jax.make_jaxpr(step)(params, opt_state, batch))
        return re.sub(r" at 0x[0-9a-f]+", "", jaxpr)  # the callback's address
    return step.lower(params, opt_state, batch).as_text()


@pytest.mark.parametrize("name", sorted(OTHERS))
def test_the_other_wrappers_trace_the_parents_text(name, monkeypatch):
    text = _other_text(name)
    assert _gauges() == (0, 0)
    _mechanism_off(monkeypatch)
    assert text == _other_text(name)


def test_synchronous_sgd_says_what_it_is():
    base = optax.adamw(1e-3)
    opt = synchronous_sgd(base, "dp")
    assert isinstance(opt, optax.GradientTransformation)
    assert isinstance(opt, core.SynchronousSGD) and opt.axis_name == "dp"
    assert opt.init is base.init and callable(opt.update_reduced)
    init, update = opt  # still the pair optax takes it for
    assert init is opt.init and update is opt.update
    for built in (optax.chain(opt), adaptive_sgd(base, 3, "dp"),
                  synchronous_averaging(base, "dp")):
        assert not isinstance(built, core.SynchronousSGD)


# -- (e) a loss that reduces nothing, or not everything, falls back ----------


def _mlp_loss(params, batch):
    x, y = batch[:, :8], batch[:, 8:]
    return jnp.mean((jnp.tanh(x @ params["w_in"]) @ params["w_out"] - y) ** 2)


def _half_reduced_loss(params, batch):
    """Reduces one of its two leaves itself: not everything."""
    return _mlp_loss({"w_in": collective.reduce_in_backward(params["w_in"]),
                      "w_out": params["w_out"]}, batch)


def _fully_reduced_loss(params, batch):
    return _mlp_loss(collective.reduce_in_backward(params), batch)


@pytest.mark.parametrize("loss_fn,expected", [
    (_mlp_loss, "by_optimizer"), (_half_reduced_loss, "by_optimizer"),
    (_fully_reduced_loss, "in_backward")])
def test_a_loss_decides_by_what_it_reduces(loss_fn, expected):
    """Against S-SGD written out: local gradients, `pmean`, the base's
    update. A loss that ignores the declared axis, or covers a part of its
    leaves, gets `optimizer.update` as ever (a leaf the loss did reduce is
    then reduced again: the values stand); one that covers every leaf gets
    the base update and no second all-reduce."""
    base = optax.adamw(1e-2)
    key_in, key_out, key_batch = jax.random.split(jax.random.PRNGKey(3), 3)
    params = {"w_in": jax.random.normal(key_in, (8, 16)),
              "w_out": jax.random.normal(key_out, (16, 4))}
    batch = jax.random.normal(key_batch, (2 * DP, 12))
    step = make_train_step(loss_fn, synchronous_sgd(base, "dp"), _mesh(DP),
                           donate=False)
    got = _one_step(step, (params, base.init(params), batch), DP)
    total = _nbytes(params)
    assert _gauges() == ((total, 0) if expected == "in_backward" else (0, total))
    text = step.lower(params, base.init(params), batch).compile().as_text()
    assert sum(r[2] for r in _all_reduces(text)) == total + 4 + (
        _nbytes(params["w_in"]) if loss_fn is _half_reduced_loss else 0)

    def written_out(params, opt_state, batch):
        loss, grads = jax.value_and_grad(_mlp_loss)(params, batch)
        grads = jax.tree.map(lambda g: lax.pmean(g, "dp"), grads)
        updates, opt_state = base.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                lax.pmean(loss, "dp"))

    reference = jax.jit(jax.shard_map(
        written_out, mesh=_mesh(DP), in_specs=(P(), P(), P("dp")),
        out_specs=(P(), P(), P()), check_vma=False))
    _assert_close(got, _one_step(reference, (params, base.init(params), batch), DP))


def test_a_slice_must_be_a_slice_of_what_it_names():
    stacked = {"w": jnp.zeros((3, 4, 5))}
    with collective.reducing_in_backward("dp"):
        with pytest.raises(ValueError, match="no slice"):
            collective.reduce_in_backward({"w": jnp.zeros((5, 4))}, of=stacked)


def test_no_axis_declared_means_nothing_is_traced():
    tree = {"w": jnp.ones((2, 3))}
    assert collective.reduce_in_backward(tree) is tree
    assert collective.reduce_in_backward(tree["w"], of=tree) is tree["w"]
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: jnp.sum(collective.reduce_in_backward(p)["w"])))(tree)
    assert "custom_vjp" not in str(jaxpr) and "psum" not in str(jaxpr)


# -- the counter that says it engaged ----------------------------------------


@pytest.mark.parametrize("members,expected", [(DP, (435_151_872, 0)),
                                              (1, (0, 0))])
def test_the_gauges_at_bert_base(members, expected):
    """`bert_base`'s shapes, nothing computed: the step is traced by
    `eval_shape` and the gauges say who reduces its 108.8 M float32
    gradients."""
    cfg = TransformerConfig.bert_base()
    opt = synchronous_sgd(optax.adamw(3e-4), "dp")
    step = make_train_step(functools.partial(transformer_loss, cfg=cfg), opt,
                           _mesh(members))
    params = jax.eval_shape(
        lambda: init_transformer(jax.random.PRNGKey(0), cfg))
    assert _nbytes(params) == 435_151_872
    jax.eval_shape(step, params, jax.eval_shape(opt.init, params),
                   jax.ShapeDtypeStruct((2 * members, 33), jnp.int32))
    assert _gauges() == expected
    rendered = metrics.REGISTRY.render()
    assert all(name in rendered for name in GAUGES)
