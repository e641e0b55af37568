"""The scopes in the program (`jax.named_scope`, vocabulary in
docs/telemetry.md): every name reaches the compiled HLO's `op_name`s in its
forward and backward form, `grad_allreduce` holds the gradients'
all-reduces, few instructions carry no scope, and the scopes are metadata
only: the StableHLO text a step lowers to is the same without them.
Single process, on the CPU mesh; nothing here opens a port."""

import contextlib
import functools
import re

import jax
import jax.numpy as jnp
import optax
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from kungfu_tpu.models.resnet import init_resnet, resnet18_thin, resnet_loss
from kungfu_tpu.models.transformer import (TransformerConfig, init_transformer,
                                           transformer_loss)
from kungfu_tpu.optimizers import (adaptive_sgd, synchronous_averaging,
                                   synchronous_sgd, zero_sharded)
from kungfu_tpu.parallel import make_mesh, make_train_step
from kungfu_tpu.telemetry import device

DP = 4
MODEL_SCOPES = {
    "transformer": ("embed", "attn", "attn_core", "ffn", "head_loss"),
    "moe": ("embed", "attn", "qk_norm", "rope", "attn_core", "moe",
            "moe_router", "moe_dispatch", "moe_experts", "moe_combine",
            "head_loss"),
    "resnet": ("ResNet", "conv_init", "bn_init", "BottleneckBlock_0",
               "BottleneckBlock_1", "Conv_0", "BatchNorm_0", "Dense_0",
               "head_loss"),
}
# XLA's own instructions (copies, bitcasts, parameters, tuples) carry no
# op_name, and the updates the step factory applies outside `optimizer` (the
# ResNet body's) and the loss's pmean carry none of the vocabulary. Read here:
# 12 % (transformer) and 21 % (ResNet) of the entry's and the loops'
# instructions that have an op_name at all.
# The expert layer's step: 21 %, most of them `shard_map/broadcast.<n>`s that
# the CPU's lowering of the sort and of the interpreted flash kernel hoists
# to the top; on the chip `unattributed_ms` reads what no scope claims.
UNSCOPED_LIMIT = {"transformer": 0.20, "moe": 0.25, "resnet": 0.30}


def _mesh():
    return make_mesh({"dp": DP}, devices=jax.devices()[:DP])


def _transformer_step(wrap, cfg=None):
    cfg = cfg or TransformerConfig.tiny()
    opt = wrap(optax.adamw(1e-3))
    step = make_train_step(functools.partial(transformer_loss, cfg=cfg), opt,
                           _mesh())
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    batch = jnp.zeros((2 * DP, 17), jnp.int32)
    return step, (params, opt.init(params), batch)


def _resnet_step(wrap):
    """The per-chip body a loss with auxiliary state needs (it has no place
    in `make_train_step`), as the benchmark's ResNet family has it, with the
    step factory's `optimizer` scope."""
    net = resnet18_thin()
    opt = wrap(optax.sgd(0.1, momentum=0.9))
    params, stats = init_resnet(jax.random.PRNGKey(0), net, image_size=32)

    def local_step(state, opt_state, batch):
        def loss_of(p):
            return resnet_loss(net, p, state["batch_stats"], batch)

        (loss, new_stats), grads = jax.value_and_grad(loss_of, has_aux=True)(
            state["params"])
        with jax.named_scope("optimizer"):
            updates, opt_state = opt.update(grads, opt_state, state["params"])
            new_params = optax.apply_updates(state["params"], updates)
        new_stats = jax.tree.map(lambda x: lax.pmean(x, "dp"), new_stats)
        return ({"params": new_params, "batch_stats": new_stats}, opt_state,
                lax.pmean(loss, "dp"))

    step = jax.jit(jax.shard_map(
        local_step, mesh=_mesh(), in_specs=(P(), P(), P("dp")),
        out_specs=(P(), P(), P()), check_vma=False))
    batch = (jnp.zeros((2 * DP, 32, 32, 3), jnp.float32),
             jnp.zeros((2 * DP,), jnp.int32))
    return step, ({"params": params, "batch_stats": stats}, opt.init(params),
                  batch)


def _moe_step(wrap):
    """Every mechanism of the OLMoE layer on (`tiny_moe`), 16 positions."""
    return _transformer_step(wrap, TransformerConfig.tiny_moe(
        flash_blocks=(16, 16)))


STEPS = {"transformer": _transformer_step, "moe": _moe_step,
         "resnet": _resnet_step}


def _zero_step(wrap):
    """`make_train_step`'s body with the optimizer's state sharded over the
    axis, as `zero_sharded` needs it: each replica makes and keeps the
    state of its shard."""
    cfg = TransformerConfig.tiny()
    # momentum's state is arrays only: a scalar step count has no shard
    opt = wrap(optax.sgd(0.1, momentum=0.9))
    loss_fn = functools.partial(transformer_loss, cfg=cfg)

    def local_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        with jax.named_scope("optimizer"):
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, lax.pmean(loss, "dp")

    step = jax.jit(jax.shard_map(
        local_step, mesh=_mesh(), in_specs=(P(), P("dp"), P("dp")),
        out_specs=(P(), P("dp"), P()), check_vma=False))
    init = jax.jit(jax.shard_map(opt.init, mesh=_mesh(), in_specs=P(),
                                 out_specs=P("dp"), check_vma=False))
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    return step, (params, init(params), jnp.zeros((2 * DP, 17), jnp.int32))


WRAPPERS = {
    "synchronous_sgd": lambda base: synchronous_sgd(base, "dp"),
    "zero_sharded": lambda base: zero_sharded(base, DP, "dp"),
    "synchronous_averaging": lambda base: synchronous_averaging(base, "dp"),
    "adaptive_sgd": lambda base: adaptive_sgd(base, 3, "dp"),
}


@functools.lru_cache(maxsize=None)
def _table(model: str, wrapper: str = "synchronous_sgd") -> dict:
    build = _zero_step if wrapper == "zero_sharded" else STEPS[model]
    step, args = build(WRAPPERS[wrapper])
    compiled = step.lower(*args).compile()
    return {"table": device.scope_table(compiled), "text": compiled.as_text()}


_parts = device.scope_parts


def _has(table, scope, form):
    """Some instruction lies under `scope` in the forward (`jvp(`, no
    `transpose(`) or backward (`transpose(`) form. A scope at the top of
    the differentiated function is written `jvp(scope)`; one further down
    follows as a component of its own."""
    for op_name in table.values():
        parts = _parts(op_name)
        under = any(p == scope or p in (f"jvp({scope})",
                                        f"transpose(jvp({scope}))")
                    for p in parts)
        backward = any(p.startswith("transpose(") for p in parts)
        if under and backward == (form == "backward"):
            return True
    return False


@pytest.mark.parametrize("form", ["forward", "backward"])
@pytest.mark.parametrize("model,scope", [
    (m, s) for m, scopes in MODEL_SCOPES.items() for s in scopes])
def test_model_scope_reaches_the_compiled_program(model, scope, form):
    assert _has(_table(model)["table"], scope, form), (
        f"no {form} instruction of the {model} step under {scope!r}")


@pytest.mark.parametrize("model", sorted(STEPS))
@pytest.mark.parametrize("scope", ["optimizer", "grad_allreduce",
                                   "optimizer_update"])
def test_optimizer_scopes_reach_the_compiled_program(model, scope):
    table = _table(model)["table"]
    assert any(scope in _parts(v) for v in table.values())


def _names(op_name):
    """Every word of a scope path, the transforms' wrappers opened: JAX
    writes a scope at the top of a transposed function into the wrapper,
    `transpose(jvp(grad_allreduce))`."""
    return {word for p in _parts(op_name) for word in re.split(r"[()]", p)
            if word}


@pytest.mark.parametrize("model", sorted(STEPS))
def test_optimizer_scopes_nest_under_the_step_factorys(model):
    """`optimizer_update` lies under the step factory's `optimizer`, and so
    does `grad_allreduce` where the optimizer's own `pmean` reduces (the
    ResNet body); under plain S-SGD through `make_train_step` on four
    members the loss reduces, and `grad_allreduce` lies in the backward
    pass."""
    for op_name in _table(model)["table"].values():
        parts = _parts(op_name)
        if "optimizer_update" in parts:
            assert "optimizer" in parts, op_name
        # (an all-reduce's own `add` is named from inside the reduction)
        if "grad_allreduce" in _names(op_name) and op_name.startswith("jit("):
            in_backward = any(p.startswith("transpose(") for p in parts)
            assert in_backward == (model != "resnet"), op_name
            assert in_backward or "optimizer" in parts, op_name


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("model", sorted(STEPS))
def test_grad_allreduce_holds_every_all_reduce_of_the_gradients(model):
    """Read off the traced program, before XLA combines all-reduces (on the
    CPU it makes one of the gradients', the batch statistics' and the
    loss's, under the first's name): one `psum` a parameter leaf lies under
    `grad_allreduce`, and the others (the loss's, ResNet's statistics')
    do not. Where the loss reduces in its backward pass (the transformer
    under plain S-SGD, PR 29), the stacked layers' `psum` is the scan
    body's, over one layer's slices."""
    step, args = STEPS[model](WRAPPERS["synchronous_sgd"])
    params = args[0]["params"] if model == "resnet" else args[0]
    psums = [e for e in _eqns(step.trace(*args).jaxpr.jaxpr)
             if e.primitive.name == "psum"]
    under = [e for e in psums
             if "grad_allreduce" in _names(f"{e.source_info.name_stack}/psum")]
    assert sum(len(e.invars) for e in under) == len(jax.tree.leaves(params))
    shapes = sorted(v.aval.shape for e in under for v in e.invars)
    stacked = params.get("layers", {}) if model != "resnet" else {}
    assert shapes == sorted(
        [l.shape[1:] for l in jax.tree.leaves(stacked)]
        + [l.shape for l in jax.tree.leaves(
            {k: v for k, v in params.items() if k != "layers"}
            if stacked else params)])
    assert len(psums) > len(under)  # the loss's, at least, is outside


def _all_reduces(text):
    """The op_name of every all-reduce instruction of a compiled program."""
    found = []
    for line in text.splitlines():
        if re.match(r"\s*(?:ROOT\s+)?%?[\w.\-]+ = .*? all-reduce(?:-start)?\(", line):
            scope = re.search(r'op_name="([^"]*)"', line)
            found.append(scope.group(1) if scope else "")
    return found


def test_the_compiled_all_reduce_keeps_the_scope():
    """In the transformer step the gradients' all-reduce is the program's
    first, so the combined instruction carries `grad_allreduce`: what the
    benchmark's reader finds in the trace."""
    reduces = _all_reduces(_table("transformer")["text"])
    assert reduces and all("grad_allreduce" in _names(r) for r in reduces)


@pytest.mark.parametrize("model", sorted(STEPS))
def test_few_instructions_carry_no_scope(model):
    table = _table(model)["table"]
    none = [v for v in table.values() if device.phase_of(v) == "unattributed"]
    # parameters are named by their argument (`params['embed']`), not a scope
    none = [v for v in none if "/" in v]
    assert len(none) / len(table) < UNSCOPED_LIMIT[model], (
        len(none), len(table), sorted(set(none))[:20])


@pytest.mark.parametrize("model", sorted(STEPS))
@pytest.mark.parametrize("phase", ["forward", "backward", "optimizer",
                                   "all_reduce"])
def test_every_phase_has_instructions(model, phase):
    table = _table(model)["table"]
    assert any(device.phase_of(v) == phase for v in table.values())


@pytest.mark.parametrize("wrapper", ["zero_sharded", "synchronous_averaging",
                                     "adaptive_sgd"])
@pytest.mark.parametrize("scope", ["grad_allreduce", "optimizer_update"])
def test_the_other_wrappers_use_the_same_two_names(wrapper, scope):
    table = _table("transformer", wrapper)["table"]
    assert any(scope in _parts(v) for v in table.values())
    collectives = [v for v in table.values()
                   if v.rsplit("/", 1)[-1] in ("psum", "psum_scatter",
                                               "reduce_scatter")]
    assert any("grad_allreduce" in _parts(v) for v in collectives)


def _no_scopes(monkeypatch):
    """`jax.named_scope` does nothing for the rest of the test."""
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())


@pytest.mark.parametrize("model", sorted(STEPS))
def test_scopes_are_metadata_only(model, monkeypatch):
    """The StableHLO text without debug info, which PERF.md hashes, is byte
    for byte the same with `jax.named_scope` patched to do nothing: no
    metric can move."""
    step, args = STEPS[model](WRAPPERS["synchronous_sgd"])
    with_scopes = step.lower(*args).as_text()
    _no_scopes(monkeypatch)
    step, args = STEPS[model](WRAPPERS["synchronous_sgd"])
    assert with_scopes == step.lower(*args).as_text()


def test_the_patched_scope_is_really_off(monkeypatch):
    """The comparison above compares two different traces: with the patch
    the compiled program carries none of the transformer's names."""
    _no_scopes(monkeypatch)
    step, args = _transformer_step(WRAPPERS["synchronous_sgd"])
    table = device.scope_table(step.lower(*args).compile())
    assert not any("attn_core" in v or "grad_allreduce" in v
                   for v in table.values())


@pytest.mark.parametrize("op_name,want", [
    ("jit(local_step)/shard_map/jvp()/while/body/closed_call/attn/attn_core/dot_general",
     ["jvp()", "attn", "attn_core"]),
    ("jit(local_step)/shard_map/transpose(jvp(head_loss))/jit(log_softmax)/mul",
     ["transpose(jvp(head_loss))"]),
    ("jit(local_step)/shard_map/optimizer/grad_allreduce/psum",
     ["optimizer", "grad_allreduce"]),
    ("jit(local_step)/shard_map/psum", []),
    ("params['embed']", []),
    ("", []),
    # what a `jax.checkpoint` inside a scope adds (PR 25): the wrapper and,
    # for what the backward pass recomputes, `rematted_computation`, with
    # the scope path written a second time in front of them
    ("jit(local_step)/transpose(jvp())/while/body/closed_call/checkpoint/attn/attn_core/bhqd,bhkd->bhqk/dot_general",
     ["transpose(jvp())", "attn", "attn_core", "bhqd,bhkd->bhqk"]),
    ("jit(local_step)/shard_map/transpose(jvp())/while/body/closed_call/attn/attn_core/attn/attn_core/checkpoint/rematted_computation/reduce_max",
     ["transpose(jvp())", "attn", "attn_core", "attn", "attn_core"]),
    ("jit(local_step)/shard_map/transpose(jvp())/while/body/closed_call/ffn/ffn/checkpoint/rematted_computation/tanh",
     ["transpose(jvp())", "ffn", "ffn"]),
    ("jit(local_step)/shard_map/transpose(jvp(head_loss))/jvp(head_loss)/checkpoint/rematted_computation/rsqrt",
     ["transpose(jvp(head_loss))", "jvp(head_loss)"]),
    ("jit(local_step)/shard_map/jvp()/while/body/closed_call/attn/attn/checkpoint/rsqrt",
     ["jvp()", "attn", "attn"]),
])
def test_scope_parts(op_name, want):
    assert device.scope_parts(op_name) == want


BLOCK = "jit(local_step)/shard_map/jvp()/while/body/closed_call"
BACK = "jit(local_step)/shard_map/transpose(jvp())/while/body/closed_call"


@pytest.mark.parametrize("op_name,phase", [
    (f"{BACK}/attn/attn_core/bhqk,bhkd->bhqd/dot_general", "backward"),
    (f"{BLOCK}/attn/attn_core/reduce_max", "forward"),
    (f"{BLOCK}/ffn/dot_general", "forward"),
    ("jit(local_step)/shard_map/optimizer/grad_allreduce/psum", "all_reduce"),
    ("jit(local_step)/shard_map/optimizer/grad_allreduce/div", "all_reduce"),
    ("jit(local_step)/shard_map/optimizer/optimizer_update/mul", "optimizer"),
    ("jit(local_step)/optimizer/add", "optimizer"),
    ("jit(step)/optimizer_update/sqrt", "optimizer"),
    ("jit(local_step)/shard_map/jvp()/while", "forward"),
    ("jit(local_step)/shard_map/transpose(jvp())/while", "backward"),
    ("jit(local_step)/while", "unattributed"),  # a bare while
    ("jit(local_step)/shard_map/psum", "unattributed"),  # the loss's
    ("jit(local_step)/add", "unattributed"),
    ("params['embed']", "unattributed"),
    ("", "unattributed"),
    (None, "unattributed"),
    ("jit(local_step)/jvp(head_loss)/jit(log_softmax)/reduce_max", "forward"),
    ("jit(local_step)/transpose(jvp(head_loss))/jit(take_along_axis)/scatter-add",
     "backward"),
    ("jit(local_step)/jvp(ResNet)/BottleneckBlock_12/BatchNorm_1/mul", "forward"),
    ("jit(local_step)/transpose(jvp(ResNet))/bn_init/reduce_sum", "backward"),
    # whole components only: a scope that merely contains a name of the rule
    ("jit(local_step)/my_optimizer_update/mul", "forward"),
    # a recomputed op runs in the backward pass and counts there (PR 25)
    ("jit(local_step)/transpose(jvp())/while/body/closed_call/checkpoint/attn/attn_core/bhqd,bhkd->bhqk/dot_general",
     "backward"),
    (f"{BACK}/attn/attn_core/attn/attn_core/checkpoint/rematted_computation/bhqd,bhkd->bhqk/dot_general",
     "backward"),
    (f"{BACK}/attn/attn_core/attn/attn_core/checkpoint/rematted_computation/exp", "backward"),
    (f"{BACK}/ffn/ffn/checkpoint/rematted_computation/tanh", "backward"),
    (f"{BACK}/attn/attn/checkpoint/rematted_computation/rsqrt", "backward"),
    ("jit(local_step)/shard_map/transpose(jvp(head_loss))/jvp(head_loss)/checkpoint/rematted_computation/rsqrt",
     "backward"),
    # the same pieces where they first run
    (f"{BLOCK}/attn/attn_core/exp", "forward"),
    (f"{BLOCK}/ffn/ffn/checkpoint/tanh", "forward"),
])
def test_the_phase_rule_case_by_case(op_name, phase):
    assert device.phase_of(op_name) == phase


@pytest.mark.parametrize("scope", ["attn_core", "attn", "ffn"])
def test_what_the_backward_pass_recomputes_keeps_its_scope(scope):
    """The dense core, the norms (`attn`, `ffn`) and the gelu (`ffn`) are
    recomputed in the backward pass (PR 25): the compiled step holds their
    `rematted_computation`, every instruction of it sorts under backward,
    and its scope is still among its parts."""
    # `jit(`: a reducer (the `add` of a reduce_sum) is a computation of its
    # own and carries the path from the scope on, without the loop's
    again = [v for v in _table("transformer")["table"].values()
             if v.startswith("jit(") and "rematted_computation" in v.split("/")
             and scope == _parts(v)[-1]]
    assert again
    assert {device.phase_of(v) for v in again} == {"backward"}


def test_the_dense_core_has_a_forward_and_a_recomputed_matmul():
    """QK^T is in the program three times: forward, recomputed, and
    transposed for the gradients of q and k."""
    table = _table("transformer")["table"]
    qk = [v for v in table.values() if v.endswith("bhqd,bhkd->bhqk/dot_general")]
    assert any(device.phase_of(v) == "forward" for v in qk)
    assert any("rematted_computation" in v and device.phase_of(v) == "backward"
               for v in qk)


def test_the_compile_cache_is_keyed_by_the_scopes():
    """JAX's cache key leaves metadata out by default (it hashes the text
    the test above shows to be the same), so an executable cached before a
    scope changed would serve its old names; `enable_compile_cache` asks
    for the key that holds them."""
    from kungfu_tpu.parallel import chip

    flag = "jax_compilation_cache_include_metadata_in_key"
    before = {k: getattr(jax.config, k)
              for k in (flag, "jax_compilation_cache_dir")}
    try:
        jax.config.update(flag, False)
        chip.enable_compile_cache()
        assert getattr(jax.config, flag) is True
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
