"""What the layer scan of `models/transformer.py` saves for the backward
pass (PERF.md, PR 25): the dense attention core, RMSNorm (`models/blocks.py`)
and the gelu keep their inputs and recompute the rest. The values are those of the same model
without `jax.checkpoint`, on the dense, the pipeline and the ring path; a
core plugged from outside is never run twice; the declared precision holds;
and the residuals of `bert_base`'s step stay inside a budget that is read
off the traced program, on the CPU, before it costs a chip run."""

import functools
import importlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kungfu_tpu.models import blocks, transformer
from kungfu_tpu.models.transformer import (TransformerConfig, init_transformer,
                                           make_ring_transformer_loss,
                                           transformer_loss)
from kungfu_tpu.parallel import make_mesh
from kungfu_tpu.parallel.pipeline import make_pp_transformer_loss
from kungfu_tpu.telemetry import device

CFG = TransformerConfig(vocab_size=64, d_model=16, n_heads=2, n_layers=4,
                        d_ff=32, max_seq=16, dtype=jnp.float32)
LEAVES = ["embed", "pos_embed", "ln_f_scale", "layers/ln1_scale",
          "layers/ln2_scale", "layers/wqkv", "layers/wo", "layers/w_in",
          "layers/w_out"]


def _batch(B=4):
    tokens = jax.random.randint(jax.random.PRNGKey(7), (B, CFG.max_seq), 0,
                                CFG.vocab_size)
    targets = jax.random.randint(jax.random.PRNGKey(8), (B, CFG.max_seq), 0,
                                 CFG.vocab_size)
    return tokens, targets


def _dense():
    return functools.partial(transformer_loss, cfg=CFG)


def _pipeline():
    mesh = make_mesh({"pp": 2}, devices=jax.devices()[:2])
    return make_pp_transformer_loss(CFG, mesh, n_micro=2)


def _ring():
    mesh = make_mesh({"dp": 1, "sp": 2}, devices=jax.devices()[:2])
    return make_ring_transformer_loss(CFG, mesh)


PATHS = {"dense": _dense, "pipeline": _pipeline, "ring": _ring}


@pytest.fixture(scope="module")
def plain():
    """A second copy of `models/transformer.py` and of what it is built
    from (`models/blocks.py`, `models/mixers/`), imported anew with
    `jax.checkpoint` patched to the identity: the model as it was. The first
    copies are put back where they were, in `sys.modules` and on their
    packages."""
    import kungfu_tpu.models as package

    prefixes = tuple(package.__name__ + name
                     for name in (".blocks", ".mixers", ".transformer"))
    first = {name: module for name, module in sys.modules.items()
             if name.startswith(prefixes)}
    real = jax.checkpoint
    jax.checkpoint = lambda fn, **kwargs: fn
    for name in first:
        del sys.modules[name]
    try:
        module = importlib.import_module(transformer.__name__)
    finally:
        jax.checkpoint = real
        for name in [n for n in sys.modules if n.startswith(prefixes)]:
            del sys.modules[name]
        sys.modules.update(first)
        for name, module_ in first.items():
            parent, _, leaf = name.rpartition(".")
            setattr(sys.modules[parent], leaf, module_)
    assert module is not transformer and module._rmsnorm is not blocks._rmsnorm
    return module


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for inner in device._sub_jaxprs(eqn):
            yield from _primitives(inner)


@pytest.fixture(scope="module")
def both(plain):
    """{path: ((loss, grads) as committed, (loss, grads) with the plain
    copy's block and norm in the module's place)}. Each loss is built and
    traced anew on its side of the patch."""
    params = init_transformer(jax.random.PRNGKey(0), CFG)
    batch = _batch()
    out = {}
    for path, make in PATHS.items():
        sides = []
        for patched in (False, True):
            with pytest.MonkeyPatch.context() as m:
                if patched:
                    m.setattr(transformer, "_block", plain._block)
                    m.setattr(transformer, "_layer", plain._layer)
                    m.setattr(transformer, "_rmsnorm", plain._rmsnorm)
                fn = jax.value_and_grad(make())
                names = set(_primitives(jax.make_jaxpr(fn)(params, batch).jaxpr))
                # the comparison compares two different programs
                assert ("remat2" in names) != patched, (path, patched)
                # compiled, as a step runs it (a tenth of the time of the
                # same six programs an operation at a time: 61 s, PR 47)
                sides.append(jax.jit(fn)(params, batch))
        out[path] = sides
    return out


@pytest.mark.parametrize("path", sorted(PATHS))
def test_the_loss_is_that_of_the_model_without_checkpoints(both, path):
    (loss, _), (want, _) = both[path]
    assert abs(float(loss) - float(want)) <= 1e-6 * abs(float(want))


@pytest.mark.parametrize("leaf", LEAVES)
@pytest.mark.parametrize("path", sorted(PATHS))
def test_every_gradient_is_that_of_the_model_without_checkpoints(both, path,
                                                                 leaf):
    (_, grads), (_, want) = both[path]
    for key in leaf.split("/"):
        grads, want = grads[key], want[key]
    assert float(jnp.abs(want).max()) > 0
    np.testing.assert_allclose(grads, want, rtol=1e-6,
                               atol=1e-6 * float(jnp.abs(want).max()))


def test_the_leaves_are_the_whole_parameter_tree():
    params = init_transformer(jax.random.PRNGKey(0), CFG)
    paths = ["/".join(k.key for k in path)
             for path, _ in jax.tree_util.tree_leaves_with_path(params)]
    assert sorted(paths) == sorted(LEAVES)


def _counted(calls, key, fn):
    """`fn` with a host callback in front: `calls[key]` counts how often
    the compiled program really runs it, a layer at a time. The callback
    takes an operand that differs a layer, or the scan's partial evaluation
    hoists it out of the loop."""
    def core(*args, **kwargs):
        jax.debug.callback(
            lambda _: calls.update({key: calls.get(key, 0) + 1}),
            args[0].ravel()[0])
        return fn(*args, **kwargs)
    return core


def _scan_loss(core):
    """The layer scan with a core plugged from outside, as
    `ring_transformer_apply_shard` and flash attention's docstring do it."""
    def loss(params, batch):
        tokens, targets = batch
        x = params["embed"][tokens] + params["pos_embed"][:tokens.shape[1]]
        x, _ = jax.lax.scan(
            lambda h, layer: (transformer._block(h, layer, CFG, core=core), None),
            x, params["layers"])
        return transformer.lm_head_loss(params, x, targets, CFG)
    return loss


def _plain_core(q, k, v):
    return blocks._full_attention_core.__wrapped__(q, k, v)


def _custom_vjp_core(calls):
    """A core with residuals of its own, as `flash_attention` has them."""
    @jax.custom_vjp
    def core(q, k, v):
        return _plain_core(q, k, v)

    def fwd(q, k, v):
        return _counted(calls, "fwd", _plain_core)(q, k, v), (q, k, v)

    def bwd(res, g):
        return _counted(calls, "bwd", lambda g: jax.vjp(_plain_core, *res)[1](g))(g)

    core.defvjp(fwd, bwd)
    return core


@pytest.mark.parametrize("kind", ["function", "custom_vjp", "ring"])
def test_a_plugged_core_runs_once_a_layer_and_never_again(kind, monkeypatch):
    """Counted where the program runs, not where it is traced (a scan's body
    is traced once whatever it does): a checkpoint around `_attention`,
    `_block` or the scan's body would run the core a second time in the
    backward pass, rotate the ring's K/V twice and throw away the flash
    kernels' (o, lse)."""
    calls = {}
    params = init_transformer(jax.random.PRNGKey(0), CFG)
    if kind == "ring":
        from kungfu_tpu.ops import ring_attention

        monkeypatch.setattr(
            ring_attention, "ring_self_attention",
            _counted(calls, "fwd", ring_attention.ring_self_attention))
        loss, shards = _ring(), 2
    elif kind == "custom_vjp":
        loss, shards = _scan_loss(_custom_vjp_core(calls)), 1
    else:
        loss, shards = _scan_loss(_counted(calls, "fwd", _plain_core)), 1
    value, grads = jax.jit(jax.value_and_grad(loss))(params, _batch())
    jax.block_until_ready(grads)
    jax.effects_barrier()
    assert np.isfinite(float(value))
    assert calls["fwd"] == CFG.n_layers * shards
    if kind == "custom_vjp":
        assert calls["bwd"] == CFG.n_layers


def test_the_dense_core_is_the_checkpointed_one():
    """The other side of the test above: the default core is recomputed,
    so its softmax appears in the backward scan as well."""
    fn = jax.value_and_grad(_dense())
    params = init_transformer(jax.random.PRNGKey(0), CFG)
    scans = [e for e in jax.make_jaxpr(fn)(params, _batch()).jaxpr.eqns
             if e.primitive.name == "scan"]
    forward, backward = scans
    assert not forward.params["reverse"] and backward.params["reverse"]
    for scan in scans:
        assert "exp" in set(_primitives(scan.params["jaxpr"].jaxpr))


def test_the_declared_precision_holds_under_the_checkpoints():
    """`harness.precision_faults` walks into the remat jaxprs: bf16 blocks,
    f32 softmax sums over the head's width, f32 head matmul and loss."""
    from benchmark import harness
    from benchmark.families import transformer as family

    config = {"family": "transformer", "hidden_size": 64,
              "num_hidden_layers": 2, "num_attention_heads": 4,
              "intermediate_size": 128, "vocab_size": 256,
              "max_position_embeddings": 64, "param_dtype": "float32",
              "compute_dtype": "bfloat16", "head_dtype": "float32"}
    state = family.init(config, 5)
    sample = family.host_batch(config, 5, 0, family.REFERENCE_SAMPLES)
    traced = family.program_loss_and_grads(config).trace(state, sample)
    assert "remat2" in set(_primitives(traced.jaxpr.jaxpr))
    assert harness.precision_faults(config, family.head_width(config),
                                    traced.jaxpr, state, state) == []


# --- the residual budget at bert_base's size, nothing run or compiled ---

@pytest.fixture(scope="module")
def saved():
    cfg = TransformerConfig.bert_base()
    params = jax.eval_shape(lambda: init_transformer(jax.random.PRNGKey(0), cfg))
    batch = jax.ShapeDtypeStruct((16, 513), jnp.int32)
    return device.saved_bytes(functools.partial(transformer_loss, cfg=cfg),
                              params, batch)


def test_the_layer_scan_stacks_at_most_2_7_gb(saved):
    """9.84 GB in 32 arrays before PR 25; 2.59 GB in 14 since."""
    assert saved and sum(nbytes for _, _, nbytes in saved) <= 2.7e9


def test_no_probabilities_are_stacked(saved):
    assert not [s for s in saved if s[0][-2:] == (512, 512)]


def test_no_float32_activation_is_stacked(saved):
    assert not [s for s in saved if s[1] == "float32" and len(s[0]) >= 4]


def test_at_most_two_activations_of_the_feed_forward_width_are_stacked(saved):
    # rank 4: (layers, batch, positions, 3072); w_in's bf16 cast is rank 3
    assert len([s for s in saved if s[0][-1] == 3072 and len(s[0]) >= 4]) <= 2


def test_every_stacked_array_leads_with_the_layers(saved):
    assert all(shape[0] == 12 for shape, _, _ in saved)
    assert saved == sorted(saved, key=lambda s: -s[2])


def _tanh_layers(act):
    def loss(ws, x):
        y, _ = jax.lax.scan(lambda h, w: (act(h @ w), None), x, ws)
        return y.sum()
    return loss


WS = jax.ShapeDtypeStruct((2, 8, 8), jnp.float32)
X = jax.ShapeDtypeStruct((4, 8), jnp.float32)


def test_saved_bytes_reads_a_scan_by_hand():
    """Two layers of tanh(h @ w): the scan stacks each layer's input h for
    the matmul's transpose, and the tanh's output and a temporary of its
    derivative; w is the scan's own input and not stacked. A function
    without a scan saves nothing this reader counts."""
    assert device.saved_bytes(_tanh_layers(jnp.tanh), WS, X) == [
        ((2, 4, 8), "float32", 256)] * 3
    assert device.saved_bytes(lambda w, x: jnp.tanh(x @ w[0]).sum(), WS, X) == []


def test_saved_bytes_sees_a_checkpoint():
    """The same scan with its tanh checkpointed stacks h and the
    pre-activation, and no temporary."""
    act = jax.checkpoint(jnp.tanh, prevent_cse=False)
    assert device.saved_bytes(_tanh_layers(act), WS, X) == [
        ((2, 4, 8), "float32", 256)] * 2
