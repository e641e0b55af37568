"""The seam between a layer and its token mixer (`models/mixers/`): the table
is what `TransformerConfig` accepts, a record's leaves and shardings are one
set of names, a record planted in the table trains with no other edit, and
nothing below `models/transformer.py` imports it. Tiny shapes; one small step
is compiled."""

import ast
import pathlib
import re
import types

import jax
import jax.numpy as jnp
import pytest

from family_cases import FAMILIES
from kungfu_tpu.models import blocks, mixers
from kungfu_tpu.models.transformer import (TransformerConfig, init_transformer,
                                           param_pspecs, transformer_loss)

MODELS = pathlib.Path(blocks.__file__).parent
BELOW = sorted([MODELS / "blocks.py", *(MODELS / "mixers").glob("*.py")])


def test_the_table_is_what_the_configuration_accepts():
    for name, record in mixers.MIXERS.items():
        asked = types.SimpleNamespace(mixer=name, sparse_index=())
        assert mixers.mixer_of(asked) is record
    assert mixers.mixer_of(types.SimpleNamespace(mixer="none", sparse_index=())) is None
    # an index is softmax attention's, whatever the field says: its check refuses the rest
    assert mixers.mixer_of(types.SimpleNamespace(
        mixer="mamba2", sparse_index=(2, 8, 16))) is mixers.SPARSE_ATTENTION
    with pytest.raises(ValueError, match="not of mixer 'none'"):
        TransformerConfig(mixer="none", sparse_index=(2, 8, 16), head_size=16,
                          positions="rope")
    listed = re.escape(str((*mixers.MIXERS, "none")))
    with pytest.raises(ValueError, match=f"mixer 'mla' is not one of {listed}"):
        TransformerConfig(mixer="mla")
    assert TransformerConfig(mixer="none").mixer == "none"


def _kinds():
    yield "tiny", TransformerConfig.tiny()
    yield "tiny_moe", TransformerConfig.tiny_moe()
    for family in FAMILIES:
        cfg = family.module.model_config(family.config)
        kinds = [kind for kind, _ in cfg.stacks]
        if cfg.mtp_depth:
            kinds.append(cfg.mtp_kind)
        for i, kind in enumerate(dict.fromkeys(kinds)):
            yield f"{family.name}.{i}", kind


KINDS = dict(_kinds())


@pytest.mark.parametrize("name", KINDS)
def test_a_records_leaves_and_shardings_are_one_set_of_names(name):
    cfg = KINDS[name]
    record = mixers.mixer_of(cfg)
    if record is None:
        assert cfg.mixer == "none"
        return
    leaves = jax.eval_shape(lambda key: record.init(
        key, cfg, lambda k, shape: jnp.zeros(shape, jnp.float32),
        lambda cfg, shape: jnp.ones(shape, jnp.float32)), jax.random.PRNGKey(0))
    specs = record.pspecs(cfg, "tp")
    assert set(leaves) == set(specs) and leaves
    for leaf, spec in specs.items():  # the stack's layer axis in front
        assert len(spec) == leaves[leaf].ndim + 1 and spec[0] is None, leaf
    assert not {"ln1_scale", "ln2_scale"} & set(leaves)  # the layer's own


def _gained(x, layer, cfg, core, segments, marks):
    return blocks._mixer_input(x, layer, cfg) * layer["gain"].astype(x.dtype), None


PLANTED = mixers.Mixer(
    "planted",
    init=lambda key, cfg, dense, unit: {"gain": unit(cfg, (cfg.d_model,))},
    pspecs=lambda cfg, tp: {"gain": jax.sharding.PartitionSpec(None, None)},
    apply=_gained)


@pytest.mark.parametrize("kinds", [(), ((("mixer", "planted"),),
                                        (("mixer", "attention"),))])
def test_a_planted_record_trains_with_no_other_edit(kinds, monkeypatch):
    """The seam's own test: a mixer that returns its (normed) input times a
    gain, known to the table alone, goes through the configuration, the
    state, the shardings, the layer and the loss."""
    monkeypatch.setitem(mixers.MIXERS, "planted", PLANTED)
    cfg = TransformerConfig(vocab_size=64, d_model=16, n_heads=2, n_layers=2,
                            d_ff=32, max_seq=16, dtype=jnp.float32,
                            mixer="planted", layer_kinds=kinds)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    first = params["layers"][0] if kinds else params["layers"]
    assert set(first) == {"ln1_scale", "ln2_scale", "gain", "w_in", "w_out"}
    is_spec = lambda s: isinstance(s, jax.sharding.PartitionSpec)
    assert (jax.tree.structure(param_pspecs(cfg), is_leaf=is_spec)
            == jax.tree.structure(params))
    batch = jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0, 64)
    step = jax.jit(jax.value_and_grad(
        lambda params: transformer_loss(params, batch, cfg)))
    assert "planted/" in step.lower(params).as_text(debug_info=True)
    losses = []
    for _ in range(4):
        loss, grads = step(params)
        params = jax.tree.map(lambda p, g: p - 0.5 * g, params, grads)
        losses.append(float(loss))
    assert float(jnp.abs(grads["layers"][0]["gain"] if kinds
                         else grads["layers"]["gain"]).max()) > 0
    assert losses[-1] < losses[0] and all(l == l for l in losses)


def _imports(path):
    """[(the module an import statement names, whether it stands at the
    module's own level)] of a source file."""
    tree = ast.parse(path.read_text())
    top = {id(node) for node in tree.body}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, id(node) in top
        elif isinstance(node, ast.ImportFrom):
            for alias in node.names:  # `from a.b import c` may name a.b.c
                yield f"{node.module}.{alias.name}", id(node) in top


@pytest.mark.parametrize("path", BELOW, ids=lambda p: p.parent.name + "/" + p.name)
def test_nothing_below_the_layer_imports_it_nor_an_op_at_its_own_level(path):
    """The arrows point one way (`ops/` <- `blocks.py` <- `mixers/` <-
    `transformer.py`), and an op's kernels are imported by the function that
    calls them: `import_s` is part of every cell's `setup_s`."""
    assert len(BELOW) == 8  # blocks.py, the package's table and six mixers
    for module, at_top in _imports(path):
        assert not module.startswith("kungfu_tpu.models.transformer"), module
        assert not (at_top and module.startswith("kungfu_tpu.ops")), module
        if path.name == "blocks.py":
            assert not module.startswith("kungfu_tpu.models"), module
