"""A family's files collect every shared case of `tests/family_cases.py`
(PR 47): what the one definition makes easier than copying did is to leave
a case out."""

import glob
import importlib
import os

import jax
import pytest

import family_cases as fc
from benchmark import harness


def test_every_family_collects_every_shared_case():
    collected = {}
    for path in glob.glob(os.path.join(os.path.dirname(__file__), "test_*.py")):
        with open(path) as f:
            if "import family_cases" not in f.read() or path == __file__:
                continue
        module = importlib.import_module(os.path.basename(path)[:-3])
        assert module.pytest_generate_tests is fc.pytest_generate_tests, path
        collected.setdefault(module.FAMILY.name, set()).update(
            name for name in (*fc.CASES, fc.FAULT_CASE)
            if getattr(module, name, None) is getattr(fc, name))
    assert sorted(collected) == sorted(family.name for family in fc.FAMILIES)
    for name, cases in collected.items():
        assert cases == {*fc.CASES, fc.FAULT_CASE}, (name, cases)


@pytest.mark.parametrize(
    "family", [f for f in fc.FAMILIES if f is not fc.XING4_0], ids=lambda f: f.name)
def test_one_residual_stream_builds_no_map_and_carries_b_s_d(family):
    """Every family from before the residual streams (PR 71) runs one
    (`streams` 1): its small configuration's parameter tree has no map leaf,
    its loss no equation under an `hc` scope, and every layer scan of it
    carries (B, S, D)."""
    config = family.config
    mc = family.module.model_config(config)
    assert mc.streams == 1
    state = jax.eval_shape(lambda: family.module.init(config, 0))
    assert not [path for path, _ in jax.tree_util.tree_leaves_with_path(state)
                if "hc" in jax.tree_util.keystr(path)]
    sample = family.sample()
    jaxpr = jax.make_jaxpr(family.module.loss_fn(config))(state, sample)
    eqns = list(harness.eqns_of(jaxpr.jaxpr))
    assert not [eqn for eqn in eqns
                if {"hc", "hc_in", "hc_out"} & set(str(eqn.source_info.name_stack).split("/"))]
    B, D = sample.shape[0], mc.d_model
    carried = [eqn.outvars[0].aval.shape for eqn in eqns
               if eqn.primitive.name == "scan" and eqn.params["num_carry"]
               and eqn.outvars[0].aval.ndim == 3 and eqn.outvars[0].aval.shape[0] == B]
    assert carried and {shape[-1] for shape in carried} == {D}, carried
