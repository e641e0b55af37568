"""A family's files collect every shared case of `tests/family_cases.py`
(PR 47): what the one definition makes easier than copying did is to leave
a case out."""

import glob
import importlib
import os

import family_cases as fc


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
