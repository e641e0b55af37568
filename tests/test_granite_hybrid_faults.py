"""Each mechanism of granite-4.0-h-micro's layers on packed documents knocked
out in turn (PR 52): the float32 program with the fault against the plain
reference on the family's trained-like state and its packed sample
(`tests/family_cases.py`); every fault has to read far over what the bfloat16
program is allowed. A file of its own so that the suite's workers share the
compiles."""

import jax
import pytest

import family_cases as fc
from family_cases import (  # noqa: F401  the shared case
    pytest_generate_tests, test_a_fault_fails_the_familys_tolerance)
from kungfu_tpu.models import transformer
from kungfu_tpu.ops import flash_attention, gated_delta, ssm_scan

_as = lambda **changes: fc.model_changed(fc.GRANITE_HYBRID.module, **changes)


@pytest.fixture(autouse=True)
def four_chunks_a_row(monkeypatch):
    monkeypatch.setattr(ssm_scan, "CHUNK", fc.GRANITE_CHUNK)


def _one_document(m, module, name, arguments):
    """The op `module.name` without the documents' numbers it is handed
    behind its first `arguments` arguments."""
    op = getattr(module, name)
    m.setattr(module, name, lambda *args: op(*args[:arguments]))


def _an_untied_head(m):
    """The head's matrix a leaf of its own that happens to hold the
    embedding's values: the embedding is given no gradient through it."""
    logits = transformer._head_logits
    m.setattr(transformer, "_head_logits", lambda params, x, cfg, normed=False: logits(
        {**params, "embed": jax.lax.stop_gradient(params["embed"])}, x, cfg, normed))


FAULTS = {
    "boundaries_ignored_in_the_scan": lambda m: _one_document(
        m, ssm_scan, "ssm_scan", 5),
    "boundaries_ignored_in_the_convolution": lambda m: _one_document(
        m, gated_delta, "causal_conv", 3),
    "boundaries_ignored_in_attention": lambda m: _one_document(
        m, flash_attention, "flash_attention", 9),
    "embedding_multiplier_1": _as(embedding_multiplier=1.0),
    "attention_scale_1_over_sqrt_head_size": _as(attention_multiplier=0.0),
    "residual_multiplier_1": _as(residual_multiplier=1.0),
    "logits_scaling_1": _as(logits_scaling=1.0),
    "an_untied_head": _an_untied_head,
    "norm_before_the_gate": fc.gate_after_the_norm,
    "norm_over_eight_groups": fc.norm_over(8),
    "a_rotary_pass": _as(positions="rope"),
}

FAMILY = fc.GRANITE_HYBRID.with_cases(faults=FAULTS)
