"""A span a kernel's trace (ISSUE 73): every `pl.pallas_call` under
`kungfu_tpu/` is built by `ops/kernel_call.kernel_call`, and each of its
traces is one `device_plane.compile.kernel` span with the kernel's name and
the branch it was traced for. Toy kernels on the CPU, nothing compiled."""

import ast
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from kungfu_tpu.ops import kernel_call as door
from kungfu_tpu.ops.gated_delta import _on_platform
from kungfu_tpu.telemetry import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE = "device_plane.compile.trace"


@pytest.fixture(scope="module", autouse=True)
def watched(runtime_watchers):
    """The compile watch for this file's tests, and out of the process
    after them (`tests/conftest.py`)."""


@pytest.fixture(autouse=True)
def ring():
    tracing.clear()
    yield
    tracing.clear()


def _double_kernel(x_ref, o_ref, *, times=2):
    o_ref[...] = x_ref[...] * times


def _double(x, *, interpret, name="toy_double", kernel=_double_kernel):
    return door.kernel_call(
        kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret, name=name)(x)


def _kernel_spans():
    return [(e.args["kernel"], e.args["branch"])
            for e in tracing.full_events(door.SPAN)]


def _inside(inner, outer, slack=0.05):
    """The watch dates a `.trace` span back from its end by JAX's own
    duration, on another clock than a span's: to a rounding."""
    return (outer.start - slack <= inner.start
            and inner.start + inner.duration <= outer.start + outer.duration + slack)


def test_a_kernel_under_platform_dependent_is_a_span_a_branch_inside_one_trace():
    x = jnp.ones((8, 128))
    tracing.clear()  # the eager `ones` compiled too
    jax.jit(lambda x: _on_platform(_double, x) + 1).lower(x)
    assert _kernel_spans() == [("toy_double", "tpu"), ("toy_double", "interpret")]
    (trace,) = tracing.full_events(TRACE)
    assert all(_inside(e, trace) for e in tracing.full_events(door.SPAN))
    # JAX's own events name neither: both bodies are `wrapped`'s
    assert ["wrapped", "trace"] in [row[:2] for row in trace.args["own"]]
    assert trace.args["events"] >= 3
    # no reader that goes by a stage's prefix takes a kernel's span for one
    assert not door.SPAN.startswith(tuple(
        "device_plane.compile." + stage for stage in ("trace", "lower", "backend")))


@pytest.mark.parametrize("named,kernel,shown", [
    ("toy_double", _double_kernel, "toy_double"),
    (None, _double_kernel, "_double_kernel"),
    (None, functools.partial(functools.partial(_double_kernel, times=3)),
     "_double_kernel")], ids=["the_calls_name", "the_functions_own", "a_partials"])
def test_the_span_names_the_kernel_as_mosaic_will(named, kernel, shown):
    x = jnp.ones((8, 128))
    tracing.clear()
    jaxpr = jax.make_jaxpr(functools.partial(
        _double, interpret=True, name=named, kernel=kernel))(x)
    assert _kernel_spans() == [(shown, "interpret")]
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.params["jaxpr"].debug_info.func_name == shown


def test_a_call_that_a_jits_cache_serves_enters_no_span():
    """The count of a kernel's spans is the count of its traces."""
    double = jax.jit(functools.partial(_double, interpret=True, name="toy_once"))
    x = jnp.ones((8, 128))
    tracing.clear()
    jax.make_jaxpr(lambda x: double(double(x)) + double(x))(x)
    assert _kernel_spans() == [("toy_once", "interpret")]


def test_the_rotary_pass_is_a_span_a_branch_under_its_own_name(fresh_traces):
    from kungfu_tpu.models import blocks

    t = jnp.ones((1, 2, 16, 8))
    tracing.clear()
    jax.make_jaxpr(lambda t: blocks._turned(t, (10000.0, 1.0, None), False))(t)
    assert _kernel_spans() == [("rotary", "tpu"), ("rotary", "interpret")]


def test_the_span_changes_nothing_of_the_program():
    x = jnp.ones((8, 128))
    through_the_door = jax.jit(functools.partial(_double, interpret=True)).lower(x)
    from jax.experimental import pallas as pl

    plain = jax.jit(lambda x: pl.pallas_call(
        _double_kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=True, name="toy_double")(x)).lower(x)
    strip = lambda text: [line.split(" loc(")[0] for line in text.splitlines()
                          if not line.startswith(("#loc", "module @"))]
    assert strip(through_the_door.as_text()) == strip(plain.as_text())
    assert jnp.array_equal(_double(x, interpret=True), 2 * x)


def test_no_pallas_call_under_the_package_but_through_the_door():
    """So the next kernel cannot arrive unnamed: `pallas_call` is named in
    `ops/kernel_call.py` alone, as a call, a reference or an import."""
    found = []
    package = os.path.join(REPO, "kungfu_tpu")
    for folder, _, files in os.walk(package):
        for name in files:
            path = os.path.join(folder, name)
            if not name.endswith(".py") or path == door.__file__:
                continue
            with open(path) as f:
                tree = ast.parse(f.read(), path)
            for node in ast.walk(tree):
                said = (getattr(node, "attr", None), getattr(node, "id", None),
                        *(a.name for a in getattr(node, "names", ())
                          if isinstance(a, ast.alias)))
                if "pallas_call" in said:
                    found.append(f"{os.path.relpath(path, REPO)}:{node.lineno}")
    assert found == []
    # and the kernels there are do come through it: eleven modules of `ops/`
    ops = os.path.dirname(door.__file__)

    def calls_it(name):
        with open(os.path.join(ops, name)) as f:
            return "kernel_call(" in f.read()

    assert len([name for name in os.listdir(ops) if name.endswith(".py")
                and name != os.path.basename(door.__file__) and calls_it(name)]) == 11
