"""`telemetry/device.py` and the launcher's spans: a profile's scope table
and phase times on the CPU backend, the span ring mirrored into the profile
while `profile()` is open and only then, and the launcher and placement
spans of a two-process `kfrun` run (on this xdist worker's own ports)."""

import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import optax
import pytest

from ports import kfrun_ports

from kungfu_tpu.models.transformer import (TransformerConfig, init_transformer,
                                           transformer_loss)
from kungfu_tpu.optimizers import synchronous_sgd
from kungfu_tpu.parallel import make_mesh, make_train_step
from kungfu_tpu.parallel.dp import replicate
from kungfu_tpu.telemetry import device, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AGENT = os.path.join(REPO, "tests", "integration", "span_agent.py")
STEPS = 3


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """A tiny S-SGD step on two CPU devices, compiled, warmed up, and run
    STEPS times under `profile()` with a span around each step."""
    cfg = TransformerConfig.tiny()
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    opt = synchronous_sgd(optax.adamw(1e-3), "dp")
    step = make_train_step(functools.partial(transformer_loss, cfg=cfg), opt, mesh)
    params = replicate(init_transformer(jax.random.PRNGKey(0), cfg), mesh)
    opt_state = replicate(opt.init(params), mesh)
    batch = jnp.zeros((4, 17), jnp.int32)
    compiled = step.lower(params, opt_state, batch).compile()
    params, opt_state, loss = compiled(params, opt_state, batch)
    loss.block_until_ready()
    log_dir = str(tmp_path_factory.mktemp("profile"))
    tracing.clear()
    with device.profile(log_dir):
        for i in range(STEPS):
            with tracing.span("test.step", i=i):
                params, opt_state, loss = compiled(params, opt_state, batch)
                loss.block_until_ready()
    return {"compiled": compiled, "xplane": device.find_xplane(log_dir),
            "table": device.scope_table(compiled)}


def _host_events(xplane, name):
    from jax.profiler import ProfileData

    return [e for plane in ProfileData.from_file(xplane).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events if e.name == name]


def test_scope_table_names_instructions_of_the_compiled_text(profiled):
    table, text = profiled["table"], profiled["compiled"].as_text()
    assert len(table) > 100
    for name, scope in list(table.items())[:50]:
        assert f"{name} = " in text and f'op_name="{scope}"' in text


@pytest.mark.parametrize("phase", ["forward", "backward", "optimizer",
                                   "all_reduce"])
def test_scope_table_holds_every_phase(profiled, phase):
    assert any(device.phase_of(v) == phase for v in profiled["table"].values())


def test_phase_ms_splits_the_profiled_step(profiled):
    phases = device.phase_ms(profiled["xplane"], profiled["table"])
    assert set(phases) == set(device.PHASES)
    assert all(v >= 0 for v in phases.values())
    for phase in ("forward", "backward", "optimizer", "all_reduce"):
        assert phases[phase] > 0, phases
    # each op's own time: the parts cannot exceed the steps they ran in
    spans = [e.duration * 1e3 for e in tracing.full_events("test.step")]
    assert sum(phases.values()) < 2 * max(spans)


def test_phase_ms_of_a_table_that_names_nothing_is_unattributed(profiled):
    phases = device.phase_ms(profiled["xplane"], {})
    assert phases["unattributed"] > 0
    assert sum(phases.values()) == pytest.approx(phases["unattributed"])


def test_find_xplane_says_where_it_looked(tmp_path):
    with pytest.raises(FileNotFoundError, match=str(tmp_path)):
        device.find_xplane(str(tmp_path))


def test_a_span_under_profile_is_in_the_profiles_host_plane(profiled):
    found = _host_events(profiled["xplane"], "test.step")
    assert len(found) == STEPS
    ring = tracing.full_events("test.step")
    assert len(ring) == STEPS
    # one clock for both: the annotation lasts as long as the ring's span
    for e, r in zip(sorted(found, key=lambda e: e.start_ns), ring):
        assert e.duration_ns / 1e9 == pytest.approx(r.duration, rel=0.2, abs=2e-4)


class _Counting:
    entered = 0

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _Counting.entered += 1
        return self

    def __exit__(self, *exc):
        return False


def test_outside_a_profile_no_annotation_is_entered(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Counting)
    _Counting.entered = 0
    assert tracing._mirror is None
    with tracing.span("test.quiet"):
        pass
    assert _Counting.entered == 0


def test_profile_mirrors_spans_only_while_it_is_open(monkeypatch, tmp_path):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Counting)
    _Counting.entered = 0
    with device.profile(str(tmp_path)):
        with tracing.span("test.outer"):
            with tracing.span("test.inner"):
                pass
        assert _Counting.entered == 2
    with tracing.span("test.after"):
        pass
    assert _Counting.entered == 2 and tracing._mirror is None


def test_profile_closes_the_mirror_when_the_body_raises(tmp_path):
    with pytest.raises(RuntimeError, match="boom"):
        with device.profile(str(tmp_path)):
            raise RuntimeError("boom")
    assert tracing._mirror is None
    with device.profile(str(tmp_path)):  # the profiler was stopped too
        pass


def test_a_span_open_across_the_profiles_end_still_closes(tmp_path):
    tracing.clear()
    with device.profile(str(tmp_path)):
        s = tracing.span("test.straddle")
        s.__enter__()
    s.__exit__(None, None, None)
    assert [e.name for e in tracing.full_events("test.straddle")] == ["test.straddle"]


@pytest.fixture(scope="module")
def kfrun_spans():
    """Two kfrun workers on the CPU backend, each printing its ring."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_NUM_CPU_DEVICES"] = "1"
    r = subprocess.run(
        [sys.executable, "-m", "kungfu_tpu.runner.cli", *kfrun_ports().args,
         "-np", "2", "-H", "127.0.0.1:2", "--", sys.executable, AGENT],
        env=env, capture_output=True, text=True, timeout=240, cwd=REPO)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    tag = "SPAN_AGENT "
    workers = [json.loads(l.split(tag, 1)[1])
               for l in r.stdout.splitlines() if tag in l]
    assert sorted(w["rank"] for w in workers) == [0, 1]
    return {w["rank"]: {s["name"]: s for s in w["spans"]} for w in workers}


@pytest.mark.parametrize("rank", [0, 1])
@pytest.mark.parametrize("name", [
    "worker.startup", "device_plane.compile_cache", "device_plane.bootstrap",
    "device_plane.distributed_initialize", "device_plane.backend_start",
    "broadcast.one_to_all", "broadcast.replicate"])
def test_every_worker_records_the_launcher_and_placement_spans(
        kfrun_spans, rank, name):
    assert name in kfrun_spans[rank], sorted(kfrun_spans[rank])
    assert kfrun_spans[rank][name]["ms"] >= 0


@pytest.mark.parametrize("rank", [0, 1])
def test_the_broadcast_span_carries_bytes_and_leaves(kfrun_spans, rank):
    args = kfrun_spans[rank]["broadcast.one_to_all"]["args"]
    nbytes = 64 * 32 * 4 + 32 * 4  # numpy leaves: all of it from the host
    assert args == {"leaves": 2, "bytes": nbytes, "host_bytes": nbytes}
