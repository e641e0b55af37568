"""The four per-layer metrics that read who was traced in the first step
(PR 73): the `device_plane.compile.kernel` spans of `ops/kernel_call.py`
and the `events` that `telemetry/device._CompileWatch` folds into the
outermost `.trace` and `.lower` span, between the record's marks
`t_first_0` and `t_first_1`. On records drawn by hand, and on one traced run
of `measure` at tiny size on the CPU mesh."""

import copy
import os
import time

import pytest

from benchmark import end_to_end, harness, manifest as mf
from benchmark.launchers.none import OneProcess
from benchmark.layer_metrics import (first_step_kernel_trace_s,
                                     first_step_lower_s,
                                     first_step_trace_events,
                                     first_step_unrun_branch_trace_s)
from drawn_setup import child_marks, drawn_setup
from test_bench_loop import _tiny_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
LAYERS = {"first_step_kernel_trace_s": ("Kernels", "program_span", "s"),
          "first_step_unrun_branch_trace_s": ("Kernels", "program_span", "s"),
          "first_step_lower_s": ("Train step", "program_span", "s"),
          "first_step_trace_events": ("Train step", "program_counter", "count")}
READERS = (first_step_kernel_trace_s, first_step_unrun_branch_trace_s,
           first_step_lower_s, first_step_trace_events)
TRACE, LOWER, BACKEND, KERNEL = (
    "device_plane.compile.trace", "device_plane.compile.lower",
    "device_plane.compile.backend", "device_plane.compile.kernel")


def _kernel(start, end, kernel, branch):
    return [KERNEL, start, end, 1, {"kernel": kernel, "branch": branch}]


# The drawn set-up's marks: t_pool = t_first_0 114.25, t_first_1 114.95.
FIRST_STEP_SPANS = [
    # the state's and the pool's, before the first step: not its
    [TRACE, 112.0, 112.5, 0, {"fun_name": "init", "nested": 7, "events": 8, "own": []}],
    _kernel(112.125, 112.25, "rotary", "interpret"),
    [LOWER, 112.5, 112.625, 0, {"fun_name": "jit(init)", "nested": 1, "events": 2}],
    # a kernel's trace across `t_first_0`: the part after it
    _kernel(114.1875, 114.3125, "kda_pairs", "tpu"),
    # the step: traced with its kernels a branch each, one pair inside a
    # layer's own nested trace (spans may overlap: merged, not summed)
    [TRACE, 114.25, 114.5, 0, {"fun_name": "local_step", "nested": 40, "events": 41,
                               "own": [["wrapped", "trace", 0.15, 6]]}],
    _kernel(114.3125, 114.34375, "kda_pairs", "interpret"),
    _kernel(114.375, 114.40625, "attn_core", "tpu"),
    _kernel(114.375, 114.4375, "kda_forward", "tpu"),
    _kernel(114.4375, 114.46875, "kda_forward", "interpret"),
    # lowered, with a rule's eager op compiled inside the lowering, and loaded
    [LOWER, 114.5, 114.625, 0, {"fun_name": "jit(local_step)", "nested": 9,
                                "events": 10, "own": []}],
    [BACKEND, 114.53125, 114.5625, 0, {"fun_name": "jit(iota)", "cache": "hit"}],
    [BACKEND, 114.625, 114.875, 0, {"fun_name": "jit(local_step)", "cache": "hit"}],
    # the float of the loss
    [TRACE, 114.875, 114.890625, 0, {"fun_name": "convert", "nested": 0, "events": 1}],
    # the warm-up's, across and after `t_first_1`
    _kernel(114.9375, 115.0, "late", "interpret"),
    [TRACE, 115.0, 115.25, 0, {"fun_name": "probe", "nested": 2, "events": 3}],
    [LOWER, 115.25, 115.5, 0, {"fun_name": "jit(probe)", "nested": 0, "events": 1}],
]


def _record(spans=FIRST_STEP_SPANS, platform="tpu", kfrun=False):
    setup = drawn_setup(kfrun)
    setup["spans"] = sorted(setup["spans"] + copy.deepcopy(spans),
                            key=lambda s: s[1])
    return {"traced": True, "rank": 0, "device": {"platform": platform}, **setup}


def test_the_kernels_traces_are_merged_and_clipped_to_the_first_steps_marks():
    record = _record()
    # 114.25-114.34375, 114.375-114.46875 and 114.9375-114.95
    assert first_step_kernel_trace_s.read(record, None) == pytest.approx(
        0.09375 + 0.09375 + 0.0125)
    record["spans"] = [s for s in record["spans"] if s[4].get("kernel") != "late"]
    assert first_step_kernel_trace_s.read(record, None) == 0.1875


@pytest.mark.parametrize("platform,unrun", [
    ("tpu", 0.03125 + 0.03125 + 0.0125), ("cpu", 0.0625 + 0.0625)],
    ids=["a_tpu_never_interprets", "a_cpu_never_runs_mosaic"])
def test_the_unrun_branch_is_the_one_the_records_device_never_runs(platform, unrun):
    record = _record(platform=platform)
    got = first_step_unrun_branch_trace_s.read(record, None)
    assert got == pytest.approx(unrun)
    assert 0 < got <= first_step_kernel_trace_s.read(record, None)


def test_the_lowering_is_less_the_requests_inside_it():
    assert first_step_lower_s.read(_record(), None) == 0.125 - 0.03125


def test_the_events_are_those_of_the_spans_that_began_in_the_first_step():
    """The step's trace, its lowering and the loss's conversion; not the
    state's before nor the probe's after."""
    assert first_step_trace_events.read(_record(), None) == 41 + 10 + 1


@pytest.mark.parametrize("spans", [
    [], [[TRACE, 114.25, 114.5, 0, {"fun_name": "local_step", "nested": 40}],
         [BACKEND, 114.625, 114.875, 0, {"fun_name": "jit(local_step)", "cache": "hit"}]]],
    ids=["an_empty_ring", "the_parents_spans"])
@pytest.mark.parametrize("kfrun", [False, True], ids=["one_process", "kfrun"])
def test_a_ring_with_none_of_their_spans_reads_zero_not_nothing(kfrun, spans):
    """A metric listed for a cell is in its traced line: a hook that broke
    reads 0 on the chip, and so does the program of PR 72 and before, whose
    ring holds no kernel's span and no `events`."""
    record = _record(spans, kfrun=kfrun)
    assert [r.read(record, None) for r in READERS] == [0.0] * 4
    assert all(isinstance(r.read(record, None), float) for r in READERS)


def test_an_untraced_record_is_not_asked():
    record = {**_record(), "traced": False}
    assert [r.read(record, None) for r in READERS] == [None] * 4


def test_the_four_entries_and_their_files():
    """Every cell's, under `setup_s`. A later PR appends entries and cells:
    nothing here counts entries or asks where in `per_layer` these stand."""
    manifest = mf.load()
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name, (layer, source, unit) in LAYERS.items():
        entry = entries[name]
        assert (entry["layer"], entry["source"], entry["unit"], entry["better"],
                entry["moves"]) == (layer, source, unit, "lower", "setup_s")
        assert "workloads" not in entry
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
    for cell in manifest["workloads"]:
        mine = {m["name"] for m in mf.metrics_of(manifest, "per_layer", cell["name"])}
        assert set(LAYERS) <= mine


@pytest.fixture(scope="module")
def measured(tmp_path_factory, runtime_watchers):
    """One traced run of `measure` at tiny size on four CPU devices with the
    watchers installed by their own functions (`tests/conftest.py`)."""
    from kungfu_tpu.telemetry import tracing

    cell, mesh = _tiny_cell()
    try:
        yield harness.measure(
            cell, mesh, OneProcess(), {"bf16_flops": 197e12}, seed=11,
            seconds=0.2, trace_dir=str(tmp_path_factory.mktemp("trace")),
            events=harness.EventCounter(), t_command=time.time(),
            marks=child_marks())
    finally:
        tracing.clear()  # the next file of this xdist worker starts clean


def test_a_measured_record_gives_every_reader_a_number(measured):
    found = end_to_end.layer_values(measured, None, list(LAYERS))
    assert set(found) == set(LAYERS)
    assert all(isinstance(v, float) for v in found.values())
    # `bert_base` holds no Pallas kernel
    assert found["first_step_kernel_trace_s"] == 0.0
    assert found["first_step_unrun_branch_trace_s"] == 0.0
    outside = end_to_end.layer_values(measured, None, ["first_step_trace_lower_s"])
    assert 0 < found["first_step_lower_s"] < outside["first_step_trace_lower_s"]
    # the step's trace and its lowering, each with what it folded
    marks = measured["marks"]
    step = {s[0]: s[4] for s in measured["spans"]
            if s[4].get("fun_name") in ("local_step", "jit(local_step)")
            and s[0] in (TRACE, LOWER)}
    assert set(step) == {TRACE, LOWER}
    assert found["first_step_trace_events"] >= sum(a["events"] for a in step.values())
    assert step[TRACE]["events"] == step[TRACE]["nested"] + 1 > 100
    own = step[TRACE]["own"]
    assert 1 <= len(own) <= 8 and all(len(row) == 4 for row in own)
    assert sum(row[2] for row in own) <= marks["t_first_1"] - marks["t_first_0"]
