"""The seven per-layer metrics that read the runtime's own spans of the
program's ring (PR 39): `worker.import`, `device_plane.compile.*` and
`worker.gc`, between the record's own marks. On records drawn by hand, and on
one traced run of `measure` at tiny size on the CPU mesh with the watchers
installed by their own functions."""

import copy
import json
import os
import subprocess
import sys
import time

import pytest

from benchmark import end_to_end, harness, manifest as mf
from benchmark.launchers.none import OneProcess
from benchmark.layer_metrics import (gc_pause_max_ms, gc_pause_share_pct,
                                     import_s, peer_compile_miss_s,
                                     replicate_compile_s,
                                     state_init_load_or_compile_s,
                                     state_init_s, state_init_trace_lower_s)
from drawn_setup import child_marks, drawn_setup
from test_bench_loop import _tiny_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
KFRUN_CELL = "bert_base.ssgd_kfrun_4chip"
ONE_PROCESS_CELLS = ("bert_base.ssgd_1chip", "resnet50.ssgd_1chip",
                     "olmoe_1b_7b.ssgd_seq4096_1chip",
                     "laguna_s_2_1.ssgd_1seq_1chip",
                     "qwen3_next_80b_a3b.ssgd_longseq_1chip")
EVERYWHERE = {"import_s": "Launcher", "state_init_trace_lower_s": "Model",
              "state_init_load_or_compile_s": "Model",
              "gc_pause_share_pct": "Train step", "gc_pause_max_ms": "Train step"}
KFRUN_ONLY = {"peer_compile_miss_s": "Launcher", "replicate_compile_s": "Launcher"}
READERS = (import_s, state_init_trace_lower_s, state_init_load_or_compile_s,
           peer_compile_miss_s, replicate_compile_s, gc_pause_share_pct,
           gc_pause_max_ms)
TRACE, LOWER, BACKEND = ("device_plane.compile.trace", "device_plane.compile.lower",
                         "device_plane.compile.backend")

# The drawn set-up's marks: t_child 100.5, t_joined 100.75 (110.5 under
# kfrun), t_world 112, t_init 113.5, t_placed 114, t_window 117.5; the window
# below runs for 2 s on its own clock, so to 119.5 on the marks'.
RUNTIME_SPANS = [
    ["worker.import", 100.5, 100.625, 0, {"module": "kungfu_tpu"}],
    ["worker.import", 100.625, 110.25, 0, {"module": "kungfu_tpu.parallel"}],
    # an import after the world stood (a plugin's) is not the launch's
    ["worker.import", 112.25, 112.375, 0, {"module": "kungfu_tpu.late"}],
    # before the world stood: not the state's
    [TRACE, 111.0, 111.5, 0, {"fun_name": "early", "nested": 0}],
    [BACKEND, 111.5, 111.75, 0, {"fun_name": "jit(early)", "cache": "hit"}],
    # the init program: traced with an eager op's request inside the trace,
    # lowered, loaded
    [TRACE, 112.0, 112.5, 0, {"fun_name": "init", "nested": 7}],
    [BACKEND, 112.125, 112.25, 0, {"fun_name": "jit(ones)", "cache": "hit"}],
    [LOWER, 112.5, 112.625, 0, {"fun_name": "jit(init)", "nested": 1}],
    [BACKEND, 112.625, 113.0, 0, {"fun_name": "jit(init)", "cache": "hit"}],
    # across `t_init`: the part before it
    [LOWER, 113.375, 113.75, 0, {"fun_name": "jit(place)", "nested": 0}],
    # the collector: before the window, across its start, inside it (a young
    # collection that took long and a full one), across its end, after it
    ["worker.gc", 117.0, 117.25, 0, {"generation": 2, "collected": 10}],
    ["worker.gc", 117.4375, 117.5625, 1, {"generation": 2, "collected": 0}],
    ["worker.gc", 118.0, 118.015625, 1, {"generation": 0, "collected": 3}],
    ["worker.gc", 118.5, 118.625, 1, {"generation": 2, "collected": 99}],
    ["worker.gc", 119.46875, 119.75, 0, {"generation": 1, "collected": 0}],
    ["worker.gc", 121.0, 121.5, 0, {"generation": 2, "collected": 0}],
]
WINDOW = {"t_start": 50.0, "t_done": [50.5, 51.0, 51.5, 52.0], "compiles": 0,
          "spans": []}


def _record(kfrun=False, spans=RUNTIME_SPANS):
    setup = drawn_setup(kfrun)
    setup["spans"] = sorted(setup["spans"] + copy.deepcopy(spans),
                            key=lambda s: s[1])
    return {"traced": True, "rank": 0, "window": copy.deepcopy(WINDOW), **setup}


def test_import_s_is_the_imports_before_the_world_stood():
    assert import_s.read(_record(), None) == pytest.approx(0.125 + 9.625)
    # merged: the package's body holds a subpackage's where one imports it
    nested = _record(spans=[
        ["worker.import", 100.5, 102.5, 0, {"module": "kungfu_tpu"}],
        ["worker.import", 101.0, 102.0, 0, {"module": "kungfu_tpu.parallel"}]])
    assert import_s.read(nested, None) == pytest.approx(2.0)


def test_the_state_inits_two_parts_are_disjoint_and_lie_between_its_marks():
    record = _record()
    # trace 0.5 less the request inside it, the lowering, and 0.125 of the
    # lowering that runs across `t_init`
    assert state_init_trace_lower_s.read(record, None) == pytest.approx(
        (0.5 - 0.125) + 0.125 + 0.125)
    assert state_init_load_or_compile_s.read(record, None) == pytest.approx(
        0.125 + 0.375)
    both = (state_init_trace_lower_s.read(record, None)
            + state_init_load_or_compile_s.read(record, None))
    assert both <= state_init_s.read(record, None) == pytest.approx(1.5)


def _rank(rank, spans, t_world=112.0, t_placed=114.0):
    return {"rank": rank, "marks": {"t_world": t_world, "t_placed": t_placed},
            "spans": spans}


def test_peer_compile_miss_s_is_the_slowest_other_ranks_misses():
    record = _record(kfrun=True)
    assert peer_compile_miss_s.read(record, None) == 0.0  # no other rank
    miss, hit = {"fun_name": "jit(init)", "cache": "miss"}, {"cache": "hit"}
    record["ranks"] = [
        # the reporting rank's own misses are not what it waits for
        _rank(0, [[BACKEND, 112.0, 113.75, 0, miss]]),
        _rank(1, [[BACKEND, 112.25, 113.25, 0, miss],
                  [BACKEND, 113.25, 113.5, 0, hit],
                  [BACKEND, 113.5, 113.625, 0, miss],
                  [TRACE, 112.0, 112.25, 0, {"fun_name": "init", "nested": 3}]]),
        # by its own marks: before its world stood, and across its placement
        _rank(2, [[BACKEND, 112.0, 112.5, 0, miss],
                  [BACKEND, 114.5, 115.5, 0, miss]], t_world=112.25, t_placed=115.0),
        _rank(3, [[BACKEND, 112.5, 113.0, 0, hit]]),
    ]
    assert peer_compile_miss_s.read(record, None) == pytest.approx(1.125)
    record["ranks"][2]["spans"].append([BACKEND, 113.0, 114.0, 0, miss])
    assert peer_compile_miss_s.read(record, None) == pytest.approx(0.25 + 0.5 + 1.0)
    for r in record["ranks"]:
        r["spans"] = [s for s in r["spans"] if s[4].get("cache") != "miss"]
    assert peer_compile_miss_s.read(record, None) == 0.0


def test_replicate_compile_s_is_what_compiled_inside_the_replication():
    # `broadcast.replicate` runs from 113.875 to 114.0 in the drawn set-up
    record = _record(kfrun=True)
    assert replicate_compile_s.read(record, None) == 0.0
    record["spans"] += [
        [TRACE, 113.8125, 113.90625, 0, {"fun_name": "check", "nested": 0}],  # across
        [LOWER, 113.90625, 113.921875, 0, {"fun_name": "jit(check)", "nested": 0}],
        [BACKEND, 113.921875, 113.96875, 0, {"fun_name": "jit(check)", "cache": "hit"}],
        [BACKEND, 113.9375, 113.953125, 0, {"fun_name": "jit(other)", "cache": "hit"}],
        [BACKEND, 114.0, 114.5, 0, {"fun_name": "jit(opt_init)", "cache": "hit"}],
    ]
    assert replicate_compile_s.read(record, None) == pytest.approx(
        113.96875 - 113.875)
    # a replication after the placement (a resize's) is not the launch's
    record["spans"] += [["broadcast.replicate", 115.0, 116.0, 0, {}],
                        [BACKEND, 115.0, 115.5, 0, {"cache": "miss"}]]
    assert replicate_compile_s.read(record, None) == pytest.approx(
        113.96875 - 113.875)


def test_the_collectors_pauses_are_those_inside_the_window():
    record = _record()
    # 0.0625 of the one across the start, two inside, 0.03125 of the one
    # across the end; the window runs for 2 s
    inside = 0.0625 + 0.015625 + 0.125 + 0.03125
    assert gc_pause_share_pct.read(record, None) == pytest.approx(100 * inside / 2.0)
    assert gc_pause_max_ms.read(record, None) == pytest.approx(125.0)
    # the denominator is the window's own length, whatever its clock reads
    record["window"]["t_start"] += 1000.0
    record["window"]["t_done"] = [t + 1000.0 for t in record["window"]["t_done"]]
    assert gc_pause_share_pct.read(record, None) == pytest.approx(100 * inside / 2.0)


@pytest.mark.parametrize("kfrun", [False, True], ids=["one_process", "kfrun"])
def test_a_ring_with_none_of_their_spans_reads_zero_not_nothing(kfrun):
    """A metric listed for a cell is in its traced line: a hook that broke
    reads 0 on the chip, where the acceptance looks for it."""
    record = _record(kfrun, spans=[])
    assert [r.read(record, None) for r in READERS] == [0.0] * len(READERS)
    assert all(isinstance(r.read(record, None), float) for r in READERS)


def test_an_untraced_record_is_not_asked():
    record = {**_record(kfrun=True), "traced": False}
    assert [r.read(record, None) for r in READERS] == [None] * len(READERS)


def test_the_seven_entries_and_their_files():
    """Held to what this PR gave them in the cells it knew. A later PR
    appends entries, cells and cells to a `workloads` list: nothing here
    counts entries or asks where in `per_layer` these stand."""
    manifest = mf.load()
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name, layer in {**EVERYWHERE, **KFRUN_ONLY}.items():
        entry = entries[name]
        assert (entry["layer"], entry["source"], entry["better"]) == (
            layer, "program_span", "lower")
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
    moves = {name: entries[name]["moves"] for name in {**EVERYWHERE, **KFRUN_ONLY}}
    assert moves.pop("gc_pause_share_pct") == "samples_per_s_per_chip"
    assert moves.pop("gc_pause_max_ms") == "step_ms_p95"
    assert set(moves.values()) == {"setup_s"}
    for cell in ONE_PROCESS_CELLS + (KFRUN_CELL,):
        mine = {m["name"] for m in mf.metrics_of(manifest, "per_layer", cell)}
        assert set(EVERYWHERE) <= mine
        # a cell of one process has no other rank and no `broadcast.replicate`
        assert (set(KFRUN_ONLY) <= mine) == (cell == KFRUN_CELL)
        assert mine.isdisjoint(KFRUN_ONLY) == (cell != KFRUN_CELL)


def test_run_check_passes_with_them():
    r = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
                        "--check"], capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "sound" in r.stdout


@pytest.fixture(scope="module")
def measured(tmp_path_factory, runtime_watchers):
    """One traced run of `measure` at tiny size on four CPU devices with the
    watchers installed by their own functions and taken out after this
    file's tests (`tests/conftest.py`'s `runtime_watchers`):
    `enable_compile_cache()` would point the whole test process at the
    checkout's cache."""
    from kungfu_tpu.telemetry import tracing

    cell, mesh = _tiny_cell()
    try:
        yield harness.measure(
            cell, mesh, OneProcess(), {"bf16_flops": 197e12}, seed=7,
            seconds=0.2, trace_dir=str(tmp_path_factory.mktemp("trace")),
            events=harness.EventCounter(), t_command=time.time(),
            marks=child_marks())
    finally:
        tracing.clear()  # the next file of this xdist worker starts clean


def test_a_measured_record_holds_the_state_inits_compile_requests(measured):
    spans, marks = measured["spans"], measured["marks"]
    requests = [s for s in spans if s[0] == BACKEND
                and marks["t_world"] <= s[1] and s[2] <= marks["t_init"]]
    # the cache is off under pytest (`tests/conftest.py`)
    assert requests and {s[4]["cache"] for s in requests} == {"off"}
    assert all(isinstance(s[4]["fun_name"], str) for s in requests)
    load_or_compile = state_init_load_or_compile_s.read(measured, None)
    trace_lower = state_init_trace_lower_s.read(measured, None)
    assert load_or_compile > 0 and trace_lower > 0
    assert load_or_compile + trace_lower <= state_init_s.read(measured, None) + 5e-3
    # the step's own request is there too, after the state's
    assert any(s[4]["fun_name"] == "jit(local_step)" and s[1] >= marks["t_first_0"]
               for s in spans if s[0] == BACKEND)
    # the model's jitted calls are folded into the step's trace, not spans
    (step_trace,) = [s for s in spans if s[0] == TRACE
                     and s[4]["fun_name"] == "local_step"]
    assert step_trace[4]["nested"] >= 2
    assert len([s for s in spans if s[0].startswith("device_plane.compile.")]) < 200
    json.dumps(spans)


def test_a_measured_record_gives_every_reader_a_number(measured):
    manifest = mf.load()
    names = list(EVERYWHERE) + list(KFRUN_ONLY)
    found = end_to_end.layer_values(measured, None, names)
    assert set(found) == set(names)
    assert all(isinstance(v, float) and v >= 0 for v in found.values()), found
    assert found["peer_compile_miss_s"] == found["replicate_compile_s"] == 0.0
    assert found["gc_pause_share_pct"] <= 100.0
    assert found["gc_pause_max_ms"] <= 1e3 * (
        measured["window"]["t_done"][-1] - measured["window"]["t_start"])
    units = {m["name"]: m["unit"] for m in manifest["per_layer"]}
    assert {units[n] for n in names} == {"s", "%", "ms"}
