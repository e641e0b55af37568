"""The pause of a reload from drawn marks and events: `pause_parts` is a
pure function of what crossed the process boundaries (KF_RESIZE_MARKS)
and of one worker's ring, so every case here is a few tuples."""

import pytest

from kungfu_tpu.elastic.state import _minus, _union, pause_parts

# a reload as its second incarnation's rank 0 might have seen it: proposed
# at 100.0, the runner had the Stage at 100.5, its last old worker was gone
# at 101.0 and this worker was spawned at 101.25; its first step ran from
# 107.0 to 108.0
MARKS = {
    "t_propose": 100.0, "t_stage": 100.5, "t_killed": 101.0, "t_spawn": 101.25,
    "phases_ms": {"wait_config_ms": 100.0, "consensus_ms": 200.0},
    "mode": "reload", "old_size": 4, "version": 1, "new_size": 2,
}
NOW, FIRST_STEP = 108.0, 107.0
PARTS = ("agree_ms", "kill_ms", "spawn_ms", "import_ms", "startup_ms",
         "device_plane_ms", "restore_ms", "broadcast_ms", "compile_ms",
         "first_step_ms")
KEYS = PARTS + ("mode", "version", "old_size", "new_size", "wait_config_ms",
                "consensus_ms", "notify_ms", "compile_hits", "compile_misses",
                "pause_ms", "unaccounted_ms", "unaccounted_largest")


def _sum(parts: dict) -> float:
    return sum(parts[k] or 0.0 for k in PARTS) + parts["unaccounted_ms"]


def test_a_first_incarnation_has_no_pause():
    assert pause_parts({}, [("worker.import", 1.0, 1.0, None)], 5.0, 4.0) == {}
    assert pause_parts({"phases_ms": {}, "mode": "reload"}, [], 5.0) == {}


CASES = {
    # nothing in the ring: the marks' parts and the first step are all there is
    "marks_alone": dict(
        events=[],
        want=dict(agree_ms=500.0, notify_ms=200.0, kill_ms=500.0, spawn_ms=250.0,
                  first_step_ms=1000.0, pause_ms=8000.0, unaccounted_ms=5750.0),
    ),
    # a trace span inside a backend span inside a broadcast: merged, and the
    # broadcast keeps what no compile claims (103.0-103.5 and 105.0-105.25)
    "nested_compiles_are_merged": dict(
        events=[
            ("broadcast.one_to_all", 103.0, 2.25, None),
            ("device_plane.compile.trace", 103.5, 1.0, {"fun_name": "f"}),
            ("device_plane.compile.lower", 103.75, 0.5, {"fun_name": "f"}),
            ("device_plane.compile.backend", 104.0, 1.0, {"cache": "miss"}),
        ],
        want=dict(compile_ms=1500.0, broadcast_ms=750.0, compile_misses=1,
                  compile_hits=0, unaccounted_ms=3500.0),
    ),
    "a_hit_and_a_miss_are_counted": dict(
        events=[
            ("device_plane.compile.backend", 102.0, 0.5, {"cache": "hit"}),
            ("device_plane.compile.backend", 103.0, 1.5, {"cache": "miss"}),
            ("device_plane.compile.backend", 50.0, 1.0, {"cache": "miss"}),  # before the spawn
        ],
        want=dict(compile_ms=2000.0, compile_hits=1, compile_misses=1),
    ),
    # the runner's `t_killed` is missing: neither kill nor spawn can be read,
    # and their 750 ms are unaccounted, not hidden
    "a_missing_runner_mark": dict(
        marks={k: v for k, v in MARKS.items() if k != "t_killed"},
        events=[],
        want=dict(agree_ms=500.0, kill_ms=None, spawn_ms=None,
                  pause_ms=8000.0, unaccounted_ms=6500.0),
    ),
    # a standby imported jax before it was activated, and `worker.startup`
    # starts a clock's reading before the runner's `t_spawn`: cut to the window
    "a_span_that_straddles_the_spawn": dict(
        events=[
            ("worker.import", 100.25, 2.0, {"module": "kungfu_tpu"}),
            ("worker.startup", 101.0, 2.25, None),
            ("worker.start.update", 103.25, 0.75, None),
        ],
        want=dict(import_ms=1000.0, startup_ms=1750.0, unaccounted_ms=3000.0),
    ),
    # the first step holds a compile and the state's sync: each keeps its own
    "the_first_step_is_what_is_left_of_it": dict(
        events=[
            ("elastic.sync_state", 107.0, 0.25, None),
            ("device_plane.compile.backend", 107.25, 0.5, {"cache": "hit"}),
            ("device_plane.backend_start", 102.0, 3.0, None),
            ("checkpoint.open", 105.0, 0.5, None),
            ("checkpoint.restore", 105.5, 1.0, {"step": 21}),
        ],
        want=dict(first_step_ms=250.0, broadcast_ms=250.0, compile_ms=500.0,
                  device_plane_ms=3000.0, restore_ms=1500.0, unaccounted_ms=1250.0),
    ),
}


@pytest.mark.parametrize("case", CASES)
def test_pause_parts(case):
    spec = CASES[case]
    parts = pause_parts(spec.get("marks", MARKS), spec["events"], NOW, FIRST_STEP)
    assert set(KEYS) <= set(parts), set(KEYS) - set(parts)
    for key, value in spec["want"].items():
        assert parts[key] == value, (key, parts)
    assert parts["pause_ms"] == 8000.0
    assert parts["unaccounted_ms"] >= 0
    assert _sum(parts) == pytest.approx(parts["pause_ms"], abs=1e-6)


def test_the_largest_unclaimed_stretch_is_named():
    parts = pause_parts(MARKS, [
        ("worker.startup", 101.25, 1.75, None),
        ("smoke.init_params", 103.25, 1.5, None),  # no part's: under the stretch
        ("checkpoint.restore", 105.0, 1.0, {"step": 21}),
    ], NOW, FIRST_STEP)
    assert parts["unaccounted_largest"] == {
        "ms": 2000.0, "after": "worker.startup", "before": "checkpoint.restore",
        "under": ["smoke.init_params"],
    }
    # without a first step's start the step is not a part, and says so
    parts = pause_parts(MARKS, [], NOW)
    assert parts["first_step_ms"] is None
    assert parts["unaccounted_ms"] == 6750.0


def test_the_pause_begins_at_the_first_mark_there_is():
    """A Stage the runner made itself (a worker died) has no proposer."""
    marks = {"t_stage": 100.5, "t_killed": 101.0, "t_spawn": 101.25,
             "mode": "reload", "old_size": 4}
    parts = pause_parts(marks, [], NOW, FIRST_STEP)
    assert parts["agree_ms"] is None and parts["notify_ms"] is None
    assert parts["kill_ms"] == 500.0 and parts["pause_ms"] == 7500.0
    assert _sum(parts) == pytest.approx(7500.0)


@pytest.mark.parametrize("a,b,union,minus", [
    ([(0, 2), (1, 3), (5, 6)], [(2, 5.5)], [(0, 3), (5, 6)], [(0, 2), (5.5, 6)]),
    ([(0, 10)], [(1, 2), (3, 4)], [(0, 10)], [(0, 1), (2, 3), (4, 10)]),
    ([(1, 1), (2, 1)], [], [], []),  # empty and inverted intervals are nothing
])
def test_interval_union_and_difference(a, b, union, minus):
    assert _union(a) == union
    assert _minus(_union(a), _union(b)) == minus


def test_the_runners_ring_is_one_more_process_of_the_cluster_trace():
    from kungfu_tpu.runner.watch import _with_runner_ring
    from kungfu_tpu.telemetry import tracing

    tracing.clear()
    with tracing.span("runner.stage", version=1, reload=True):
        with tracing.span("runner.spawn", rank=0, version=1):
            pass
    merged = {"traceEvents": [
        {"name": "process_name", "ph": "M", "pid": 0, "args": {"name": "w0"}},
        {"name": "worker.startup", "ph": "X", "pid": 1, "ts": 5.0, "dur": 1.0},
    ], "displayTimeUnit": "ms"}
    doc = _with_runner_ring(merged)
    tracing.clear()
    runner = [e for e in doc["traceEvents"] if e["pid"] == 2]
    assert {"name": "process_name", "ph": "M", "pid": 2, "tid": 0,
            "args": {"name": "runner"}} in runner
    spans = {e["name"]: e for e in runner if e["ph"] == "X"}
    assert set(spans) == {"runner.stage", "runner.spawn"}
    assert spans["runner.spawn"]["args"] == {"rank": 0, "version": 1, "depth": 1}
    # on the merge's clock as they are: the runner's perf_counter, in us
    assert spans["runner.stage"]["ts"] <= spans["runner.spawn"]["ts"]
    # the workers' events are untouched
    assert doc["traceEvents"][:2] == merged["traceEvents"][:2]
    # and with no worker scraped yet the runner is process 0
    assert {e["pid"] for e in _with_runner_ring({"traceEvents": []})["traceEvents"]} == {0}
