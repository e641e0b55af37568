"""The reduction from a device trace to the per-layer metrics: interval
arithmetic by hand on a drawn trace, then the same code on a trace recorded
on the chip (cut from this PR's own run) against answers worked out
independently of it."""

import gzip
import json
import os

import pytest

from benchmark import end_to_end, manifest as mf, trace_reduce as tr
from benchmark.layer_metrics import (allreduce_exposed_ms, allreduce_ms,
                                     device_idle_pct, device_step_ms)

MS = 1_000_000  # nanoseconds

# Two steps of 10 ms on one chip, drawn by hand (times in ms):
#
#   step 0: [0, 10)  while [0, 6) encloses fusion.1 [0, 2) and fusion.2 [3, 6)
#                    all-reduce.1 [6, 9), fusion.3 [8, 9.5) overlaps its end
#   step 1: [12, 22) fusion.1 [12, 15), all-reduce.1 [15, 19), fusion.3 [19, 21)
#
# host spans: bench.wait [9, 12.5) covers the gap between the steps
DRAWN = {
    "chips": [{
        "plane": "/device:TPU:0", "program": "jit_step",
        "steps": [[0, 10 * MS], [12 * MS, 22 * MS]],
        "ops": [
            ["while.1", 0, 6 * MS],
            ["fusion.1", 0, 2 * MS],
            ["fusion.2", 3 * MS, 6 * MS],
            ["all-reduce.1", 6 * MS, 9 * MS],
            ["fusion.3", 8 * MS, int(9.5 * MS)],
            ["fusion.1", 12 * MS, 15 * MS],
            ["all-reduce.1", 15 * MS, 19 * MS],
            ["fusion.3", 19 * MS, 21 * MS],
        ],
    }],
    "host": [["bench.input", 0, 1 * MS], ["bench.wait", 9 * MS, int(12.5 * MS)],
             ["bench.dispatch", int(12.5 * MS), 13 * MS]],
    "lines": {},
}


@pytest.mark.parametrize("intervals,want", [
    ([[0, 2], [1, 3], [5, 6]], [[0, 3], [5, 6]]),
    ([[5, 6], [0, 2], [2, 4]], [[0, 4], [5, 6]]),
    ([[1, 1], [3, 2]], []),
    ([], []),
])
def test_union(intervals, want):
    assert tr.union(intervals) == want
    assert tr.length(intervals) == sum(b - a for a, b in want)


@pytest.mark.parametrize("intervals,holes,want", [
    ([[0, 10]], [[2, 3], [5, 7]], [[0, 2], [3, 5], [7, 10]]),
    ([[0, 10]], [[0, 10]], []),
    ([[0, 4], [6, 9]], [[3, 7]], [[0, 3], [7, 9]]),
    ([[0, 4]], [], [[0, 4]]),
    ([[2, 4]], [[0, 3], [3, 10]], []),
])
def test_subtract(intervals, holes, want):
    assert tr.subtract(intervals, holes) == want


def test_clip():
    assert tr.clip([[0, 4], [6, 9], [10, 12]], 3, 10) == [[3, 4], [6, 9]]


def test_self_segments_take_enclosed_ops_out():
    own = dict()
    for name, segments in tr.self_segments(DRAWN["chips"][0]["ops"][:3]):
        own[name] = segments
    # the while runs by itself only between its body's two fusions
    assert own["while.1"] == [[2 * MS, 3 * MS]]
    assert own["fusion.1"] == [[0, 2 * MS]]
    assert own["fusion.2"] == [[3 * MS, 6 * MS]]


def test_self_segments_of_disjoint_ops_are_the_ops():
    ops = [["a", 0, 5], ["b", 5, 9], ["c", 20, 30]]
    assert tr.self_segments(ops) == [("a", [[0, 5]]), ("b", [[5, 9]]),
                                     ("c", [[20, 30]])]


def test_self_segments_nested_twice():
    ops = [["outer", 0, 100], ["inner", 10, 60], ["leaf", 20, 30],
           ["leaf", 40, 50], ["tail", 70, 80]]
    own = {}
    for name, segments in tr.self_segments(ops):
        own.setdefault(name, []).extend(segments)
    assert own["outer"] == [[0, 10], [60, 70], [80, 100]]
    assert own["inner"] == [[10, 20], [30, 40], [50, 60]]
    assert sorted(own["leaf"]) == [[20, 30], [40, 50]]


def test_is_all_reduce():
    assert tr.is_all_reduce("all-reduce.17", {})
    assert tr.is_all_reduce("all-reduce-start.2", {})
    assert not tr.is_all_reduce("fusion.3", {})
    assert not tr.is_all_reduce("reduce.4", {})
    # the trace's own account of the operation wins over the name
    kinds = {"psum.73": "all-reduce", "all-reduce_fusion": "fusion"}
    assert tr.is_all_reduce("psum.73", kinds)
    assert not tr.is_all_reduce("all-reduce_fusion", kinds)


def test_drawn_busy_union_and_idle_share():
    busy_s, window_s = tr.device_busy_and_window_s(DRAWN)
    # busy: [0, 9.5) less nothing = 9.5 ms, and [12, 21) = 9 ms; the while's
    # own millisecond [2, 3) counts, an op was running
    assert busy_s == pytest.approx(18.5e-3)
    assert window_s == pytest.approx(22e-3)
    assert device_idle_pct.read({}, DRAWN) == pytest.approx(100 * 3.5 / 22)


def test_drawn_device_step():
    # 9.5 ms and 9 ms busy in the two steps: the median of two is their mean
    assert device_step_ms.read({}, DRAWN) == pytest.approx(9.25)


def test_drawn_all_reduce_and_its_exposed_part():
    # 3 ms and 4 ms a step
    assert allreduce_ms.read({}, DRAWN) == pytest.approx(3.5)
    # step 0: fusion.3 covers [8, 9) of it, 2 ms exposed; step 1: all 4 ms
    assert allreduce_exposed_ms.read({}, DRAWN) == pytest.approx(3.0)


def test_one_chip_trace_has_no_all_reduce():
    alone = json.loads(json.dumps(DRAWN))
    alone["chips"][0]["ops"] = [o for o in alone["chips"][0]["ops"]
                                if not tr.is_all_reduce(o[0], {})]
    assert allreduce_ms.read({}, alone) == 0.0
    assert allreduce_exposed_ms.read({}, alone) == 0.0


def test_drawn_breakdown():
    b = tr.breakdown(DRAWN)
    ops = dict(b["device_ops"])
    assert ops["all-reduce.1"] == pytest.approx(7e-3)
    assert ops["fusion.1"] == pytest.approx(5e-3)
    assert ops["fusion.3"] == pytest.approx(3.5e-3)
    assert ops["while.1"] == pytest.approx(1e-3)  # its own time only
    assert [n for n, _ in b["device_ops"]][0] == "all-reduce.1"
    # gaps: [9.5, 12) under bench.wait, [21, 22) under no span
    assert b["idle_gaps"][0] == ["bench.wait", pytest.approx(2.5e-3)]
    assert b["idle_gaps"][1] == ["no_span", pytest.approx(1e-3)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 5


def test_place_spans_puts_the_host_clock_on_the_trace_clock():
    """The host saw the two drawn steps end at 100.0102 s and 100.0222 s
    on its own clock: 0.2 ms after the device ended them at 10 and 22 ms."""
    t = json.loads(json.dumps(DRAWN))
    t["host"] = []
    window = {"t_done": [100.0102, 100.0222],
              "spans": [["bench.wait", 100.0097, 100.0102],
                        ["bench.input", 100.0102, 100.0112],
                        ["bench.wait", 100.0112, 100.0222]]}
    tr.place_spans(t, window)
    # offset: the device's step ends less the host's completion times
    assert t["host"][0] == ["bench.wait", round(9.5 * MS), 10 * MS]
    assert t["host"][1] == ["bench.input", 10 * MS, 11 * MS]
    assert t["host"][2] == ["bench.wait", 11 * MS, 22 * MS]
    # the gap between the steps, [9.5, 12) ms: 1.5 ms of it under the waits
    assert tr.breakdown(t)["idle_gaps"][0][0] == "bench.wait"
    empty = {"chips": [], "host": [], "lines": {}}
    tr.place_spans(empty, window)
    assert empty["host"] == []


def test_readers_return_nothing_without_a_trace():
    for reader in (device_step_ms, allreduce_ms, allreduce_exposed_ms,
                   device_idle_pct):
        assert reader.read({}, None) is None
        assert reader.read({}, {"chips": [], "host": [], "lines": {}}) is None


def test_a_traced_line_without_device_ops_is_refused():
    record = {"workload": "bert_base.ssgd_1chip", "traced": True,
              "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
              "t_command": 0.0, "t_world": 1.0, "first_step_s": 1.0,
              "chips": 1, "samples_per_step": 16,
              "window": {"compiles": 0, "t_done": [1.0, 1.1, 1.2, 1.3],
                         "spans": [["bench.input", 1.0, 1.001]]},
              "program_memory": {"total_bytes": 1}, "memory_stats_peak_bytes": 1,
              "correct": True, "attempted": 20, "failed": 0}
    with pytest.raises(RuntimeError, match="no device operation"):
        end_to_end.result_line(record, {"chips": [], "host": [], "lines": {}},
                               mf.load())
    line = end_to_end.result_line(record, DRAWN, mf.load())
    assert tuple(line) == mf.TRACED_RESULT_KEYS
    assert line["device"]["busy_s"] == pytest.approx(18.5e-3)
    assert line["device"]["window_s"] == pytest.approx(22e-3)
    assert set(line["metrics"]) == {m["name"] for m in mf.load()["per_layer"]}


def test_asynchronous_all_reduce_counts_from_start_to_done():
    """Across chips XLA may split an all-reduce into `-start` and `-done`;
    it is under way for the whole of the event the async line holds."""
    t = json.loads(json.dumps(DRAWN))
    c = t["chips"][0]
    c["ops"] = [["fusion.1", 0, 4 * MS], ["all-reduce-start.1", 4 * MS, 4 * MS + 10],
                ["fusion.2", 4 * MS + 10, 7 * MS], ["all-reduce-done.1", 7 * MS, 9 * MS]]
    c["async"] = [["all-reduce-start.1", 4 * MS, 9 * MS]]
    c["steps"] = [[0, 10 * MS]]
    assert allreduce_ms.read({}, t) == pytest.approx(5.0)
    # fusion.2 hides [4 ms + 10 ns, 7 ms) of it; the wait in -done is exposed
    assert allreduce_exposed_ms.read({}, t) == pytest.approx(2.0 + 10e-6)


# -- a trace recorded on the chip ---------------------------------------------
# bert_base.ssgd_1chip, steps 7 and 8 of a traced run of PR 23. The answers
# were worked out apart from trace_reduce, by painting every op onto an
# array with one cell a nanosecond and counting cells.

@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(mf.BENCH_DIR, "testdata", "bert_base_1chip_2steps.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_recorded_trace_is_what_it_says(recorded):
    c = tr.chip(recorded)
    assert c["plane"] == "/device:TPU:0"
    assert c["program"].startswith("jit_local_step(")
    assert len(c["steps"]) == 2 and len(c["ops"]) == 4662 and len(c["async"]) == 1336
    assert recorded["lines"]["/device:TPU:0"]["XLA Ops"] == 46620
    # step boundaries: 78.58 ms each, 3,215 ns apart
    assert c["steps"] == [[0, 78579627], [78582842, 157148684]]


def test_recorded_busy_union_and_idle_share(recorded):
    busy_s, window_s = tr.device_busy_and_window_s(recorded)
    assert round(window_s * 1e9) == 157_148_684
    assert round(busy_s * 1e9) == 157_139_205
    assert device_idle_pct.read({}, recorded) == pytest.approx(0.006031867247, rel=1e-9)


def test_recorded_device_step(recorded):
    c = tr.chip(recorded)
    assert tr.per_step(c, tr.busy(c)) == [78_576_449, 78_562_756]
    assert device_step_ms.read({}, recorded) == pytest.approx(78.5696025, rel=1e-12)


def test_recorded_while_keeps_only_its_own_time(recorded):
    own = {}
    for name, segments in tr.self_segments(tr.chip(recorded)["ops"]):
        own.setdefault(name, []).append(sum(b - a for a, b in segments))
    # forward scan 27.3 ms, backward scan 33.9 ms; nearly all of it their bodies'
    assert own["while.9"][0] == 25_476
    assert own["while.10"][0] == 9_362
    leaf = sum(b - a for n, a, b in tr.chip(recorded)["ops"]
               if n == "bitcast_dynamic-update-slice_fusion.25")
    assert sum(own["bitcast_dynamic-update-slice_fusion.25"]) == leaf == 14_611_780


def test_recorded_one_chip_has_no_all_reduce(recorded):
    assert allreduce_ms.read({}, recorded) == 0.0
    assert allreduce_exposed_ms.read({}, recorded) == 0.0


def test_recorded_breakdown(recorded):
    b = tr.breakdown(recorded)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 5
    name, seconds = b["device_ops"][0]
    assert name.startswith("bitcast_dynamic-update-slice_fusion.25 = (bf16[12,16,512,3072]")
    assert seconds == pytest.approx(14_611_780e-9)
    assert not any(n.startswith("while") for n, _ in b["device_ops"])
    # the longest gap, 5,027 ns, falls while the host waits for a loss
    assert b["idle_gaps"][0] == ["bench.wait", pytest.approx(5_027e-9)]


@pytest.fixture(scope="module")
def recorded_four():
    path = os.path.join(mf.BENCH_DIR, "testdata",
                        "bert_base_kfrun_4chip_1step.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_recorded_four_chip_all_reduces(recorded_four):
    """One step of rank 0's chip under kfrun. `lax.pmean` over the
    gradient tree came out as four synchronous all-reduces, only one of them
    called `all-reduce`; read off the trace by eye: 2,183,710 + 1,997,850 +
    1,999,118 + 1,501,835 ns, back to back with the optimizer's fusions
    between them and nothing beside them."""
    c = tr.chip(recorded_four)
    found = [(n, b - a) for n, a, b in c["ops"]
             if tr.is_all_reduce(n, c["kinds"])]
    assert found == [("all-reduce", 2_183_710), ("psum.73", 1_997_850),
                     ("psum.74", 1_999_118), ("psum.76", 1_501_835)]
    assert allreduce_ms.read({}, recorded_four) == pytest.approx(7.682513, rel=1e-12)
    assert allreduce_exposed_ms.read({}, recorded_four) == pytest.approx(7.682513, rel=1e-12)


def test_recorded_four_chip_step(recorded_four):
    c = tr.chip(recorded_four)
    assert c["steps"] == [[0, 86_377_242]]
    assert tr.per_step(c, tr.busy(c)) == [86_369_599]  # painted: 86,369,599
    assert device_idle_pct.read({}, recorded_four) == pytest.approx(
        100 * (1 - 86_369_599 / 86_377_242), rel=1e-9)
    assert recorded_four["lines"]["/device:TPU:0"]["XLA TraceMe"] == 20


ALL_REDUCE_TEXT = (
    "%all-reduce = (f32[30522,768]{1,0:T(8,128)}, f32[12,768]{1,0:T(8,128)S(1)}, "
    "/*index=5*/f32[512,768]{1,0:T(8,128)S(1)}, f32[]{:T(128)}) all-reduce("
    "f32[30522,768]{1,0:T(8,128)} %fusion.179), channel_id=1")


@pytest.mark.parametrize("text,kind", [
    (ALL_REDUCE_TEXT, "all-reduce"),
    ("%psum.73 = f32[12,768,3072]{2,1,0:T(8,128)} all-reduce(f32[12,768,3072]"
     "{2,1,0:T(8,128)} %get-tuple-element.3046), channel_id=1", "all-reduce"),
    ("%fusion.13 = (f32[256]{0:T(256)S(1)}, bf16[128,56,56,256]{3,0,2,1:T(8,128)(2,1)}) "
     "fusion(f32[256]{0:T(256)S(1)} %copy-done.313)", "fusion"),
    ("%while.9 = (s32[]{:T(128)}, bf16[16,512,768]{2,1,0:T(8,128)(2,1)}) while("
     "(s32[]{:T(128)}) %tuple.1), condition=%cond", "while"),
    ("%copy-start.31 = (s32[2,4,8,128]{3,1,2,0:T(4,128)}, u32[]{:S(2)}) "
     "copy-start(s32[2,4,8,128]{3,2,1,0} %x)", "copy-start"),
])
def test_op_kind(text, kind):
    assert tr.op_kind(text) == kind


def test_op_names_and_labels():
    text = ("%fusion.13 = (f32[256]{0:T(256)S(1)}, bf16[128,56,56,256]{3,0,2,1:T(8,128)(2,1)}) "
            "fusion(f32[256]{0:T(256)S(1)} %copy-done.313)")
    assert tr.op_name(text) == "fusion.13"
    assert tr.op_label(text).startswith("(f32[256], bf16[128,56,56,256]) fusion(")
    assert len(tr.op_label(text)) <= 56
    assert tr.op_name("bench.wait") == "bench.wait"
