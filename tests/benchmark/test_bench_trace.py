"""The reduction from a device trace to the per-layer metrics: interval
arithmetic by hand on a drawn trace, then the same code on a trace recorded
on the chip (cut from this PR's own run) against answers worked out
independently of it."""

import copy
import gzip
import json
import os

import pytest

from benchmark import end_to_end, manifest as mf, trace_reduce as tr
from drawn_setup import drawn_setup
from benchmark.layer_metrics import (allreduce_exposed_ms, allreduce_ms,
                                     attention_core_ms, bwd_ms,
                                     device_idle_pct, device_step_ms, fwd_ms,
                                     head_loss_ms, optimizer_ms,
                                     unattributed_ms)

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


# The drawn step program's scope table, as `trace_reduce.scope_table` reads
# one off a compiled program: the scan (`while.1`) and the attention core's
# matmul in its body forward, the head's backward, the optimizer's update,
# and the all-reduce under `grad_allreduce`.
BLOCK = "jit(step)/shard_map/jvp()/while/body/closed_call"
DRAWN_SCOPES = {
    "while.1": "jit(step)/shard_map/jvp()/while",
    "fusion.1": f"{BLOCK}/attn/attn_core/bhqd,bhkd->bhqk/dot_general",
    "fusion.2": "jit(step)/shard_map/transpose(jvp(head_loss))/jit(log_softmax)/mul",
    "all-reduce.1": "jit(step)/shard_map/optimizer/grad_allreduce/psum",
    "fusion.3": "jit(step)/shard_map/optimizer/optimizer_update/add",
}
SCOPE_READERS = (fwd_ms, bwd_ms, optimizer_ms, unattributed_ms,
                 attention_core_ms, head_loss_ms)


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
    scoped = {"scopes": DRAWN_SCOPES}
    for reader in (device_step_ms, allreduce_ms, allreduce_exposed_ms,
                   device_idle_pct, *SCOPE_READERS):
        assert reader.read(scoped, None) is None
        assert reader.read(scoped, {"chips": [], "host": [], "lines": {}}) is None


@pytest.mark.parametrize("record", [{}, {"scopes": None}, {"scopes": {}}])
def test_scope_readers_return_nothing_without_a_scope_table(record):
    """An untraced run's record has `scopes: None`, and one written before
    PR 26 has no such key."""
    for reader in SCOPE_READERS:
        assert reader.read(record, DRAWN) is None
    assert device_step_ms.read(record, DRAWN) == pytest.approx(9.25)


def test_drawn_scopes_a_while_keeps_only_its_own_time():
    """Forward: step 0 the scan's own millisecond [2, 3) and fusion.1's two,
    not the six the scan spans; step 1 fusion.1's three."""
    record = {"scopes": DRAWN_SCOPES}
    assert fwd_ms.read(record, DRAWN) == pytest.approx(3.0)
    assert attention_core_ms.read(record, DRAWN) == pytest.approx(2.5)
    # the head's backward runs in step 0 alone: 3 ms and 0
    assert bwd_ms.read(record, DRAWN) == pytest.approx(1.5)
    assert head_loss_ms.read(record, DRAWN) == pytest.approx(1.5)
    # fusion.3: 1.5 and 2 ms; the all-reduce beside it under
    # `optimizer/grad_allreduce` is `allreduce_ms`' and in no scope's time
    assert optimizer_ms.read(record, DRAWN) == pytest.approx(1.75)
    assert allreduce_ms.read(record, DRAWN) == pytest.approx(3.5)
    # every op is in the table: nothing is unattributed, and none is read
    assert unattributed_ms.read(record, DRAWN) is None


def test_drawn_scopes_an_op_the_table_does_not_name_is_unattributed():
    table = {k: v for k, v in DRAWN_SCOPES.items() if k != "while.1"}
    table["fusion.3"] = "jit(step)/shard_map"  # plumbing alone names no scope
    record = {"scopes": table}
    assert fwd_ms.read(record, DRAWN) == pytest.approx(2.5)
    # the scan's own millisecond of step 0 and fusion.3: 2.5 and 2 ms
    assert unattributed_ms.read(record, DRAWN) == pytest.approx(2.25)
    assert optimizer_ms.read(record, DRAWN) is None
    # an all-reduce is one by its HLO operation, whatever its scope says
    table["all-reduce.1"] = f"{BLOCK}/ffn/psum"
    assert fwd_ms.read({"scopes": table}, DRAWN) == pytest.approx(2.5)
    kinds = json.loads(json.dumps(DRAWN))
    kinds["chips"][0]["kinds"] = {"fusion.1": "all-reduce"}
    assert fwd_ms.read({"scopes": table}, kinds) is None


def _parts_and_step(record, trace):
    parts = [reader.read(record, trace) for reader in (
        fwd_ms, bwd_ms, optimizer_ms, unattributed_ms)]
    return sum(p or 0.0 for p in parts), parts


def test_drawn_parts_and_the_exposed_all_reduce_make_up_the_step():
    """Forward, backward, optimizer, unattributed and the all-reduce are
    every op's own time once. Where an op overlaps an all-reduce (drawn:
    fusion.3 over its last millisecond in step 0) the step holds that time
    once, so it is the exposed part that completes the sum; on the chip the
    all-reduces were synchronous and the two are one number."""
    record = {"scopes": {k: v for k, v in DRAWN_SCOPES.items() if k != "while.1"}}
    total, parts = _parts_and_step(record, DRAWN)
    assert None not in parts
    assert total + allreduce_exposed_ms.read(record, DRAWN) == pytest.approx(
        device_step_ms.read(record, DRAWN))
    assert total + allreduce_ms.read(record, DRAWN) == pytest.approx(
        device_step_ms.read(record, DRAWN) + 0.5)


def _record(workload="bert_base.ssgd_1chip"):
    return {"workload": workload, "traced": True,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            **drawn_setup(kfrun="kfrun" in workload),
            "chips": 1, "samples_per_step": 16,
            "window": {"compiles": 0, "t_done": [1.0, 1.1, 1.2, 1.3],
                       "spans": [["bench.input", 1.0, 1.001]]},
            "program_memory": {"total_bytes": 1}, "memory_stats_peak_bytes": 1,
            # the scan's own time is left to no scope, so that every
            # scope metric has something to read
            "scopes": {k: v for k, v in DRAWN_SCOPES.items() if k != "while.1"},
            "correct": True, "attempted": 20, "failed": 0}


def test_a_traced_line_without_device_ops_is_refused():
    with pytest.raises(RuntimeError, match="no device operation"):
        end_to_end.result_line(_record(), {"chips": [], "host": [], "lines": {}},
                               mf.load())


@pytest.mark.parametrize("workload", [w["name"] for w in mf.load()["workloads"]])
def test_a_traced_line_holds_exactly_its_cells_per_layer_metrics(workload):
    """Each cell's traced line holds the per-layer metrics the manifest
    lists for that cell and no other: a metric reported in some cells only
    (`"workloads": [...]`) is absent from the others' lines."""
    manifest = mf.load()
    line = end_to_end.result_line(_record(workload), DRAWN, manifest)
    assert tuple(line) == mf.TRACED_RESULT_KEYS
    assert line["device"]["busy_s"] == pytest.approx(18.5e-3)
    assert line["device"]["window_s"] == pytest.approx(22e-3)
    mine = {m["name"] for m in mf.metrics_of(manifest, "per_layer", workload)}
    assert set(line["metrics"]) == mine
    everywhere = {m["name"] for m in manifest["per_layer"] if "workloads" not in m}
    assert {"fwd_ms", "bwd_ms", "unattributed_ms"} <= everywhere <= mine
    assert mf.check_result_line(line, manifest, workload, True) == []


def test_a_metric_listed_for_another_cell_alone_is_absent_from_this_line():
    manifest = copy.deepcopy(mf.load())
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == "head_loss_ms"]
    entry["workloads"] = ["resnet50.ssgd_1chip"]
    assert mf.check(manifest) == []
    line = end_to_end.result_line(_record(), DRAWN, manifest)
    assert "head_loss_ms" not in line["metrics"]
    assert "attention_core_ms" in line["metrics"]
    assert mf.check_result_line(line, manifest, "bert_base.ssgd_1chip", True) == []
    other = end_to_end.result_line(_record("resnet50.ssgd_1chip"), DRAWN, manifest)
    assert other["metrics"]["head_loss_ms"] == {"value": pytest.approx(1.5), "unit": "ms"}
    # and a line that carries it all the same is refused
    line["metrics"]["head_loss_ms"] = {"value": 1.5, "unit": "ms"}
    assert any("head_loss_ms is not a per_layer metric of bert_base.ssgd_1chip" in f
               for f in mf.check_result_line(line, manifest, "bert_base.ssgd_1chip", True))


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


def _partial_table(trace):
    """A scope table that names only some of a recorded trace's ops, by
    their names: the traces are PR 23's, recorded before the program had
    scopes. Every `fusion` forward under `ffn`, every
    `bitcast_dynamic-update-slice_fusion` backward under the attention
    core, every `copy` the optimizer's; some 300 other ops are left out."""
    table = {}
    for name in {o[0] for o in tr.chip(trace)["ops"]}:
        if name.startswith("fusion"):
            table[name] = f"{BLOCK}/ffn/dot_general"
        elif name.startswith("bitcast"):
            table[name] = ("jit(step)/shard_map/transpose(jvp())/while/body/"
                           "closed_call/attn/attn_core/dot_general")
        elif name.startswith("copy"):
            table[name] = "jit(step)/shard_map/optimizer/optimizer_update/add"
    return table


def test_recorded_parts_sum_to_the_device_step(recorded):
    """Own times painted apart from trace_reduce (one cell a nanosecond, an
    enclosed op painted over its `while`), counted by the same name rule."""
    record = {"scopes": _partial_table(recorded)}
    total, parts = _parts_and_step(record, recorded)
    assert parts == pytest.approx([47.354286, 21.580496, 1.8021235, 7.832697],
                                  rel=1e-9)
    assert attention_core_ms.read(record, recorded) == pytest.approx(21.580496)
    assert head_loss_ms.read(record, recorded) is None
    assert total == pytest.approx(device_step_ms.read(record, recorded), abs=1e-6)


def test_recorded_four_chip_parts_and_all_reduces_sum_to_the_step(recorded_four):
    """`psum.73` is an all-reduce by its HLO operation and by nothing in
    its name; given the optimizer's scope it is still no part of
    `optimizer_ms`, and the five numbers make up the step."""
    table = _partial_table(recorded_four)
    table["psum.73"] = "jit(step)/shard_map/optimizer/grad_allreduce/psum"
    table["psum.74"] = "jit(step)/shard_map/optimizer/optimizer_update/add"
    record = {"scopes": table}
    total, parts = _parts_and_step(record, recorded_four)
    assert parts == pytest.approx([43.544945, 21.595075, 2.054824, 11.492242],
                                  rel=1e-9)
    assert total + allreduce_ms.read(record, recorded_four) == pytest.approx(
        device_step_ms.read(record, recorded_four), abs=1e-6)


@pytest.fixture(scope="module")
def recorded_scopes():
    """`bert_base.ssgd_1chip` after PR 25, steps 7 and 8 of a traced run of
    PR 26 whose step program came from the compile cache, with the rows of
    its record's scope table that name an op of those steps."""
    path = os.path.join(mf.BENCH_DIR, "testdata",
                        "bert_base_1chip_scopes_2steps.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_recorded_scope_metrics(recorded_scopes):
    """Against a painting made apart from trace_reduce: every op's interval
    onto an array of one cell a nanosecond, an enclosed op over its `while`,
    each cell labelled by the scope path of the op that owns it."""
    c = tr.chip(recorded_scopes)
    assert len(c["steps"]) == 2 and len(c["ops"]) == 3804
    names = {o[0] for o in c["ops"]}
    # 268 of the 373 ops have no row: the compiler's copies, converts, slices
    assert len(names) == 373 and len(names & set(recorded_scopes["scopes"])) == 105
    record = {"scopes": recorded_scopes["scopes"]}
    want = {fwd_ms: 21.29225, bwd_ms: 31.9941575, optimizer_ms: 3.7527755,
            unattributed_ms: 0.8148525, attention_core_ms: 10.6110965,
            head_loss_ms: 11.706252}
    for reader, ms in want.items():
        assert reader.read(record, recorded_scopes) == pytest.approx(ms, rel=1e-12)
    total, _ = _parts_and_step(record, recorded_scopes)
    assert tr.per_step(c, tr.busy(c)) == [57_852_706, 57_855_365]
    assert total == pytest.approx(device_step_ms.read(record, recorded_scopes), abs=1e-6)
    assert allreduce_ms.read(record, recorded_scopes) == 0.0
    # under 2 % of the step is no scope's
    assert (unattributed_ms.read(record, recorded_scopes)
            < 0.02 * device_step_ms.read(record, recorded_scopes))


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


HLO_TEXT = '''HloModule jit_local_step, is_scheduled=true

%fused_computation.13 (param_0: f32[256]) -> f32[256] {
  %param_0 = f32[256]{0} parameter(0)
  ROOT %multiply.5 = f32[256]{0} multiply(%param_0, %param_0), metadata={op_name="jit(local_step)/shard_map/jvp()/while/body/closed_call/ffn/mul" source_file="transformer.py" source_line=178}
}

ENTRY %main.1 (p: f32[256]) -> f32[256] {
  %p = f32[256]{0} parameter(0), metadata={op_name="state[\\'embed\\']"}
  %copy.3 = f32[256]{0} copy(%p)
  %fusion.13 = f32[256]{0} fusion(%copy.3), kind=kLoop, calls=%fused_computation.13, metadata={op_name="jit(local_step)/shard_map/jvp()/while/body/closed_call/ffn/mul" source_file="transformer.py" source_line=178}
  %psum.73 = f32[256]{0} all-reduce(%fusion.13), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add, metadata={op_name="jit(local_step)/shard_map/optimizer/grad_allreduce/psum"}
  ROOT %fusion.205 = f32[256]{0} fusion(%psum.73), kind=kOutput, calls=%fused_computation.13, metadata={op_name="jit(local_step)/shard_map/optimizer/optimizer_update/add"}
}
'''


def test_scope_table_reads_the_compiled_programs_text():
    table = tr.scope_table(HLO_TEXT)
    assert table["fusion.13"] == "jit(local_step)/shard_map/jvp()/while/body/closed_call/ffn/mul"
    assert table["psum.73"] == "jit(local_step)/shard_map/optimizer/grad_allreduce/psum"
    assert table["fusion.205"].endswith("optimizer/optimizer_update/add")  # a ROOT
    assert table["multiply.5"].endswith("ffn/mul")  # inside a fusion: never an event
    assert "copy.3" not in table  # the compiler's own: no op_name
    assert tr.scope_table("") == {}
    # the names are the trace's: what `op_name` cuts from an event's text
    event = "%fusion.13 = f32[256]{0:T(256)} fusion(f32[256]{0:T(256)} %copy.3)"
    assert tr.op_name(event) in table


BACK = "jit(local_step)/shard_map/transpose(jvp())/while/body/closed_call"


@pytest.mark.parametrize("op_name,parts,phase", [
    (f"{BLOCK}/attn/attn_core/dot_general", ["jvp()", "attn", "attn_core"], "forward"),
    (f"{BACK}/attn/attn_core/attn/attn_core/checkpoint/rematted_computation/exp",
     ["transpose(jvp())", "attn", "attn_core", "attn", "attn_core"], "backward"),
    ("jit(local_step)/shard_map/transpose(jvp(head_loss))/jit(log_softmax)/mul",
     ["transpose(jvp(head_loss))"], "backward"),
    ("jit(local_step)/shard_map/jvp(ResNet)/BottleneckBlock_0/Conv_0/conv_general_dilated",
     ["jvp(ResNet)", "BottleneckBlock_0", "Conv_0"], "forward"),
    # a transposed array is not a transposed computation
    (f"{BLOCK}/attn/transpose", ["jvp()", "attn"], "forward"),
    ("jit(local_step)/shard_map/optimizer/optimizer_update/sub",
     ["optimizer", "optimizer_update"], "optimizer"),
    ("jit(local_step)/shard_map/grad_allreduce/div", ["grad_allreduce"], "optimizer"),
    ("jit(local_step)/shard_map/optimizer/transpose(jvp(x))/mul",
     ["optimizer", "transpose(jvp(x))"], "optimizer"),
    ("jit(local_step)/shard_map", [], "unattributed"),
    ("copy.3", [], "unattributed"),
    ("", [], "unattributed"),
])
def test_scope_parts_and_phase(op_name, parts, phase):
    assert tr.scope_parts(op_name) == parts
    assert tr.phase_of(parts) == phase


def test_scope_names_open_the_transforms_wrappers():
    assert tr.scope_names(["transpose(jvp(head_loss))", "jvp(head_loss)"]) == {
        "transpose", "jvp", "head_loss"}
    assert "attn_core" in tr.scope_names(["jvp()", "attn", "attn_core"])
    assert "head_loss" not in tr.scope_names(["jvp()", "head_loss_scale"])
    assert tr.scope_names([]) == set()
