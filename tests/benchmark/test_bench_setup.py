"""The set-up read from inside (PR 38): the record's marks, the program's
spans and JAX's duration events of the first step; `setup_s` as the whole
less the backend's start; the per-layer metrics that read the marks and the
spans; every rank's marks and spans in the record. On the CPU mesh at tiny
size, and on records drawn by hand."""

import json
import time

import jax
import pytest

from benchmark import end_to_end, harness, manifest as mf
from benchmark.launchers.none import OneProcess
from benchmark.layer_metrics import (broadcast_replicate_s, broadcast_wait_s,
                                     first_step_load_or_compile_s,
                                     first_step_s, first_step_trace_lower_s,
                                     host_pool_s, launch_to_world_s,
                                     state_init_s, state_place_s,
                                     warmup_probe_s)
from drawn_setup import (COMPILE_EVENT, LOWER_EVENT, TRACE_EVENT, child_marks,
                         drawn_setup)
from test_bench_loop import _tiny_cell

PARTS = (launch_to_world_s, state_init_s, state_place_s, host_pool_s,
         first_step_s, warmup_probe_s)
SETUP_METRICS = {
    "launch_to_world_s": ("Launcher", "host_clock"),
    "state_init_s": ("Model", "host_clock"),
    "state_place_s": ("Launcher", "host_clock"),
    "host_pool_s": ("Input", "host_clock"),
    "first_step_s": ("Train step", "host_clock"),
    "warmup_probe_s": ("Train step", "host_clock"),
    "first_step_trace_lower_s": ("Train step", "program_counter"),
    "first_step_load_or_compile_s": ("Train step", "program_counter"),
    "broadcast_wait_s": ("Launcher", "program_span"),
    "broadcast_replicate_s": ("Launcher", "program_span"),
}
KFRUN_CELL = "bert_base.ssgd_kfrun_4chip"


class Broadcasting(OneProcess):
    """A one-process world that raises the spans a kfrun worker's
    `broadcast_variables` raises, where it raises them: inside the
    placement, and once more, for the window's step count, after it."""

    def place_state(self, state, mesh):
        from kungfu_tpu.telemetry import tracing

        with tracing.span("broadcast.one_to_all", leaves=9, bytes=1 << 20):
            time.sleep(0.02)
        with tracing.span("broadcast.replicate"):
            return jax.block_until_ready(super().place_state(state, mesh))

    def agree_steps(self, n):
        from kungfu_tpu.telemetry import tracing

        with tracing.span("broadcast.one_to_all", leaves=1, bytes=4):
            time.sleep(0.01)
        return n


@pytest.fixture(scope="module")
def measured():
    """One run of `measure` at tiny size on four CPU devices, as `child.py`
    calls it: with the child's own marks from before the call."""
    from kungfu_tpu.telemetry import tracing

    t_command = time.time()
    marks = child_marks()
    with tracing.span("worker.startup"):
        pass
    with tracing.span("sched.not_the_records"):
        pass
    cell, mesh = _tiny_cell()
    return harness.measure(cell, mesh, Broadcasting(), {"bf16_flops": 197e12},
                           seed=5, seconds=0.2, trace_dir=None,
                           events=harness.EventCounter(), t_command=t_command,
                           marks=marks)


def test_measure_gives_the_marks_in_order(measured):
    marks = measured["marks"]
    assert tuple(marks) == harness.MARKS
    times = list(marks.values())
    assert times == sorted(times)
    for name in ("t_command", "t_world", "t_window"):  # under their old names too
        assert measured[name] == marks[name]
    assert marks["t_first_1"] - marks["t_first_0"] == pytest.approx(
        measured["first_step_s"], abs=5e-3)
    json.dumps(measured["marks"])


def test_measure_gives_the_programs_spans_on_the_marks_clock(measured):
    spans, marks = measured["spans"], measured["marks"]
    assert all(len(s) == 5 for s in spans)
    names = [s[0] for s in spans]
    assert names.count("broadcast.one_to_all") == 2
    assert "broadcast.replicate" in names and "worker.startup" in names
    assert all(n.startswith(harness.SPAN_PREFIXES) for n in names)
    assert [s[1] for s in spans] == sorted(s[1] for s in spans)
    for name, start, end, depth, args in spans:
        assert marks["t_command"] - 60 <= start <= end <= time.time()
        assert isinstance(depth, int) and isinstance(args, dict)
    # the ring's clock is perf_counter; in the record the spans lie where
    # the marks put their phases, to the clocks' own jitter
    first, later = [s for s in spans if s[0] == "broadcast.one_to_all"]
    (replicate,) = [s for s in spans if s[0] == "broadcast.replicate"]
    assert first[4] == {"leaves": 9, "bytes": 1 << 20}
    assert marks["t_init"] - 2e-3 <= first[1] and replicate[2] <= marks["t_placed"] + 2e-3
    assert first[2] - first[1] >= 0.02
    assert marks["t_first_1"] - 2e-3 <= later[1] and later[2] <= marks["t_window"] + 2e-3
    json.dumps(spans)


def test_the_parts_of_a_measured_set_up_sum_to_it(measured):
    parts = {r.__name__.split(".")[-1]: r.read(measured, None) for r in PARTS}
    assert all(v >= 0 for v in parts.values()), parts
    assert sum(parts.values()) == pytest.approx(
        end_to_end.values(measured)["setup_s"], abs=5e-3)
    world_on = sum(v for k, v in parts.items() if k != "launch_to_world_s")
    assert world_on == pytest.approx(
        measured["t_window"] - measured["t_world"], abs=5e-3)
    # the span readers count the broadcast inside the placement alone
    assert 0.02 <= broadcast_wait_s.read(measured, None) < 0.03
    assert 0 < broadcast_replicate_s.read(measured, None)
    assert (broadcast_wait_s.read(measured, None)
            + broadcast_replicate_s.read(measured, None)
            <= parts["state_place_s"])


def test_the_first_steps_events_are_in_the_record(measured):
    events = measured["first_step_events"]
    assert {TRACE_EVENT, LOWER_EVENT, COMPILE_EVENT} <= set(events)
    for body in events.values():
        assert body["count"] >= 1 and body["sum_s"] >= 0
        assert body["spans"] == sorted(body["spans"])
        for start, end in body["spans"]:
            assert measured["marks"]["t_first_0"] <= start <= end
            assert end <= measured["marks"]["t_first_1"]
    # the model calls jitted functions: their traces lie inside the step's
    assert events[TRACE_EVENT]["count"] > len(events[TRACE_EVENT]["spans"])
    trace_lower = first_step_trace_lower_s.read(measured, None)
    load_or_compile = first_step_load_or_compile_s.read(measured, None)
    assert trace_lower > 0 and load_or_compile > 0
    assert trace_lower + load_or_compile <= measured["first_step_s"]
    json.dumps(events)


def test_the_event_counter_keeps_sums_and_merged_spans():
    from jax import monitoring

    events = harness.EventCounter()
    before = events.reading()
    assert events.since(before) == {}
    for start, end in ((10.0, 11.0), (10.25, 10.5), (12.0, 12.5)):
        monitoring.record_event_duration_secs("/test/traced", end - start)
        monitoring.record_event_time_span("/test/traced", start, end)
    monitoring.record_event_duration_secs("/test/retrieved", 0.125)
    monitoring.record_event("/test/counted")
    assert events["/test/traced"] == 3 and events["/test/counted"] == 1
    assert events.since(before) == {
        "/test/traced": {"count": 3, "sum_s": 1.75,
                         "spans": [[10.0, 11.0], [12.0, 12.5]]},
        "/test/retrieved": {"count": 1, "sum_s": 0.125, "spans": []}}
    assert events.since(events.reading()) == {}


@pytest.mark.parametrize("kfrun,backend_s", [(False, 8.0), (True, 9.0)])
def test_setup_s_is_the_whole_less_the_backends_start(kfrun, backend_s):
    """From the program's span where the ring has one (a kfrun worker: the
    child's own `jax.devices()` finds the world up) and from the child's
    marks where it has none."""
    record = {**drawn_setup(kfrun), "window": {"t_done": [117.6, 117.7, 117.8]},
              "samples_per_step": 16, "chips": 1, "flops_per_sample": 1e9,
              "peak_flops": 1e12}
    assert end_to_end.command_to_window_s(record) == pytest.approx(17.5)
    assert end_to_end.backend_start_s(record) == pytest.approx(backend_s)
    assert end_to_end.values(record)["setup_s"] == pytest.approx(17.5 - backend_s)
    assert launch_to_world_s.read(record, None) == pytest.approx(12.0 - backend_s)
    parts = [r.read(record, None) for r in PARTS]
    assert parts[1:] == pytest.approx([1.5, 0.5, 0.25, 0.7, 2.55])
    assert sum(parts) == pytest.approx(end_to_end.values(record)["setup_s"])


def test_a_backend_span_after_the_world_stood_is_not_the_launch():
    """A world that is joined again later (a resize) starts a backend
    again; what `setup_s` leaves out is the launch's."""
    record = drawn_setup(kfrun=True)
    record["spans"].append(["device_plane.backend_start", 120.0, 131.0, 0, {}])
    assert end_to_end.backend_start_s(record) == pytest.approx(9.0)


@pytest.mark.parametrize("reader,seconds", [
    (broadcast_wait_s, 0.375), (broadcast_replicate_s, 0.125)],
    ids=["broadcast_wait_s", "broadcast_replicate_s"])
def test_the_span_readers_count_what_lies_inside_the_placement(reader, seconds):
    record = drawn_setup(kfrun=True)
    # the later `broadcast.one_to_all`, of four bytes, is not in it
    assert reader.read(record, None) == pytest.approx(seconds)
    assert (broadcast_wait_s.read(record, None)
            + broadcast_replicate_s.read(record, None)
            <= state_place_s.read(record, None))
    # a one-process world raises neither span: nothing to read
    assert reader.read(drawn_setup(), None) is None


def test_the_first_step_readers_on_a_drawn_record():
    """The trace's and the lowering's spans less the compile of an eager op
    inside them, and the compile requests' own: disjoint, and no more than
    the first step together."""
    record = drawn_setup()
    assert first_step_trace_lower_s.read(record, None) == pytest.approx(
        0.25 + 0.125 - 0.03125)
    assert first_step_load_or_compile_s.read(record, None) == pytest.approx(
        0.03125 + 0.25)
    assert (first_step_trace_lower_s.read(record, None)
            + first_step_load_or_compile_s.read(record, None)
            <= first_step_s.read(record, None))
    record["first_step_events"] = {}
    assert first_step_trace_lower_s.read(record, None) is None
    assert first_step_load_or_compile_s.read(record, None) is None


def test_every_ranks_marks_and_spans_merge_into_the_record(tmp_path):
    """Each rank writes `rank_<n>.json` before the closing barrier; the
    parent puts them into the record in rank order."""
    ranks = []
    for rank in (2, 0, 1, 3):
        setup = drawn_setup(kfrun=True)
        setup["marks"]["t_init"] += 0.25 * rank  # the workers compile longer
        body = {"rank": rank, "marks": setup["marks"], "spans": setup["spans"]}
        (tmp_path / f"rank_{rank}.json").write_text(json.dumps(body))
        ranks.append(body)
    (tmp_path / "record.json").write_text("{}")  # beside them, and no rank's
    record = {"rank": 0, **drawn_setup(kfrun=True)}
    merged = end_to_end.merge_ranks(record, str(tmp_path))
    assert [r["rank"] for r in merged["ranks"]] == [0, 1, 2, 3]
    assert merged["ranks"] == sorted(ranks, key=lambda r: r["rank"])
    assert merged["ranks"][0]["marks"] == record["marks"]
    assert merged["ranks"][3]["marks"]["t_init"] == pytest.approx(114.25)
    assert "ranks" not in record and merged["marks"] == record["marks"]


@pytest.mark.parametrize("name", sorted(SETUP_METRICS))
def test_the_manifest_holds_the_set_ups_metrics(name):
    m = mf.load()
    (entry,) = [x for x in m["per_layer"] if x["name"] == name]
    layer, source = SETUP_METRICS[name]
    assert (entry["layer"], entry["source"]) == (layer, source)
    assert entry["moves"] == "setup_s" and entry["unit"] == "s"
    spans_only = source == "program_span"
    assert entry.get("workloads") == ([KFRUN_CELL] if spans_only else None)


@pytest.mark.parametrize("cell", [w["name"] for w in mf.load()["workloads"]])
def test_a_traced_line_holds_the_set_ups_parts(cell):
    from test_bench_trace import DRAWN, _record

    m = mf.load()
    line = end_to_end.result_line(_record(cell), DRAWN, m)
    mine = {n for n in SETUP_METRICS
            if cell == KFRUN_CELL or SETUP_METRICS[n][1] != "program_span"}
    assert mine == set(SETUP_METRICS) & set(line["metrics"])
    parts = sum(line["metrics"][r.__name__.split(".")[-1]]["value"] for r in PARTS)
    whole = line["device"]["command_to_window_s"] - line["device"]["backend_start_s"]
    assert parts == pytest.approx(whole)
    assert line["device"]["backend_start_s"] == (9.0 if cell == KFRUN_CELL else 8.0)


@pytest.mark.parametrize("cell", [w["name"] for w in mf.load()["workloads"]])
def test_the_five_parts_of_a_step_are_listed_in_every_cell_that_has_them(cell):
    """`fwd_ms + bwd_ms + optimizer_ms + unattributed_ms + allreduce_ms` is
    `device_step_ms` wherever the step has an `optimizer` scope: every cell
    but ResNet's, whose update XLA fuses into the weight gradients."""
    from test_bench_trace import DRAWN, _record

    m = mf.load()
    mine = {x["name"] for x in mf.metrics_of(m, "per_layer", cell)}
    parts = {"fwd_ms", "bwd_ms", "optimizer_ms", "unattributed_ms", "allreduce_ms"}
    assert parts - mine == ({"optimizer_ms"} if cell.startswith("resnet50") else set())
    assert ("head_loss_ms" in mine) == (not cell.startswith("resnet50"))
    if "optimizer_ms" in mine:
        metrics = end_to_end.result_line(_record(cell), DRAWN, m)["metrics"]
        # drawn: fusion.3 overlaps the all-reduce's last half millisecond
        assert sum(metrics[p]["value"] for p in parts) == pytest.approx(
            metrics["device_step_ms"]["value"] + 0.5)
        assert (sum(metrics[p]["value"] for p in parts - {"allreduce_ms"})
                + metrics["allreduce_exposed_ms"]["value"]
                == pytest.approx(metrics["device_step_ms"]["value"]))
