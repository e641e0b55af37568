"""The timing loop, the window's step count and the end-to-end arithmetic:
driven with fake steps, and end to end at tiny size on a four-device CPU
mesh. A record from the CPU never becomes a result line."""

import copy
import json
import time

import jax
import numpy as np
import pytest

from benchmark import end_to_end, harness, manifest as mf
from benchmark.launchers.none import OneProcess
from drawn_setup import child_marks, drawn_setup

TINY = {
    "bert_base": dict(hidden_size=64, num_hidden_layers=2,
                      num_attention_heads=4, intermediate_size=128,
                      vocab_size=256, max_position_embeddings=64),
    "resnet50": dict(stage_sizes=[1, 1], num_filters=8, num_classes=10,
                     image_size=32, label_classes_used=10,
                     compute_dtype="float32"),
}


class FakeLoss:
    """A loss that is ready `takes` ticks after its step was dispatched."""

    def __init__(self, log, i, value):
        self.log, self.i, self.value = log, i, value

    def block_until_ready(self):
        self.log.append(("wait", self.i))

    def __float__(self):
        return self.value


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def _fake_run(n, start=0, pool=3):
    log = []

    def step(state, opt_state, batch):
        log.append(("dispatch", state, batch))
        return state + 1, opt_state, FakeLoss(log, state, 10.0 - state)

    placed = lambda b: ("placed", b)
    state, _, rec = harness.run_steps(
        step, 0, None, list(range(pool)), placed, n, start, clock=FakeClock())
    return log, state, rec


def test_loop_keeps_one_step_of_look_ahead():
    log, state, rec = _fake_run(4)
    kinds = [(e[0], e[1]) for e in log]
    # step i + 1 is dispatched before the loss of step i is waited for
    assert kinds == [("dispatch", 0), ("dispatch", 1), ("wait", 0),
                     ("dispatch", 2), ("wait", 1), ("dispatch", 3),
                     ("wait", 2), ("wait", 3)]
    assert state == 4
    assert rec["losses"] == [10.0, 9.0, 8.0, 7.0]


def test_loop_records_one_completion_a_step():
    _, _, rec = _fake_run(5)
    assert len(rec["t_done"]) == 5
    names = [name for name, _, _ in rec["spans"]]
    assert names.count("bench.input") == names.count("bench.dispatch") == 5
    assert names.count("bench.wait") == 5
    assert all(a < b for _, a, b in rec["spans"])
    # a wait's end is the step's completion time
    assert [b for name, _, b in rec["spans"] if name == "bench.wait"] == rec["t_done"]
    assert rec["t_done"] == sorted(rec["t_done"])
    assert rec["t_start"] < rec["t_done"][0]
    assert len(harness.intervals(rec)) == 4


@pytest.mark.parametrize("start,want", [(0, [0, 1, 2, 0]), (2, [2, 0, 1, 2]),
                                        (7, [1, 2, 0, 1])])
def test_loop_cycles_the_pool_from_where_it_stood(start, want):
    log, _, _ = _fake_run(4, start)
    assert [e[2][1] for e in log if e[0] == "dispatch"] == want


def test_loop_of_no_steps():
    log, state, rec = _fake_run(0)
    assert log == [] and state == 0 and rec["t_done"] == []


@pytest.mark.parametrize("seconds,step_s,want", [
    (20, 0.080, 250), (20, 0.0471, 424), (10, 0.088, 113), (1, 3.0, 2)])
def test_steps_for_a_window(seconds, step_s, want):
    done = list(np.arange(6) * step_s)
    done[3] += step_s / 3  # one late step moves two intervals, not the median
    assert harness.steps_for(seconds, {"t_done": done}) == want


def test_one_process_world_agrees_with_itself():
    world = OneProcess()
    assert world.agree_steps(250) == 250
    assert world.agree_digest({"w": np.ones(3)}) is True
    assert (world.rank, world.size) == (0, 1)


def test_params_digest_sees_one_changed_value():
    a = {"w": np.zeros((4, 4), np.float32), "b": np.ones(2, np.float32)}
    b = copy.deepcopy(a)
    assert harness.params_digest(a) == harness.params_digest(b)
    b["w"][3, 3] = 1e-7
    assert harness.params_digest(a) != harness.params_digest(b)


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 3.0), (95, 4.8), (100, 5.0)])
def test_percentile_by_hand(q, want):
    assert end_to_end.percentile([5.0, 1.0, 3.0, 2.0, 4.0], q) == pytest.approx(want)
    assert end_to_end.percentile([5.0, 1.0, 3.0, 2.0, 4.0], q) == pytest.approx(
        float(np.percentile([5.0, 1.0, 3.0, 2.0, 4.0], q)))


def _record():
    return {
        "workload": "bert_base.ssgd_1chip", "traced": False,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "chips": 4, "samples_per_step": 64, "flops_per_sample": 1e9,
        "peak_flops": 1e12, **drawn_setup(),
        # five steps: four intervals of 0.1, 0.1, 0.1, 0.2 s
        "window": {"t_start": 10.0, "t_done": [10.1, 10.2, 10.3, 10.4, 10.6],
                   "spans": [["bench.input", 10.0, 10.001],
                             ["bench.dispatch", 10.001, 10.002],
                             ["bench.input", 10.1, 10.103],
                             ["bench.input", 10.2, 10.202]],
                   "compiles": 0},
        "program_memory": {"total_bytes": 15_000_000_000},
        "memory_stats_peak_bytes": 1_320_000_000,
        "attempted": 5, "failed": 0, "correct": True,
    }


def test_end_to_end_values_by_hand():
    v = end_to_end.values(_record())
    # four intervals are too few for segments of eight steps: each is one,
    # 16 samples a chip in 0.1, 0.1, 0.1 and 0.2 s, and the median is 160
    assert end_to_end.segment_rates(_record()) == pytest.approx(
        [160.0, 160.0, 160.0, 80.0])
    assert v["samples_per_s_per_chip"] == pytest.approx(160.0)
    assert v["step_ms_p50"] == pytest.approx(100.0)
    assert v["step_ms_p95"] == pytest.approx(185.0)  # 100 + 0.85 * 100
    assert v["mfu_pct"] == pytest.approx(100 * 160.0 * 1e9 / 1e12)
    # by the wall clock, first completion to last: 4 * 16 / 0.5 = 128
    assert end_to_end.stall_share(_record()) == pytest.approx(1 - 128 / 160)
    # command to window 17.5 s, of which the backend's start took 8
    assert v["setup_s"] == pytest.approx(9.5)
    assert all(x > 0 for x in v.values())


def _with_intervals(intervals):
    record = _record()
    record["window"]["t_done"] = list(10.0 + np.cumsum([0.086] + list(intervals)))
    return record


def test_segments_are_eight_steps_from_completion_to_completion():
    record = _with_intervals([0.086] * 253)  # 254 steps, as bert_base on a chip
    rates = end_to_end.segment_rates(record)
    assert len(rates) == 253 // 8
    assert rates == pytest.approx([16 / 0.086] * 31)
    # a window too short for eight segments of eight takes shorter ones
    assert len(end_to_end.segment_rates(_with_intervals([0.086] * 40))) == 8
    assert len(end_to_end.segment_rates(_with_intervals([0.086]))) == 1


@pytest.mark.parametrize("pauses", [1, 2, 4])
def test_a_pause_of_the_host_moves_the_stall_share_and_no_bounded_metric(pauses):
    """What refused PR 23's first manifest: 55 to 400 ms of pause in some
    runs and none in others, under one step in twenty. Every metric that
    has a bound stands still, and `stall_share` holds the whole of it."""
    steady = [0.086] * 253
    hit = list(steady)
    for at in (40, 97, 170, 171)[:pauses]:
        hit[at] += 0.110
    a = end_to_end.values(_with_intervals(steady))
    b = end_to_end.values(_with_intervals(hit))
    for name in a:
        assert b[name] == pytest.approx(a[name], rel=1e-9), name
    assert end_to_end.stall_share(_with_intervals(steady)) == 0.0
    assert end_to_end.stall_share(_with_intervals(hit)) == pytest.approx(
        pauses * 0.110 / (253 * 0.086 + pauses * 0.110))
    # the rate by the wall clock would have lost that share
    assert 0.004 < pauses * 0.110 / 21.9 < 0.021


def test_a_stall_that_comes_back_moves_the_rate_or_the_tail():
    """The review's cases. 100 ms at every 12th step is in more than half
    of the segments of eight steps, so the rate loses its whole cost, and
    one step in twelve is beyond the 95th percentile, which moves too. At
    every 25th step it is in under half of the segments and under one
    step in twenty: the bounded metrics stand and `stall_share` has it."""
    a = end_to_end.values(_with_intervals([0.086] * 250))
    often = [0.086] * 250
    often[11::12] = [0.186] * len(often[11::12])
    b = end_to_end.values(_with_intervals(often))
    assert b["samples_per_s_per_chip"] == pytest.approx(
        a["samples_per_s_per_chip"] * (8 * 0.086) / (8 * 0.086 + 0.1))
    assert b["mfu_pct"] / a["mfu_pct"] == pytest.approx(
        b["samples_per_s_per_chip"] / a["samples_per_s_per_chip"])
    assert b["step_ms_p95"] == pytest.approx(186.0)
    assert b["step_ms_p50"] == pytest.approx(a["step_ms_p50"])
    rare = [0.086] * 250
    rare[24::25] = [0.186] * 10
    c = end_to_end.values(_with_intervals(rare))
    for name in a:
        assert c[name] == pytest.approx(a[name]), name
    lost = 10 * 0.1 / (250 * 0.086 + 10 * 0.1)  # 4.4 % of the wall time
    assert end_to_end.stall_share(_with_intervals(rare)) == pytest.approx(lost)
    # a slowdown of every step moves the rate and the percentiles alike
    d = end_to_end.values(_with_intervals([0.086 * 1.02] * 250))
    assert d["samples_per_s_per_chip"] == pytest.approx(
        a["samples_per_s_per_chip"] / 1.02)
    assert d["step_ms_p50"] == pytest.approx(a["step_ms_p50"] * 1.02)


def test_result_line_of_a_chip_record():
    m = mf.load()
    line = end_to_end.result_line(_record(), None, m)
    assert tuple(line) == mf.RESULT_KEYS
    assert set(line["metrics"]) == {e["name"] for e in m["end_to_end"]}
    assert line["metrics"]["step_ms_p50"] == {"value": pytest.approx(100.0), "unit": "ms"}
    # memory_stats() misses the program's temporaries: the larger counts
    assert line["device"]["memory_peak_bytes"] == 15_000_000_000
    # what `setup_s` leaves out, and the whole, beside it and unbounded
    assert line["device"]["backend_start_s"] == pytest.approx(8.0)
    assert line["device"]["command_to_window_s"] == pytest.approx(17.5)
    assert line["metrics"]["setup_s"]["value"] == pytest.approx(9.5)
    # what `correct` was decided on, last: a drawn record has the counts alone
    assert line["compared"] == {"compiles_in_window": [0, 0], "steps_failed": [0, 0]}
    json.dumps(line)


def test_the_line_ends_with_each_number_compared_beside_its_limit():
    record = {**_record(), "failed": 1, "loss_passes": [10.0, 9.5],
              "reference": {"loss_error": 1e-5, "loss_rtol": 2e-4,
                            "grad_error": 0.05, "grad_rtol": 0.04,
                            "precision_faults": ["the loss is bfloat16"]},
              "checks": {"reference_grads": False, "loss_fell": True,
                         "state_spans_mesh": True, "workers_agree_on_state": False}}
    line = end_to_end.result_line(record, None, mf.load())
    assert tuple(line)[-1] == "compared" and line["compared"] == {
        "loss_error": [1e-5, 2e-4], "grad_error": [0.05, 0.04],
        "last_pass_over_first": [0.95, 1.0], "compiles_in_window": [0, 0],
        "steps_failed": [1, 0], "precision_faults": [1, 0],
        "reference_grads": [0, 1], "loss_fell": [1, 1],
        "state_spans_mesh": [1, 1], "workers_agree_on_state": [0, 1]}


@pytest.mark.parametrize("platform", ["cpu", "gpu", "CPU"])
def test_no_other_platform_reaches_a_result_line(platform):
    record = _record()
    record["device"]["platform"] = platform
    with pytest.raises(RuntimeError, match="chip runs only"):
        end_to_end.result_line(record, None, mf.load())


def test_require_chips_refuses_the_cpu():
    with pytest.raises(RuntimeError, match="no TPU"):
        harness.require_chips(jax.devices(), 1)


def test_unknown_device_kind_is_an_error():
    assert harness.load_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(RuntimeError, match="unknown device kind"):
        harness.load_peaks("TPU v9 imaginary")


def test_layer_readers_that_find_nothing_are_left_out():
    m = mf.load()
    names = [x["name"] for x in m["per_layer"]]
    record = _record()
    found = end_to_end.layer_values(record, None, names)
    # no trace: the trace's metrics are absent, the record's are there
    assert set(found) == {"launch_to_world_s", "first_step_s",
                          "compiles_in_window", "input_wait_ms_p50",
                          "stall_share_pct", "step_program_hbm_gb",
                          "state_init_s", "state_place_s", "host_pool_s",
                          "warmup_probe_s", "first_step_trace_lower_s",
                          "first_step_load_or_compile_s"}
    assert found["launch_to_world_s"] == pytest.approx(4.0)
    assert found["input_wait_ms_p50"] == pytest.approx(2.0)
    assert found["step_program_hbm_gb"] == pytest.approx(15.0)
    assert found["compiles_in_window"] == 0.0
    assert found["stall_share_pct"] == pytest.approx(20.0)
    # a window of two steps has no median to be late against
    record["window"]["t_done"] = [10.1, 10.2]
    assert "stall_share_pct" not in end_to_end.layer_values(record, None, names)


@pytest.fixture(scope="module")
def events():
    return harness.EventCounter()


def _tiny_cell(workload="bert_base.ssgd_1chip", mesh=None):
    from kungfu_tpu.parallel import make_mesh

    mesh = mesh or {"dp": 4}
    cell = mf.cell(mf.load(), workload)
    cell["config"].update(TINY[cell["config_name"]])
    cell["traffic"].update(per_chip_batch=2, mesh=mesh)
    return cell, make_mesh(mesh, devices=jax.devices()[:4])


@pytest.mark.parametrize("workload", ["bert_base.ssgd_1chip", "resnet50.ssgd_1chip"])
def test_measure_at_tiny_size_on_four_cpu_devices(workload, events):
    """The whole of `measure` — state, pool, first step, warm-up, probe,
    window, checks — on a dp = 4 mesh of virtual CPU devices."""
    m = mf.load()
    cell, mesh = _tiny_cell(workload)
    record = harness.measure(cell, mesh, OneProcess(),
                             {"bf16_flops": 197e12}, seed=3, seconds=0.3,
                             trace_dir=None, events=events,
                             t_command=time.time(), marks=child_marks())
    assert record["checks"]["no_compile_in_window"], record["window"]["compiles"]
    assert record["checks"]["state_spans_mesh"]
    assert record["checks"]["loss_fell"], (record["losses_before"],
                                           record["window"]["losses"][-8:])
    assert record["checks"]["reference_loss"], record["reference"]
    assert record["checks"]["declared_precision"], record["reference"]
    assert record["failed"] == 0
    n = record["attempted"]
    assert n == harness.steps_for(0.3, {"t_done": np.cumsum(
        [0.0] + record["probe_intervals_s"])})
    assert len(record["window"]["t_done"]) == n == len(record["window"]["losses"])
    assert record["samples_per_step"] == 8 and record["chips"] == 4
    assert record["program_memory"]["total_bytes"] > 0
    json.dumps(record)  # the child writes it as JSON
    values = end_to_end.values(record)
    assert all(v > 0 for v in values.values())
    # and the CPU's platform string never reaches a device metric
    assert record["device"]["platform"] == "cpu"
    with pytest.raises(RuntimeError, match="chip runs only"):
        end_to_end.result_line(record, None, m)


class KeepsTheState(OneProcess):
    """A world that holds on to the run's final state, as `measure` itself
    did until PR 26."""

    def agree_digest(self, state):
        self.kept = state
        return True


@pytest.mark.parametrize("world,copies", [(OneProcess, 3), (KeepsTheState, 4)])
def test_the_reference_check_holds_three_copies_of_the_parameters(
        world, copies, events, monkeypatch):
    """What is alive on the devices when the reference has computed its
    gradients: the initial state made again from the seed, the program's
    gradients and the reference's, X bytes each, and beside them only the
    sample, the two losses and the first placed batch. The run's final
    state (X) and AdamW's (2X) are gone by then: with them it was 6X, which
    no configuration that fills a chip in its window can hold beside a
    float32 reference. The second case shows that the count sees it: one
    kept copy of the state makes four."""
    from benchmark.families import transformer

    seen = {}
    reference = transformer.reference_loss_and_grads

    def counting(config, state, sample):
        out = reference(config, state, sample)
        jax.block_until_ready(out)
        seen["x"] = sum(a.nbytes for a in jax.tree.leaves(state))
        seen["alive"] = sum(a.nbytes for a in jax.live_arrays())
        return out

    monkeypatch.setattr(transformer, "reference_loss_and_grads", counting)
    cell, mesh = _tiny_cell()
    record = harness.measure(cell, mesh, world(), {"bf16_flops": 197e12},
                             seed=3, seconds=0.1, trace_dir=None, events=events,
                             t_command=time.time(), marks=child_marks())
    assert record["correct"], record["checks"]
    x = seen["x"]
    assert x > 300_000  # the tiny model's parameters, float32
    small = x // 10  # sample, losses, one placed batch: some kilobytes
    assert copies * x <= seen["alive"] <= copies * x + small, seen["alive"] / x


def test_measure_takes_a_mesh_of_two_axes(events):
    """{"dp": 2, "tp": 2}: the batch is split over `BATCH_AXIS` and the state
    spans all four devices; nothing in `measure` names the mesh's axes."""
    cell, mesh = _tiny_cell(mesh={"dp": 2, "tp": 2})
    record = harness.measure(cell, mesh, OneProcess(), {"bf16_flops": 197e12},
                             seed=3, seconds=0.1, trace_dir=None, events=events,
                             t_command=time.time(), marks=child_marks())
    assert record["correct"], (record["checks"], record["reference"])
    assert record["chips"] == 4 and record["samples_per_step"] == 8
    assert record["scopes"] is None  # the scope table is the traced run's


def test_the_traced_run_measures_the_window_and_then_profiles(events, tmp_path):
    """`--trace 1`: the same untraced window first, for the stalls' share
    and the input's wait, then TRACE_STEPS steps under the profiler, which
    the trace's reduction is given."""
    from benchmark import trace_reduce

    m = mf.load()
    cell, mesh = _tiny_cell()

    class Asked(OneProcess):
        def agree_steps(self, n):
            self.asked = n
            return n

    world = Asked()
    record = harness.measure(cell, mesh, world, {"bf16_flops": 197e12}, seed=3,
                             seconds=0.3, trace_dir=str(tmp_path), events=events,
                             t_command=time.time(), marks=child_marks())
    window, traced = record["window"], record["traced_window"]
    n = len(window["t_done"])
    assert n == harness.steps_for(0.3, {"t_done": np.cumsum(
        [0.0] + record["probe_intervals_s"])})
    assert len(traced["t_done"]) == harness.TRACE_STEPS
    # the other ranks of a world run rank 0's whole count as one window
    assert world.asked == record["attempted"] == n + harness.TRACE_STEPS
    assert record["traced"] and record["correct"], record["checks"]
    assert window["compiles"] == 0
    # the windows follow each other in the cycled pool and in time
    assert window["t_done"][-1] <= traced["t_start"]
    assert trace_reduce.find_xplane(str(tmp_path)).endswith(".xplane.pb")
    names = [x["name"] for x in m["per_layer"]]
    found = end_to_end.layer_values(record, None, names)
    assert found["stall_share_pct"] >= 0.0 and found["input_wait_ms_p50"] > 0.0
    json.dumps(record)
    # the traced record names each instruction's scope, for the per-layer
    # metrics that split the step by it: every scope of the program's
    # vocabulary is on some instruction, and each phase is there
    names = [trace_reduce.scope_names(trace_reduce.scope_parts(op))
             for op in record["scopes"].values()]
    for scope in ("embed", "attn", "attn_core", "ffn", "head_loss", "optimizer",
                  "grad_allreduce", "optimizer_update"):
        assert any(scope in n for n in names), scope
    phases = {trace_reduce.phase_of(trace_reduce.scope_parts(op))
              for op in record["scopes"].values()}
    assert phases == {"forward", "backward", "optimizer", "unattributed"}
