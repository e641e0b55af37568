"""From a run's record to the end-to-end metrics and the result line. No
jax: the parent of a run computes these from what the child wrote."""

from __future__ import annotations

import importlib
import json
import os

from benchmark import manifest as mf
from benchmark import trace_reduce
from benchmark.trace_reduce import percentile


def step_intervals(record: dict) -> list:
    """Seconds between successive step completions in the window."""
    done = record["window"]["t_done"]
    return [b - a for a, b in zip(done, done[1:])]


SEGMENT_STEPS = 8  # steps to a segment; the window's rate is its segments' median


def segment_rates(record: dict) -> list:
    """Samples a second a chip in each segment of the window: SEGMENT_STEPS
    successive steps from one completion to another, or fewer where the
    window would not hold that many segments of them."""
    done = record["window"]["t_done"]
    s = max(1, min(SEGMENT_STEPS, (len(done) - 1) // SEGMENT_STEPS))
    samples = s * record["samples_per_step"] / record["chips"]
    return [samples / (done[i + s] - done[i])
            for i in range(0, len(done) - s, s)]


def span_seconds(spans, name: str, start: float = float("-inf"),
                 end: float = float("inf")) -> float:
    """The seconds under the spans of one name, `[name, start, end, ...]`,
    that lie between two times; None where there is no such span."""
    found = [s[2] - s[1] for s in spans
             if s[0] == name and start <= s[1] and s[2] <= end]
    return sum(found) if found else None


def backend_start_s(record: dict) -> float:
    """The seconds the machine took to start the accelerator's backend, the
    first `jax.devices()` of the world, on the reporting rank: the span
    `device_plane.backend_start` where the program's ring has one (under
    kfrun `initialize_device_plane()` makes the call, and the child's own is
    near nothing by then), else the child's marks around its own call (the
    one-process cells). Four launches of one tree read 13.9, 18.5, 21.5 and
    29.2 s here (PERF.md section 5): it is the machine's, no program reaches
    it, and it is what `setup_s` leaves out."""
    marks = record["marks"]
    in_ring = span_seconds(record["spans"], "device_plane.backend_start",
                           end=marks["t_world"])
    if in_ring is not None:
        return in_ring
    return marks["t_backend_1"] - marks["t_backend_0"]


def command_to_window_s(record: dict) -> float:
    """Start of the command to start of the window, the backend's start
    included: what `setup_s` read until PR 37."""
    return record["t_window"] - record["t_command"]


def values(record: dict) -> dict:
    """Every end-to-end metric of one run. The rate is the median over the
    window's segments of the samples a segment completed over its wall
    seconds over the chips. The rate by the wall clock of the whole window
    cannot be held to a bound: a pause of the shared host hits some runs
    and not others, so one set of six runs spreads by 0.001 % and the next
    by 2 % (PERF.md, the refusal of PR 23's first manifest). The median
    holds while under half of the segments are hit. The 95th percentile of
    the step moves before that, at one late step in twenty, and what the
    rarer stalls take is `stall_share`, a per-layer metric with no bound.
    Whatever slows more than half of the segments, a stall at every
    sixteenth step or oftener among them, is in the rate at its whole
    cost. `mfu_pct` is the rate in required operations over the chip's
    peak. `setup_s` is the command's start to the window's less the
    backend's start (since PR 38): process start, imports, launcher, world
    join, state, placement, pool, first step, warm-up and probe are the
    program's or the benchmark's and stay in; `result_line` reports what
    was left out, and the whole, in `device`, held to no bound."""
    steps = step_intervals(record)
    rate = percentile(segment_rates(record), 50)
    return {
        "samples_per_s_per_chip": rate,
        "step_ms_p50": percentile(steps, 50) * 1e3,
        "step_ms_p95": percentile(steps, 95) * 1e3,
        "mfu_pct": 100.0 * rate * record["flops_per_sample"] / record["peak_flops"],
        "setup_s": command_to_window_s(record) - backend_start_s(record),
    }


def stall_share(record: dict) -> float:
    """The share by which the window's rate by the wall clock, first
    completion to last, falls short of the rate reported: what the late
    steps took and the median over the segments does not hold. 0 in a run
    that no pause hits, to the segments' own scatter."""
    done = record["window"]["t_done"]
    wall_rate = ((len(done) - 1) * record["samples_per_step"] / record["chips"]
                 / (done[-1] - done[0]))
    return max(0.0, 1.0 - wall_rate / percentile(segment_rates(record), 50))


def layer_values(record: dict, trace, names) -> dict:
    """Each listed per-layer metric from its own reader,
    `benchmark/layer_metrics/<name>.py`; a reader that finds nothing to
    read returns None and the metric is left out."""
    out = {}
    for name in names:
        reader = importlib.import_module("benchmark.layer_metrics." + name)
        value = reader.read(record, trace)
        if value is not None:
            out[name] = value
    return out


def merge_ranks(record: dict, out: str) -> dict:
    """The record with every rank's marks and spans as `record["ranks"]`,
    in rank order: each rank of the cell's world wrote its own to
    `<out>/rank_<n>.json` before the closing barrier, and all share one
    host and so one wall clock."""
    ranks = []
    for name in os.listdir(out):
        if name.startswith("rank_") and name.endswith(".json"):
            with open(os.path.join(out, name)) as f:
                ranks.append(json.load(f))
    return {**record, "ranks": sorted(ranks, key=lambda r: r["rank"])}


def compared(record: dict) -> dict:
    """What `correct` was decided on, {name: [number, limit]}: the
    reference's two errors under the family's tolerances, the last pass
    over the pool over the first (`loss_fell`: under 1), the counts that
    must be 0, and then every check of the record as 1 or 0 of 1."""
    reference = record.get("reference") or {}
    out = {f"{name}_error": [reference[f"{name}_error"], reference[f"{name}_rtol"]]
           for name in ("loss", "grad") if f"{name}_error" in reference}
    if "loss_passes" in record:
        first, last = record["loss_passes"]
        out["last_pass_over_first"] = [last / first, 1.0]
    out["compiles_in_window"] = [record["window"]["compiles"], 0]
    out["steps_failed"] = [record["failed"], 0]
    if "precision_faults" in reference:
        out["precision_faults"] = [len(reference["precision_faults"]), 0]
    for name, ok in record.get("checks", {}).items():
        out[name] = [int(bool(ok)), 1]
    return out


def result_line(record: dict, trace, manifest: dict) -> dict:
    """The run's last line. Raises for a record that is not from a TPU: no
    CPU number is written under a device metric's name."""
    device = dict(record["device"])
    if device["platform"] != "tpu":
        raise RuntimeError(f"the record is from a {device['platform']} run; "
                           "the benchmark reports chip runs only")
    workload, traced = record["workload"], record["traced"]
    kind = "per_layer" if traced else "end_to_end"
    units = {m["name"]: m["unit"]
             for m in mf.metrics_of(manifest, kind, workload)}
    found = (layer_values(record, trace, units) if traced
             else {k: v for k, v in values(record).items() if k in units})
    # memory_stats() does not see a program's temporaries (PERF.md): the
    # peak is the larger of it and the step program's own account
    device["memory_peak_bytes"] = max(record["memory_stats_peak_bytes"],
                                      record["program_memory"]["total_bytes"])
    device["backend_start_s"] = backend_start_s(record)
    device["command_to_window_s"] = command_to_window_s(record)
    line = {
        "correct": bool(record["correct"]),
        "attempted": int(record["attempted"]),
        "failed": int(record["failed"]),
        "metrics": {k: {"value": v, "unit": units[k]} for k, v in found.items()},
        "device": device,
    }
    if traced:
        if not trace or not trace["chips"]:
            raise RuntimeError("the traced run found no device operation "
                               f"in its trace: {trace and trace['lines']}")
        busy_s, window_s = trace_reduce.device_busy_and_window_s(trace)
        device["busy_s"], device["window_s"] = busy_s, window_s
        line["breakdown"] = trace_reduce.breakdown(trace)
    line["compared"] = compared(record)
    faults = mf.check_result_line(line, manifest, workload, traced)
    if faults:
        raise RuntimeError("; ".join(faults))
    return line
