"""From a run's record to the end-to-end metrics and the result line. No
jax: the parent of a run computes these from what the child wrote."""

from __future__ import annotations

import importlib

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
    peak."""
    steps = step_intervals(record)
    rate = percentile(segment_rates(record), 50)
    return {
        "samples_per_s_per_chip": rate,
        "step_ms_p50": percentile(steps, 50) * 1e3,
        "step_ms_p95": percentile(steps, 95) * 1e3,
        "mfu_pct": 100.0 * rate * record["flops_per_sample"] / record["peak_flops"],
        "setup_s": record["t_window"] - record["t_command"],
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
    faults = mf.check_result_line(line, manifest, workload, traced)
    if faults:
        raise RuntimeError("; ".join(faults))
    return line
