#!/usr/bin/env python3
"""The benchmark's command: one cell, once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmark/run.py --check

This process never imports jax: a chip belongs to one process at a time. It
starts the cell's child (`child.py`), or a `kfrun` tree of them, in a session
of its own with a time limit, echoes what they print, reads the record the
reporting rank wrote under `benchmark/out/`, kills whatever is left of the
session, and prints the result as the last line: one JSON object with
`correct`, `attempted`, `failed`, `metrics`, `device`, traced `breakdown`,
and last `compared`, each number `correct` was decided on beside its limit
(also the last lines on standard error). Without a TPU the child fails,
and so does this, with no result line. `--check` checks BENCHMARK.json and
the files it names, with no chip and no jax.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time

T_COMMAND = time.time()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)
sys.path.insert(0, REPO)

from benchmark import end_to_end, manifest  # noqa: E402  (no jax)

TIME_LIMIT_S = 1100  # a first run compiles; the contract allows 1200


def child_argv(cell: dict, args, out: str) -> list:
    argv = [sys.executable, os.path.join(BENCH_DIR, "child.py"),
            "--workload", cell["name"], "--seed", str(args.seed),
            "--seconds", str(args.seconds), "--trace", str(args.trace),
            "--t-command", repr(T_COMMAND), "--out", out]
    launcher = manifest.plugin("launchers", cell["traffic"]["launcher"])
    return launcher.argv(cell["traffic"], argv)


def run_tree(argv: list, limit: float) -> int:
    """Run a child, or a kfrun tree, in a session of its own; echo its
    output; leave nothing of it behind. Returns its exit code (124 when the
    time limit killed it)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(argv, cwd=REPO, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True,
                            start_new_session=True)

    def kill_tree():
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    timer = threading.Timer(limit, kill_tree)
    timer.start()
    try:
        for line in proc.stdout:
            sys.stdout.write(line)
        code = proc.wait()
    finally:
        timed_out = not timer.is_alive()
        timer.cancel()
        kill_tree()  # stragglers of the session, if any
    return 124 if timed_out else code


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    m = manifest.load()
    faults = manifest.check(m)
    for fault in faults:
        print(f"BENCHMARK.json: {fault}", file=sys.stderr)
    if args.check or faults:
        if not faults:
            print(f"BENCHMARK.json: sound ({len(m['workloads'])} cells, "
                  f"{len(m['end_to_end'])} end-to-end and "
                  f"{len(m['per_layer'])} per-layer metrics)")
        return 1 if faults else 0
    if args.workload is None:
        ap.error("--workload or --check is required")
    if args.seconds is None:
        args.seconds = float(m["run_seconds"])

    cell = manifest.cell(m, args.workload)
    out = os.path.join(BENCH_DIR, "out", cell["name"])
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    code = run_tree(child_argv(cell, args, out), TIME_LIMIT_S)
    if code != 0:
        print(f"benchmark: the cell's process exited with code {code}",
              file=sys.stderr)
        return code
    with open(os.path.join(out, "record.json")) as f:
        record = end_to_end.merge_ranks(json.load(f), out)
    with open(os.path.join(out, "record.json"), "w") as f:
        json.dump(record, f)
    trace = None
    if args.trace:
        with open(os.path.join(out, "trace.json")) as f:
            trace = json.load(f)
    line = end_to_end.result_line(record, trace, m)
    summary = {k: record[k] for k in ("checks", "reference", "cache",
                                      "first_step_s", "program_memory",
                                      "memory_stats_peak_bytes")}
    print("benchmark: " + json.dumps(summary))
    print("benchmark: end to end " + json.dumps(
        {**end_to_end.values(record),
         "stall_share_pct": 100 * end_to_end.stall_share(record)}))
    print(json.dumps(line), flush=True)
    for name, (value, limit) in line["compared"].items():
        print(f"benchmark: compared {name} {value} limit {limit}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
