"""The process that holds the chip: one cell, once. Started by `run.py`
(directly, or as every worker of a `kfrun` tree); writes the run's record,
and in a traced run the reduced trace, where `run.py` told it to. Only the
reporting rank (kfrun's rank 0) traces and writes those; every rank writes
its own marks and spans of the set-up beside them (`rank_<n>.json`), which
`run.py` puts into the record as `ranks`."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import manifest  # noqa: E402  (no jax)


def main() -> int:
    marks = {"t_child": time.time()}
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, required=True)
    ap.add_argument("--t-command", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    cell = manifest.cell(manifest.load(), args.workload)
    # before anything touches the JAX backend: the launcher's side of the
    # child joins the device world, if there is one, and places the cache
    world = manifest.plugin("launchers", cell["traffic"]["launcher"]).join()
    marks["t_joined"] = time.time()

    import jax

    from benchmark import harness

    from kungfu_tpu.parallel import make_mesh

    # every program is worth keeping: each run is a new process
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # the backend's start, where no launcher has made it already: the
    # imports above are left outside
    marks["t_backend_0"] = time.time()
    devices = jax.devices()
    marks["t_backend_1"] = time.time()
    peaks = harness.require_chips(devices, cell["chips"])
    events = harness.EventCounter()
    mesh = make_mesh(cell["traffic"]["mesh"], devices=devices[:cell["chips"]])

    reporter = world.rank == 0
    trace_dir = os.path.join(args.out, "trace") if args.trace and reporter else None
    record = harness.measure(cell, mesh, world, peaks, args.seed, args.seconds,
                             trace_dir, events, args.t_command, marks)
    with open(os.path.join(args.out, f"rank_{world.rank}.json"), "w") as f:
        json.dump({k: record[k] for k in ("rank", "marks", "spans")}, f)
    if reporter:
        if trace_dir:
            from benchmark import trace_reduce

            reduced = trace_reduce.read_xplane(
                trace_reduce.find_xplane(trace_dir),
                len(record["traced_window"]["t_done"]))
            trace_reduce.place_spans(reduced, record["traced_window"])
            with open(os.path.join(args.out, "trace.json"), "w") as f:
                json.dump(reduced, f)
        with open(os.path.join(args.out, "record.json"), "w") as f:
            json.dump(record, f)
    print(f"[rank {world.rank}] correct={record['correct']} "
          f"checks={record['checks']} reference={record['reference']} "
          f"cache={record['cache']} first_step_s={record['first_step_s']:.3f}",
          flush=True)
    world.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
