"""Launcher: what the reporting rank's `broadcast_wait_s` waits for: over
the other ranks of the world (`record["ranks"]`), the largest sum of the
program's `device_plane.compile.backend` spans whose `cache` is `miss`
between that rank's own marks `t_world` and `t_placed`. Only process 0 of a
world writes the persistent cache, so every other worker compiles its own
single-device programs again in every run. 0 where the record holds no other
rank, or none of them missed. Program span, seconds."""

from benchmark.layer_metrics.import_s import ring
from benchmark.layer_metrics.state_init_load_or_compile_s import BACKEND
from benchmark.trace_reduce import clip


def missed_s(rank: dict) -> float:
    marks = rank["marks"]
    mine = ring(rank["spans"], BACKEND, lambda args: args.get("cache") == "miss")
    return sum(b - a for a, b in clip(mine, marks["t_world"], marks["t_placed"]))


def read(record, trace):
    if not record["traced"]:
        return None
    others = [r for r in record.get("ranks", ()) if r["rank"] != record["rank"]]
    return float(max(map(missed_s, others), default=0.0))
