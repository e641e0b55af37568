"""Model: the part of `state_init_s` that is Python's: tracing the init
program (and every eager op of `family.init`) to a jaxpr and lowering it to
StableHLO, the program's `device_plane.compile.trace` and `.lower` spans on
the reporting rank between the marks `t_world` and `t_init`, merged. What a
compile request took inside them is `state_init_load_or_compile_s`' and is
taken out, so the two are disjoint. 0 where the ring holds no such span
there. Program span, seconds."""

from benchmark.layer_metrics.import_s import ring
from benchmark.layer_metrics.state_init_load_or_compile_s import BACKEND
from benchmark.trace_reduce import clip, length, subtract


def read(record, trace):
    if not record["traced"]:
        return None
    marks, spans = record["marks"], record["spans"]
    mine = (ring(spans, "device_plane.compile.trace")
            + ring(spans, "device_plane.compile.lower"))
    return float(length(clip(subtract(mine, ring(spans, BACKEND)),
                             marks["t_world"], marks["t_init"])))
