"""Train step: the seconds of the first step that went into lowering the
step's jaxpr to StableHLO (a Mosaic kernel's own lowering inside it): the
program's `device_plane.compile.lower` spans on the reporting rank between
the marks `t_first_0` and `t_first_1`, merged, less the compile requests
inside them (`.backend`). `first_step_trace_lower_s` less this is the
tracing alone. 0 where the ring holds no such span there. Program span,
seconds."""

from benchmark.layer_metrics.import_s import ring
from benchmark.layer_metrics.state_init_load_or_compile_s import BACKEND
from benchmark.trace_reduce import clip, length, subtract


def read(record, trace):
    if not record["traced"]:
        return None
    marks, spans = record["marks"], record["spans"]
    mine = subtract(ring(spans, "device_plane.compile.lower"), ring(spans, BACKEND))
    return float(length(clip(mine, marks["t_first_0"], marks["t_first_1"])))
