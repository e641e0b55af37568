"""Model: the part of `state_init_s` spent in compile requests, each an XLA
compile or, warm, the load of the executable from the persistent cache: the
program's `device_plane.compile.backend` spans on the reporting rank between
the marks `t_world` and `t_init`, merged. `state_init_s` less this and
`state_init_trace_lower_s` is the init program's first run and the host's
own. 0 where the ring holds no such span there. Program span, seconds."""

from benchmark.layer_metrics.import_s import ring
from benchmark.trace_reduce import clip, length

BACKEND = "device_plane.compile.backend"


def read(record, trace):
    if not record["traced"]:
        return None
    marks = record["marks"]
    return float(length(clip(ring(record["spans"], BACKEND),
                             marks["t_world"], marks["t_init"])))
