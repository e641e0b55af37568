"""Launcher: the part of `broadcast_replicate_s` spent in compile requests
and their Python side: the program's `device_plane.compile.*` spans, merged,
inside the reporting rank's `broadcast.replicate` spans between the marks
`t_world` and `t_placed`. Most of `broadcast_replicate_s`: the `device_put`
onto a mesh that spans processes is about programs; next to none of it: it
is about bytes through the host. 0 where the ring holds no such span there.
Program span, seconds."""

from benchmark.layer_metrics.import_s import ring
from benchmark.trace_reduce import clip, length


def read(record, trace):
    if not record["traced"]:
        return None
    marks, spans = record["marks"], record["spans"]
    compiles = ring(spans, "device_plane.compile.")
    replicates = clip(ring(spans, "broadcast.replicate"),
                      marks["t_world"], marks["t_placed"])
    return float(sum(length(clip(compiles, a, b)) for a, b in replicates))
