"""Kernels: the seconds of the first step that went into tracing Pallas
kernel bodies: the program's `device_plane.compile.kernel` spans
(`ops/kernel_call.py`: one a trace of a kernel, with `kernel`, the name
Mosaic gets, and `branch`, `tpu` or `interpret`) on the reporting rank
between the marks `t_first_0` and `t_first_1`, merged. JAX's own events name
every kernel's body `wrapped`; these say whose it was. A part of
`first_step_trace_lower_s`. 0 where the ring holds no such span there (a
program without a kernel, or without the spans: PR 72's and before). Program
span, seconds."""

from benchmark.layer_metrics.import_s import ring
from benchmark.trace_reduce import clip, length

KERNEL = "device_plane.compile.kernel"


def read(record, trace, where=lambda args: True):
    """`where`: which of the spans, by their args."""
    if not record["traced"]:
        return None
    marks = record["marks"]
    return float(length(clip(ring(record["spans"], KERNEL, where),
                             marks["t_first_0"], marks["t_first_1"])))
