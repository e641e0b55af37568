"""Launcher: the seconds the reporting rank spent under the program's span
`broadcast.replicate` (`initializer.broadcast_variables`: the `device_put`
of the broadcast's host copy onto the mesh, awaited) between the marks
`t_world` and `t_placed`. Nothing to read in a one-process world. Program
span, seconds."""

from benchmark.end_to_end import span_seconds


def read(record, trace):
    marks = record["marks"]
    return span_seconds(record["spans"], "broadcast.replicate",
                        marks["t_world"], marks["t_placed"])
