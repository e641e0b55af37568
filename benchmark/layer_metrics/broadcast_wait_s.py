"""Launcher: the seconds the reporting rank spent under the program's span
`broadcast.one_to_all` (`initializer.broadcast_variables`: rank 0's state to
every worker; the span ends when the values are here, so it holds the wait
for the workers that arrive last) between the marks `t_world` and
`t_placed`. `agree_steps` raises the same span later, for four bytes: it is
outside the interval and not counted. Nothing to read in a one-process
world. Program span, seconds."""

from benchmark.end_to_end import span_seconds


def read(record, trace):
    marks = record["marks"]
    return span_seconds(record["spans"], "broadcast.one_to_all",
                        marks["t_world"], marks["t_placed"])
