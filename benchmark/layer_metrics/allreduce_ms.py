"""Collective: summed time of the all-reduce operations per step on the
traced chip (their own time; 0 where XLA emitted none, as for a one-device
axis). Device trace, milliseconds."""

from benchmark.trace_reduce import all_reduce_segments, chip, median, per_step


def read(record, trace):
    if not trace or not trace["chips"]:
        return None
    c = chip(trace)
    mine, _ = all_reduce_segments(c)
    return median(per_step(c, mine)) / 1e6
