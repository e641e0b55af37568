"""Collective: the part of a step's all-reduce time during which no other
operation runs on that chip. Device trace, milliseconds."""

from benchmark.trace_reduce import (all_reduce_segments, chip, median,
                                    per_step, subtract)


def read(record, trace):
    if not trace or not trace["chips"]:
        return None
    c = chip(trace)
    mine, others = all_reduce_segments(c)
    return median(per_step(c, subtract(mine, others))) / 1e6
