"""Model: the union of device-op intervals within one run of the step
program, median over the traced steps. Device trace, milliseconds."""

from benchmark.trace_reduce import busy, chip, median, per_step


def read(record, trace):
    if not trace or not trace["chips"]:
        return None
    c = chip(trace)
    return median(per_step(c, busy(c))) / 1e6
