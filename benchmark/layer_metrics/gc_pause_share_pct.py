"""Train step: the share of the measured window that Python's collector
took: the program's `worker.gc` spans (a collection of 1 ms or more, or of
the oldest generation) inside the window, summed, over the window's length.
To be read beside `stall_share_pct`: a stall that is the collector's shows
here too, one that is the machine's does not. The window on the marks' clock
is `t_window` to `t_window + (window.t_done[-1] - window.t_start)`; its first
step, which `stall_share_pct` leaves out, is in it. 0 where the ring holds
no such span there. Program span, %."""

from benchmark.layer_metrics.import_s import ring
from benchmark.trace_reduce import clip


def pauses_and_window_s(record):
    """The seconds of each `worker.gc` span inside the window and the
    window's own; None where there is nothing to read them from."""
    done = record["window"].get("t_done", ())
    if not record["traced"] or len(done) < 2:
        return None
    mine, start = ring(record["spans"], "worker.gc"), record["marks"]["t_window"]
    window_s = done[-1] - record["window"].get("t_start", done[0])
    return [b - a for a, b in clip(mine, start, start + window_s)], window_s


def read(record, trace):
    found = pauses_and_window_s(record)
    if found is None:
        return None
    pauses, window_s = found
    return 100.0 * sum(pauses) / window_s
