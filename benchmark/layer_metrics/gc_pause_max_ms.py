"""Train step: the longest pause of Python's collector inside the measured
window: the longest of the program's `worker.gc` spans there
(`gc_pause_share_pct` has the window). One step is late by this much, so it
is in `step_ms_p95` where such pauses hit one step in twenty. 0 where the
ring holds no such span there. Program span, milliseconds."""

from benchmark.layer_metrics.gc_pause_share_pct import pauses_and_window_s


def read(record, trace):
    found = pauses_and_window_s(record)
    return None if found is None else 1e3 * max(found[0], default=0.0)
