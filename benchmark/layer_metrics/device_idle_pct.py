"""Device: 1 - busy union / traced window (first traced step's start to the
last one's end), averaged over the traced chips. Device trace, percent."""

from benchmark.trace_reduce import device_busy_and_window_s


def read(record, trace):
    if not trace or not trace["chips"]:
        return None
    busy_s, window_s = device_busy_and_window_s(trace)
    return 100.0 * (1.0 - busy_s / window_s)
