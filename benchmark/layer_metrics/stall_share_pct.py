"""Train step: the share by which the measured window's rate by the wall
clock falls short of `samples_per_s_per_chip`, the median over the window's
segments: what the rare late steps took, a pause of the host among them,
which that median does not hold. The traced run measures the same window as
an untraced one before its profile, and this reads that. Host clock, %."""

from benchmark.end_to_end import stall_share


def read(record, trace):
    if len(record["window"].get("t_done", ())) < 3:
        return None
    return 100.0 * stall_share(record)
