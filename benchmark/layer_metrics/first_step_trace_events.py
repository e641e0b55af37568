"""Train step: how much program the first step traces and lowers: the sum of
`events` over the program's `device_plane.compile.trace` and `.lower` spans
that began on the reporting rank between the marks `t_first_0` and
`t_first_1` (`telemetry/device._CompileWatch`: the trace and lowering events
JAX raised inside the outermost one, itself included). A property of the
program alone, the same on any host and in every run: beside the seconds of
`first_step_trace_lower_s` it says whether `setup_s` moved with the program
or with the machine. 0 where no such span there carries the count (PR 72's
spans and before). Program counter, events."""

MINE = ("device_plane.compile.trace", "device_plane.compile.lower")


def read(record, trace):
    if not record["traced"]:
        return None
    marks = record["marks"]
    return float(sum(
        args.get("events", 0) for name, start, _, _, args in record["spans"]
        if name.startswith(MINE) and marks["t_first_0"] <= start < marks["t_first_1"]))
