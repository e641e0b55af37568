"""Kernels: of `first_step_kernel_trace_s`, the seconds that went into the
branch the record's device never runs: the spans whose `branch` is
`interpret` where the device is a TPU, and `tpu` where it is not.
`lax.platform_dependent` stages every branch, so a kernel under it
(`gated_delta._on_platform`, `blocks._turned`) is traced once for Mosaic and
once to be interpreted: what that costs a first step, the number that
choosing the branch at trace time would take (ROADMAP S10(3)). 0 where the
ring holds no such span. Program span, seconds."""

from benchmark.layer_metrics import first_step_kernel_trace_s


def read(record, trace):
    unrun = "interpret" if record["device"]["platform"] == "tpu" else "tpu"
    return first_step_kernel_trace_s.read(
        record, trace, lambda args: args.get("branch") == unrun)
