"""Kernels: the share of its roofline of the Xing4.0 cell's two mixings, the
branch's input out of the four streams and the streams with the branch's
output mixed in. The least time the chip could take for them, the bytes they
must move over the memory peak
(`families.xing4_0.stream_bytes_per_sample`: the streams read once and
written once on each side of a branch, each way, with the one-stream rows
beside them, 41 rows of 3,584 bfloat16 a position and branch, ten branches,
12.04 GB a step of 4,096 positions, 14.7 ms; a layer run again not counted),
over the own time under `hc_read` and `hc_write`, whatever implements them.
Device trace, %."""

from benchmark.families import xing4_0


def read(record, trace):
    return xing4_0.stream_roofline_pct(record, trace)
