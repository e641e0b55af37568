"""Kernels: own time a step of the device ops under the scope `ssm_core` in the
Granite 4.0-H cell, the state-space scan of its nine Mamba-2 layers on packed
rows (64 heads of 64 on one group's B and C of 128, 8,192 positions, about
eight documents a row): the forward kernel, the backward kernel, what makes
their marks of the documents' numbers and the sum of the sixteen blocks of
heads' dq and dk after it (`kungfu_tpu/ops/ssm_scan.py`). Device trace over
the step program's scope table, milliseconds."""

from benchmark.families import granite_hybrid


def read(record, trace):
    return granite_hybrid.core_ms(record, trace, granite_hybrid.MAMBA)
