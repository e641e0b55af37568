"""Kernels: own time a step of the device ops under the scope `ssm_core`, the
state-space scan of the Mamba-2 layers (four in the Nemotron-3-Nano cell, 64
heads of 64 on 8 groups' B and C of 128, 8,192 positions in chunks of 128):
the forward kernel, the backward kernel and the sum of a group's dq and dk
after it (`kungfu_tpu/ops/ssm_scan.py`). Device trace over the step program's
scope table, milliseconds."""

from benchmark.families import nemotron_h


def read(record, trace):
    return nemotron_h.core_ms(record, trace, nemotron_h.MAMBA)
