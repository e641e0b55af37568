"""Kernels: own time a step of the device ops under the scope `kda_core`,
the delta rule of the Kimi-Linear cell's four KDA layers (32 heads of 128,
16,384 positions in chunks of 64, the decay a number a key feature): the
running sums of the log decays, the pairs' kernel (A and P in sub-blocks of
16, the decays taken feature by feature), the triangular inverse, the scan
over the chunks that carries the state, and in the backward pass the same
scan in reverse and the pairs' own backward kernel
(`kungfu_tpu/ops/kda.py`). Device trace over the step program's scope table,
milliseconds."""

from benchmark.families import kimi_linear


def read(record, trace):
    return kimi_linear.core_ms(record, trace, kimi_linear.KDA)
