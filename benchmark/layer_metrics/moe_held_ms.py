"""Model: own time a step of the device ops under the scope `moe` of the
Qwen3-Next cell (four layers: 32 of 512 experts held, 10 a token, a shared
expert behind a sigmoid gate): norm, router, dispatch, the held experts,
the gated shared expert, combine, forward and backward; the grouped-matmul
kernels that XLA makes of `lax.ragged_dot` carry no scope and are claimed
by their name (`families.olmoe.EXPERT_KERNELS`). Device trace over the step
program's scope table, milliseconds."""

from benchmark.families import qwen3_next


def read(record, trace):
    return qwen3_next.moe_ms(record, trace)
