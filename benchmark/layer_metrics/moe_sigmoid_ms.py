"""Model: own time a step of the device ops under the scope `moe` of the
GLM-4.7-Flash cell (five expert layers, the multi-token-prediction module's
among them: sigmoid scores and a selection bias, 8 of 64 experts held, 4 a
token, a shared expert with no gate): norm, router, dispatch, the held
experts, the shared expert, combine, forward and backward; the
grouped-matmul kernels that XLA makes of `lax.ragged_dot` carry no scope and
are claimed by their name (`families.olmoe.EXPERT_KERNELS`). `moe_ms` reads
the same, and its list is the OLMoE cell's alone
(`tests/benchmark/test_bench_olmoe.py`). Device trace over the step
program's scope table, milliseconds."""

from benchmark.families import olmoe


def read(record, trace):
    return olmoe.scope_own_ms(record, trace, {"moe"}, olmoe.EXPERT_KERNELS)
