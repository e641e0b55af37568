"""Model: own time a step of the device ops under the scope `moe` of the
Nemotron-3-Nano cell (four expert layers, each a layer of its own behind one
norm: sigmoid scores and a selection bias, 8 of 128 two-matrix relu^2 experts
held, 6 a token, a shared expert of twice their width): norm, router,
dispatch, the held experts, the shared expert, combine, forward and backward;
the grouped-matmul kernels that XLA makes of `lax.ragged_dot` carry no scope
and are claimed by their name (`families.olmoe.EXPERT_KERNELS`). Device trace
over the step program's scope table, milliseconds."""

from benchmark.families import nemotron_h


def read(record, trace):
    return nemotron_h.moe_ms(record, trace)
