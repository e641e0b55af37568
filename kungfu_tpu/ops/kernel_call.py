"""The one door to Pallas: `kernel_call` is `pl.pallas_call`, and what it
returns traces the kernel's body inside a span of the ring,
`device_plane.compile.kernel`, with `kernel` = the name Mosaic gets (the
call's `name=`, else the kernel function's own, as Pallas has it: the name
the device trace shows the op under, so a kernel's trace-time cost and its
run-time cost join on one name) and `branch` = `"tpu"` or `"interpret"`.

JAX raises the trace of every kernel body under one `fun_name`, `wrapped`
(`pallas_call.py`'s inner function), and under `lax.platform_dependent` every
kernel is traced once for Mosaic and once to be interpreted, of which a
program runs one: the span says whose trace it was and for which branch
(`first_step_kernel_trace_s`, `first_step_unrun_branch_trace_s`; PERF.md
section 3). However a kernel is chosen (`gated_delta._on_platform`,
`grouped_matmul._forward`, `blocks._turned`, a caller's own `interpret`) it is
built here, so the count of a kernel's spans is the count of its traces: the
span is in the traced body, and a call that a `jax.jit`'s cache serves
enters none. Host code at trace time alone; the program is the same text.
`tests/test_kernel_call.py` walks `kungfu_tpu/` for a `pallas_call(` that did
not come through here."""

from __future__ import annotations

import functools

from kungfu_tpu.telemetry import tracing

SPAN = "device_plane.compile.kernel"


def _name_of(kernel) -> str:
    """The kernel function's own name, a `functools.partial`'s function's."""
    while isinstance(kernel, functools.partial):
        kernel = kernel.func
    return getattr(kernel, "__name__", "kernel")


def kernel_call(kernel, *, name: str = None, interpret=False, **call):
    """`pl.pallas_call(kernel, name=name, interpret=interpret, **call)`,
    each call of it inside one `device_plane.compile.kernel` span."""
    from jax.experimental import pallas as pl

    built = pl.pallas_call(kernel, name=name, interpret=interpret, **call)
    args = {"kernel": name or _name_of(kernel),
            "branch": "interpret" if interpret else "tpu"}

    def traced(*operands):
        with tracing.span(SPAN, **args):
            return built(*operands)

    return traced
