"""Train step: `/jax/core/compile/backend_compile_duration` events raised
inside the window. Must be 0; `correct` is false otherwise."""


def read(record, trace):
    return float(record["window"]["compiles"])
