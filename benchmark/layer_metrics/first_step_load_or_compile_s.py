"""Train step: the part of the first step spent in compile requests, each
an XLA compile or, warm, the load of the executable from the persistent
cache (its retrieval time is in the record beside it,
`/jax/compilation_cache/cache_retrieval_time_sec`): JAX's duration event
`/jax/core/compile/backend_compile_duration` raised between the marks
`t_first_0` and `t_first_1`, the time spans merged. Seconds."""

from benchmark.trace_reduce import length

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def read(record, trace):
    events = record["first_step_events"]
    if COMPILE_EVENT not in events:
        return None
    return float(length(events[COMPILE_EVENT]["spans"]))
