"""Train step: the part of the first step that is Python's: tracing the
step function to a jaxpr and lowering it to StableHLO, JAX's duration
events `/jax/core/compile/jaxpr_trace_duration` and
`jaxpr_to_mlir_module_duration` raised between the marks `t_first_0` and
`t_first_1`. Their time spans merged, not their durations summed: a traced
function raises an event for every jitted one it calls, inside its own. What
a compile request took inside them (an eager op while tracing) is
`first_step_load_or_compile_s`' and is taken out, so the two are disjoint
and `first_step_s` less both is the first run itself and the host's own.
Seconds."""

from benchmark.trace_reduce import length, subtract

MINE = ("/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def read(record, trace):
    events = record["first_step_events"]
    mine = [s for e in MINE if e in events
            for s in events[e]["spans"]]
    if not mine:
        return None
    compiles = events.get(COMPILE_EVENT, {"spans": []})["spans"]
    return float(length(subtract(mine, compiles)))
