"""A set-up drawn by hand, for the tests that draw a record: the marks, the
program's spans and JAX's duration events of the first step, as
`harness.measure` and `child.py` put them into a run's record."""

import copy
import time

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# As a one-process cell's child records it: the backend starts in 8 of the
# 17.5 s from the command to the window, and the ring holds no span.
SETUP = {
    "t_command": 100.0, "t_world": 112.0, "t_window": 117.5,
    "first_step_s": 0.7,
    "marks": {"t_command": 100.0, "t_child": 100.5, "t_joined": 100.75,
              "t_backend_0": 102.0, "t_backend_1": 110.0, "t_world": 112.0,
              "t_init": 113.5, "t_placed": 114.0, "t_pool": 114.25,
              "t_first_0": 114.25, "t_first_1": 114.95, "t_window": 117.5},
    "spans": [],
    # the step's trace with the small compile of an eager op inside it, the
    # lowering, the load of the step's executable; a jitted function traced
    # inside the step's own trace is in the sum and not in the merged spans
    "first_step_events": {
        TRACE_EVENT: {"count": 2, "sum_s": 0.3125, "spans": [[114.25, 114.5]]},
        LOWER_EVENT: {"count": 1, "sum_s": 0.125, "spans": [[114.5, 114.625]]},
        COMPILE_EVENT: {"count": 2, "sum_s": 0.28125,
                        "spans": [[114.375, 114.40625], [114.625, 114.875]]},
        "/jax/compilation_cache/cache_retrieval_time_sec": {
            "count": 1, "sum_s": 0.2, "spans": []},
    },
}

# A kfrun worker's ring: the backend's start is the program's span, 9 s of
# it, and the child's own `jax.devices()` finds the world up; rank 0's state
# goes to the workers and onto the mesh inside the placement; `agree_steps`
# broadcasts four bytes later.
KFRUN_SPANS = [
    ["worker.startup", 100.75, 101.0, 0, {}],
    ["device_plane.distributed_initialize", 101.0, 101.25, 0, {}],
    ["device_plane.backend_start", 101.25, 110.25, 0, {}],
    ["broadcast.one_to_all", 113.5, 113.875, 0, {"leaves": 9, "bytes": 435151872}],
    ["broadcast.replicate", 113.875, 114.0, 0, {}],
    ["broadcast.one_to_all", 115.5, 115.5625, 0, {"leaves": 1, "bytes": 4}],
]


def drawn_setup(kfrun: bool = False) -> dict:
    """The keys of a record that hold its set-up; under `kfrun` with the
    worker's spans and the child's marks around a backend that is up."""
    setup = copy.deepcopy(SETUP)
    if kfrun:
        setup["spans"] = copy.deepcopy(KFRUN_SPANS)
        setup["marks"].update(t_joined=110.5, t_backend_0=111.0,
                              t_backend_1=111.0078125)
    return setup


def child_marks() -> dict:
    """What `child.py` hands `measure`: its own marks from before the call,
    here with a backend that was up already."""
    return dict.fromkeys(("t_child", "t_joined", "t_backend_0", "t_backend_1"),
                         time.time())
