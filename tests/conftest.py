"""Test config: an 8-device virtual CPU mesh, set before the backend starts.

Mirrors the reference's multi-process-on-localhost test strategy
(SURVEY.md §4): we get multi-chip semantics on one machine via XLA's
host-platform device partitioning instead of kungfu-run subprocesses
(those are exercised separately in the integration tests).

Note: a pytest plugin imports jax before this file runs, so plain env vars
are too late; jax.config.update works until the backend is initialized.
"""

import copy
import gc
import os

import jax
import pytest

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# Tests compile for the CPU and must leave no compile cache in the
# checkout: enable_compile_cache() places the directory, this switches the
# cache itself off — here, and through the environment in every agent the
# tests spawn.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
jax.config.update("jax_enable_compilation_cache", False)


def _build_native_once() -> None:
    """`kungfu_tpu/base/libkfnative.so` is built where it runs
    (`-march=native`) and git ignores it, so a fresh clone has none and
    `tests/test_wire_codec.py` and `tests/test_wire_q.py` fail at import.
    Build it with `native/build.sh` when it is absent: once, under a file
    lock, because the driver runs six pytest-xdist workers and each imports
    this file; the others wait for the lock and find the library there. A
    machine without a compiler keeps the numpy paths and those two files'
    import errors, as before."""
    import fcntl
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    lib = os.path.join(repo, "kungfu_tpu", "base", "libkfnative.so")
    build_dir = os.path.join(repo, "native", "build")  # git-ignored
    os.makedirs(build_dir, exist_ok=True)
    with open(os.path.join(build_dir, ".conftest.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if not os.path.exists(lib):
            subprocess.run(["sh", os.path.join(repo, "native", "build.sh")],
                           stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
                           check=False)


_build_native_once()


@pytest.fixture(autouse=True, scope="module")
def _the_ring_starts_empty():
    """The span ring is the process's, and an xdist worker runs many files
    in one process: a file reads what its own tests recorded, not another
    file's spans nor the worker's own `worker.import`, recorded when the
    first file was collected, minutes before."""
    from kungfu_tpu.telemetry import tracing

    tracing.clear()


@pytest.fixture
def fresh_traces():
    """`jax.jit` and `jax.checkpoint` keep the traces of the functions a
    test patches: none from before it, and none of its own after it."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def runtime_watchers():
    """The compile and collector watchers (ISSUE 39) for one file's tests,
    installed by their own functions (`enable_compile_cache()` would point
    the whole test process at the checkout's cache) and taken out after
    them: the hook and the listeners are the process's too, and the files
    this xdist worker runs next would find `worker.gc` and
    `device_plane.compile.*` spans in their rings and pay for the hook in
    every collection. The totals and the registry's counters stay."""
    from jax import monitoring

    from kungfu_tpu.telemetry import device, tracing

    device.watch_compiles()
    tracing.watch_gc()
    yield
    watch, device._compile_watch = device._compile_watch, None
    monitoring.unregister_scalar_listener(watch.entered)
    monitoring.unregister_event_listener(watch.cache_said)
    monitoring.unregister_event_time_span_listener(watch.left)
    gc.callbacks.remove(tracing._gc_watch)


# tests/benchmark/test_bench_qwen3_next.py (PR 36, a file of the benchmark
# and so no later PR's to edit) pins its cell as the manifest's last. Two
# more pin their readers' lists to their own cell alone, which the eleventh
# cell joined (PR 57: `pk_ffn_ms`; `moe_ms`, `expert_ffn_ms`,
# `moe_dispatch_ms`): they read the manifest as far as the tenth cell.
LAST_WHEN_ADDED = {
    "test_bench_qwen3_next.py::test_the_manifest_with_the_sixth_cell_is_sound":
        "qwen3_next_80b_a3b.ssgd_longseq_1chip",
    "test_bench_granite_hybrid.py::test_the_manifest_with_the_cell_is_sound":
        "granite_4_0_h_micro.ssgd_packed_1chip",
    "test_bench_olmoe.py::test_the_traced_line_holds_the_six_new_metrics":
        "granite_4_0_h_micro.ssgd_packed_1chip",
}


@pytest.fixture(autouse=True)
def _a_cell_pinned_as_the_last_reads_the_manifest_up_to_it(request, monkeypatch):
    """A manifest test that says "my cell is the last" is run against the
    manifest as far as its cell: the configurations and cells appended
    since, the metrics that list those alone and their places in older
    lists are left out, and every assertion of the test is made, soundness
    of what remains included. The whole manifest is checked by the newest
    cell's own test and by `benchmark/run.py --check`."""
    cell = next((c for test, c in LAST_WHEN_ADDED.items()
                 if request.node.nodeid.endswith(test)), None)
    if cell is None:
        return
    from benchmark import manifest as mf

    m = mf.load()
    at = [w["name"] for w in m["workloads"]].index(cell) + 1
    later = {w["name"] for w in m["workloads"][at:]}
    m["workloads"] = m["workloads"][:at]
    used = {w["config"] for w in m["workloads"]}
    m["configs"] = [c for c in m["configs"] if c["name"] in used]
    for kind in ("end_to_end", "per_layer"):
        kept = []
        for metric in m[kind]:
            if "workloads" in metric:
                metric["workloads"] = [w for w in metric["workloads"]
                                       if w not in later]
                if not metric["workloads"]:
                    continue
            kept.append(metric)
        m[kind] = kept
    monkeypatch.setattr(mf, "load", lambda path=mf.MANIFEST: copy.deepcopy(m))
