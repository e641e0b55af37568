"""The runtime's own work in the program's ring (ISSUE 39): JAX's compile
requests (`telemetry.device.watch_compiles`), the collector's pauses
(`tracing.watch_gc`) and the package's imports (`worker.import`), as spans
of the one ring and counters of the registry. On the CPU; the persistent
cache's answers in subprocesses with a scratch cache directory, and a peer's
own writes to it (ISSUE 40)."""

import gc
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest
from jax import monitoring

from kungfu_tpu.telemetry import device, metrics, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
COMPILE = "device_plane.compile."


@pytest.fixture(scope="module", autouse=True)
def watched(runtime_watchers):
    """Both watchers for this file's tests, and out of the process after
    them (`tests/conftest.py`)."""


@pytest.fixture(autouse=True)
def ring():
    """A ring of this test's spans alone, and none left for the next file
    of the same xdist worker."""
    tracing.clear()
    yield
    tracing.clear()


def _spans(prefix):
    return [e for e in tracing.full_events(prefix) if e.phase == "X"]


def _raise(event, seconds, fun_name, inside=()):
    """One of JAX's `log_elapsed_time` events as it raises them: a scalar on
    entry, whatever happens `inside`, a duration and a time span on exit."""
    start = time.time()
    monitoring.record_scalar(event, start, fun_name=fun_name)
    for each in inside:
        each()
    monitoring.record_event_duration_secs(event, seconds, fun_name=fun_name)
    monitoring.record_event_time_span(event, start, start + seconds,
                                      fun_name=fun_name)


# -- compile requests ---------------------------------------------------------

def test_a_jitted_function_is_one_trace_one_lowering_and_one_request():
    """The cache is off under pytest (`tests/conftest.py`): `cache == "off"`."""
    @jax.jit
    def inner_a(x):
        return x * 2

    @jax.jit
    def inner_b(x):
        return x + 1

    @jax.jit
    def outer(x):
        return inner_a(inner_b(x)) + inner_b(x)

    x = jnp.ones(3)
    tracing.clear()  # the eager `ones` compiled too
    before = device.compile_requests()
    jax.block_until_ready(outer(x))
    traces = [e for e in _spans(COMPILE + "trace") if e.args["fun_name"] == "outer"]
    assert len(traces) == 1 and traces[0].args["nested"] >= 2
    # the jitted functions it called are folded into it, not spans
    assert not [e for e in _spans(COMPILE) if "inner" in e.args["fun_name"]]
    (lower,) = [e for e in _spans(COMPILE + "lower")]
    (backend,) = _spans(COMPILE + "backend")
    assert lower.args["fun_name"] == backend.args["fun_name"] == "jit(outer)"
    assert backend.args["cache"] == "off"
    assert traces[0].start <= lower.start <= backend.start
    assert backend.duration > 0
    after = device.compile_requests()
    assert after["off"] == before["off"] + 1
    assert (after["hit"], after["miss"]) == (before["hit"], before["miss"])
    json.dumps([e.args for e in _spans(COMPILE)])
    # a second call compiles nothing and records nothing
    tracing.clear()
    jax.block_until_ready(outer(x))
    assert _spans(COMPILE) == []


CHILD = """
import json, sys
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from jax._src import distributed
distributed.global_state.process_id = int(sys.argv[1])
from kungfu_tpu.telemetry import device, tracing
device.watch_compiles()
device.watch_compiles()

@jax.jit
def inner_a(x): return x * 2
@jax.jit
def inner_b(x): return x + 1
@jax.jit
def outer(x): return inner_a(inner_b(x)) + inner_b(x)

jax.block_until_ready(outer(jnp.ones(3)))
print("SPANS " + json.dumps([[e.name, e.args] for e in tracing.full_events("device_plane.compile.")
                             if "outer" in e.args["fun_name"]]))
print("REQUESTS " + json.dumps(device.compile_requests()))
"""


def _child_env(cache_dir, **more):
    """The cache on (`tests/conftest.py` switches it off for every child
    through the environment) and in a scratch directory."""
    return dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
                JAX_COMPILATION_CACHE_DIR=str(cache_dir),
                JAX_ENABLE_COMPILATION_CACHE="true", **more)


def _compile_in_a_child(cache_dir, process_id):
    r = subprocess.run([sys.executable, "-c", CHILD, str(process_id)],
                       env=_child_env(cache_dir),
                       capture_output=True, text=True, timeout=120, cwd="/tmp")
    assert r.returncode == 0, r.stderr[-2000:]
    out = {line.split(" ", 1)[0]: json.loads(line.split(" ", 1)[1])
           for line in r.stdout.splitlines() if line.startswith(("SPANS", "REQUESTS"))}
    spans = {name[len(COMPILE):]: args for name, args in out["SPANS"]}
    assert set(spans) == {"trace", "lower", "backend"}, out["SPANS"]
    assert spans["trace"]["nested"] >= 2
    assert spans["backend"]["fun_name"] == "jit(outer)"
    return spans["backend"]["cache"], out["REQUESTS"]


@pytest.mark.parametrize("process_id,second", [(0, "hit"), (1, "miss")],
                         ids=["process_0_writes_the_cache",
                              "another_process_of_a_world_misses_again"])
def test_the_caches_answer_is_on_the_request(tmp_path, process_id, second):
    """Two processes, one scratch cache directory: the first misses; the
    second is served, unless the first was not process 0 of its world, which
    alone writes (`compiler._cache_write`): the kfrun cell's ranks 1 to 3,
    drawn small."""
    cache, requests = _compile_in_a_child(tmp_path, process_id)
    assert cache == "miss" and requests["miss"] >= 1 and requests["off"] == 0
    cache, requests = _compile_in_a_child(tmp_path, process_id)
    assert cache == second and requests[second] >= 1


# A worker as `enable_compile_cache()` leaves it (ISSUE 40): `process_id`
# as `jax.distributed.initialize` would have set it, one jitted function.
PEER = """
import json, os, sys, time
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from jax._src import compilation_cache, compiler, distributed
distributed.global_state.process_id = int(sys.argv[1])
what = sys.argv[2]
if what == "a_name_is_gone":  # one that JAX itself calls in process 0 alone
    del compilation_cache.put_executable_and_time
if what == "other_arguments":
    theirs = compiler._compile_and_write_cache
    compiler._compile_and_write_cache = lambda *renamed: theirs(*renamed)
if what == "spans_processes":
    # a program with a device of another process in it, as the seam sees one:
    # JAX's own function compiles for the devices there are
    class Devices:
        is_fully_addressable = False
        def __init__(self, mine): self.mine = mine
    jax_writes = compiler._compile_and_write_cache
    compiler._compile_and_write_cache = (
        lambda backend, computation, executable_devices, compile_options,
        host_callbacks, module_name, cache_key: jax_writes(
            backend, computation, getattr(executable_devices, "mine", executable_devices),
            compile_options, host_callbacks, module_name, cache_key))
from kungfu_tpu.parallel import chip
from kungfu_tpu.telemetry import device, metrics, tracing
chip.enable_compile_cache()
chip.enable_compile_cache()
if what == "spans_processes":
    peers_write = compiler._compile_and_write_cache
    compiler._compile_and_write_cache = (
        lambda backend, computation, executable_devices, *rest: peers_write(
            backend, computation, Devices(executable_devices), *rest))

@jax.jit
def outer(x):
    if what == "host_callback":
        jax.debug.print("x {}", x)
    return x * 2 + 1

x = jnp.ones(3)
if len(sys.argv) > 3:
    print("READY", flush=True)
    while not os.path.exists(sys.argv[3]):
        time.sleep(0.005)
jax.block_until_ready(outer(x))
print("SPANS " + json.dumps([e.args for e in tracing.full_events("device_plane.compile.backend")
                             if e.args["fun_name"] == "jit(outer)"]))
print("PEER_WRITES " + json.dumps(
    metrics.get_registry().get("kungfu_compile_cache_peer_writes_total").value))
"""


def _peer_argv(process_id, what, *more):
    return [sys.executable, "-c", PEER, str(process_id), what, *more]


def _peer_said(stdout):
    out = {line.split(" ", 1)[0]: json.loads(line.split(" ", 1)[1])
           for line in stdout.splitlines() if line.startswith(("SPANS", "PEER_WRITES"))}
    (request,) = out["SPANS"]
    return request, out["PEER_WRITES"]


def _a_worker_compiles(cache_dir, process_id, what="plain", **env):
    """((the request's span args, the peer-writes counter), stderr) of one
    child that compiles `outer` through `enable_compile_cache()`."""
    r = subprocess.run(_peer_argv(process_id, what), env=_child_env(cache_dir, **env),
                       capture_output=True, text=True, timeout=120, cwd="/tmp")
    assert r.returncode == 0, r.stderr[-2000:]
    return _peer_said(r.stdout), r.stderr


def _entries(cache_dir):
    return sorted(f for f in os.listdir(cache_dir) if f.startswith("jit_outer"))


@pytest.mark.parametrize("eviction", [{}, {"JAX_COMPILATION_CACHE_MAX_SIZE": "100000000"}],
                         ids=["renamed_into_place", "under_the_caches_lock"])
def test_a_peer_writes_its_own_program_once_and_loads_it_after(tmp_path, eviction):
    """The kfrun cell's ranks 1 to 3 since ISSUE 40: the first start
    compiles and writes, every later one is served."""
    (request, writes), _ = _a_worker_compiles(tmp_path, 1, **eviction)
    assert request == {"fun_name": "jit(outer)", "cache": "miss", "written": "peer"}
    assert writes >= 1
    (entry,) = [f for f in _entries(tmp_path) if f.endswith("-cache")]
    assert ".peer" not in entry
    (request, writes), _ = _a_worker_compiles(tmp_path, 1, **eviction)
    assert request == {"fun_name": "jit(outer)", "cache": "hit"}
    assert writes == 0


def test_process_0_writes_as_jax_has_it_and_is_no_peer(tmp_path):
    (request, writes), _ = _a_worker_compiles(tmp_path, 0)
    assert request == {"fun_name": "jit(outer)", "cache": "miss"} and writes == 0
    (request, writes), _ = _a_worker_compiles(tmp_path, 0)
    assert request["cache"] == "hit" and writes == 0


@pytest.mark.parametrize("what", ["host_callback", "spans_processes"])
def test_what_is_not_a_peers_alone_is_not_a_peers_to_write(tmp_path, what):
    """JAX's own rule for a program with a host callback is kept, and a
    program with a device of another process in it stays process 0's."""
    for _ in range(2):
        (request, _), _ = _a_worker_compiles(tmp_path, 1, what)
        assert request == {"fun_name": "jit(outer)", "cache": "miss"}
        assert _entries(tmp_path) == []  # the eager `ones` beside it is written


@pytest.mark.parametrize("what,named", [
    ("a_name_is_gone", "put_executable_and_time"),
    ("other_arguments", "_compile_and_write_cache takes ('renamed',)")])
def test_without_jaxs_private_names_nothing_is_installed_and_one_warning_says_so(
        tmp_path, what, named):
    for _ in range(2):  # the second run misses as on the parent
        (request, writes), stderr = _a_worker_compiles(tmp_path, 1, what)
        assert request == {"fun_name": "jit(outer)", "cache": "miss"} and writes == 0
        # `enable_compile_cache()` ran twice there
        (said,) = [l for l in stderr.splitlines() if "compile cache:" in l]
        assert named in said and " [W] " in said
    assert _entries(tmp_path) == []


def test_two_peers_writing_one_key_at_once_leave_an_entry_a_third_loads(tmp_path):
    """Two workers with one process id (of two worlds that share a cache
    directory; within one world the device assignment tells the keys apart,
    on the CPU as on the TPU) compile one program under one key: each
    writes under a name of its own and renames, so the key's file is whole."""
    cache, go = tmp_path / "cache", tmp_path / "go"
    cache.mkdir()
    two = [subprocess.Popen(_peer_argv(1, "plain", str(go)), env=_child_env(cache),
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True, cwd="/tmp") for _ in range(2)]
    try:
        for child in two:
            assert child.stdout.readline().strip() == "READY", child.stderr.read()[-2000:]
        go.write_text("")
        said = [_peer_said(child.communicate(timeout=120)[0]) for child in two]
    finally:
        for child in two:
            child.kill()
    assert all(child.returncode == 0 for child in two)
    assert all(request["cache"] == "miss" for request, _ in said)
    assert sum(writes for _, writes in said) >= 1
    assert [f.endswith("-cache") for f in _entries(cache)] == [True]
    (request, writes), _ = _a_worker_compiles(cache, 1)
    assert request == {"fun_name": "jit(outer)", "cache": "hit"} and writes == 0


def test_nested_events_fold_into_the_outermost_and_leave_the_ring_its_room():
    """ResNet's first step raises 2,471 trace events; `setup_s` reads
    `device_plane.backend_start` from the ring after the window."""
    with tracing.span("device_plane.backend_start"):
        pass
    seconds = metrics.counter("kungfu_compile_seconds_total", "", ("stage",))
    before = {stage: seconds.labels(stage).value for stage in ("trace", "lower")}
    nested = [lambda: _raise(TRACE, 0.001, "called")] * 3000
    _raise(TRACE, 4.0, "step", inside=nested)
    _raise(LOWER, 0.5, "jit(step)",
           inside=[lambda: _raise(TRACE, 0.001, "a_rule_traces")] * 5)
    found = _spans(COMPILE)
    assert len(found) < 50
    assert [(e.name, e.args["fun_name"], e.args["nested"], e.args["events"])
            for e in found] == [(COMPILE + "trace", "step", 3000, 3001),
                                (COMPILE + "lower", "jit(step)", 5, 6)]
    assert found[0].duration == pytest.approx(4.0, abs=1e-5)
    assert _spans("device_plane.backend_start")
    # the counters take every event's own seconds, so the stages sum to the
    # wall: a nested second is counted once, where it was spent (before
    # ISSUE 73 once a level: 7.005 s of tracing here)
    took = {stage: seconds.labels(stage).value - before[stage]
            for stage in ("trace", "lower")}
    assert took["trace"] == pytest.approx(4.0 + 0.005, abs=0.01)
    assert took["lower"] == pytest.approx(0.5 - 0.005, abs=0.01)


def _nest(tree):
    """Raise `(event, seconds, fun_name, [children])` as JAX would."""
    event, seconds, fun_name, children = tree
    _raise(event, seconds, fun_name,
           inside=[lambda child=child: _nest(child) for child in children])


# (the outermost event with all inside it, the rows `own` must hold, events)
NESTS = {
    "a_step_with_kernels_and_a_layer_traced_twice": (
        (TRACE, 10.0, "step", [
            (TRACE, 1.0, "wrapped", []),
            (TRACE, 3.0, "layer", [(TRACE, 1.0, "wrapped", []),
                                   (TRACE, 0.5, "_turned", [])]),
            (TRACE, 3.0, "layer", [(TRACE, 2.0, "wrapped", [])])]),
        [["step", "trace", 3.0, 1], ["wrapped", "trace", 4.0, 3],
         ["layer", "trace", 2.5, 2], ["_turned", "trace", 0.5, 1]], 7),
    "a_lowering_whose_rules_trace": (
        (LOWER, 2.0, "jit(step)", [
            (TRACE, 0.25, "a_rule", [(LOWER, 0.125, "inner", [])]),
            (LOWER, 0.5, "inner", [])]),
        [["jit(step)", "lower", 1.25, 1], ["inner", "lower", 0.625, 2],
         ["a_rule", "trace", 0.125, 1]], 4),
    "more_names_than_rows": (
        (TRACE, 20.0, "step", [(TRACE, 1.0 + i / 16, f"f{i}", [])
                               for i in range(12)]),
        [["f11", "trace", 1.6875, 1], ["f10", "trace", 1.625, 1]], 13),
    "a_compile_request_inside_is_not_the_traces_own": (
        (TRACE, 4.0, "step", [(TRACE, 2.0, "eager", [
            (BACKEND, 1.5, "jit(ones)", [])])]),
        [["step", "trace", 2.0, 1], ["eager", "trace", 0.5, 1]], 2),
}


@pytest.mark.parametrize("nest", list(NESTS))
def test_own_seconds_by_name_are_folded_into_the_span_that_is_kept(nest):
    """ISSUE 73: inside the outermost event every event's own seconds (its
    duration less what ended inside it) are summed by name and stage; the
    span says how many events there were and holds the largest sums."""
    tree, rows, events = NESTS[nest]
    seconds = metrics.counter("kungfu_compile_seconds_total", "", ("stage",))
    before = sum(seconds.labels(stage).value for stage in ("trace", "lower", "backend"))
    _nest(tree)
    (kept,) = [e for e in _spans(COMPILE) if "backend" not in e.name]
    assert kept.args["fun_name"] == tree[2]
    assert kept.args["events"] == kept.args["nested"] + 1 == events
    own = kept.args["own"]
    assert len(own) <= device.OWN_ROWS
    assert [row[2] for row in own] == sorted((row[2] for row in own), reverse=True)
    for row in rows:
        (mine,) = [r for r in own if r[:2] == row[:2]]
        assert mine[2] == pytest.approx(row[2], abs=1e-3) and mine[3] == row[3]
    requests = sum(e.duration for e in _spans(COMPILE + "backend"))
    if events <= device.OWN_ROWS:  # every row is there: they sum to the wall
        assert len(own) == len(rows)
        assert sum(row[2] for row in own) == pytest.approx(
            kept.duration - requests, abs=1e-3)
    # and so do the stages' counters, the requests' seconds with them
    after = sum(seconds.labels(stage).value for stage in ("trace", "lower", "backend"))
    assert after - before == pytest.approx(kept.duration, abs=1e-3)
    json.dumps(kept.args)
    # the next outermost event starts from nothing
    _raise(TRACE, 0.25, "next")
    assert _spans(COMPILE + "trace")[-1].args["own"] == [["next", "trace", 0.25, 1]]


def test_a_request_inside_a_trace_is_a_span_of_its_own():
    """An eager op while tracing: requests do not nest, each is a span."""
    def eager():
        _raise(TRACE, 0.01, "ones")
        _raise(LOWER, 0.01, "jit(ones)")
        _raise(BACKEND, 0.25, "jit(ones)")

    _raise(TRACE, 1.0, "step", inside=[eager])
    assert [(e.name[len(COMPILE):], e.args["fun_name"]) for e in _spans(COMPILE)] == [
        ("backend", "jit(ones)"), ("trace", "step")]
    assert _spans(COMPILE + "trace")[0].args["nested"] == 2


def test_each_thread_has_its_own_depth():
    inside = threading.Event()
    go_on = threading.Event()

    def held_open():
        inside.set()
        assert go_on.wait(30)

    other = threading.Thread(
        target=lambda: _raise(TRACE, 0.5, "theirs", inside=[held_open]))
    other.start()
    assert inside.wait(30)
    _raise(TRACE, 0.25, "mine")  # outermost here, whatever they are inside
    go_on.set()
    other.join(30)
    assert not other.is_alive()
    assert sorted(e.args["fun_name"] for e in _spans(COMPILE + "trace")) == [
        "mine", "theirs"]
    assert all(e.args["nested"] == 0 for e in _spans(COMPILE + "trace"))


def test_the_spans_carry_the_step_as_any_other():
    with tracing.step_scope(3, 17):
        _raise(BACKEND, 0.1, "jit(step)")
        gc.collect()
    (backend,) = _spans(COMPILE + "backend")
    assert backend.args["step"] == [3, 17]
    assert _spans("worker.gc")[-1].args["step"] == [3, 17]


def test_installing_twice_registers_once():
    device.watch_compiles()
    tracing.watch_gc()
    from jax._src import monitoring as registry

    for listeners in (registry.get_scalar_listeners(),
                      registry.get_event_listeners(),
                      registry.get_event_time_span_listeners()):
        mine = [l for l in listeners
                if isinstance(getattr(l, "__self__", None), device._CompileWatch)]
        assert len(mine) == 1
    assert len([c for c in gc.callbacks if isinstance(c, tracing._GcWatch)]) == 1
    gc.collect()
    assert len(_spans("worker.gc")) == 1


# -- the collector ------------------------------------------------------------

def test_a_full_collection_is_a_span_and_young_ones_are_counted_only(monkeypatch):
    # a young collection takes microseconds; on a loaded machine one of a
    # thousand may be preempted for longer than the span's floor
    monkeypatch.setattr(tracing, "GC_SPAN_MIN_S", 0.25)
    before, paused = tracing.gc_totals()
    for _ in range(1000):
        gc.collect(0)
    assert _spans("worker.gc") == []
    gc.collect()
    (full,) = _spans("worker.gc")
    assert full.args["generation"] == 2 and full.args["collected"] >= 0
    assert full.duration > 0 and full.start <= time.perf_counter()
    after, paused_after = tracing.gc_totals()
    assert after[0] - before[0] >= 1000 and after[2] - before[2] == 1
    assert paused_after >= paused + full.duration


def test_a_slow_young_collection_is_a_span_too(monkeypatch):
    monkeypatch.setattr(tracing, "GC_SPAN_MIN_S", 0.0)
    gc.collect(0)
    (young,) = _spans("worker.gc")
    assert young.args["generation"] == 0


def test_the_hook_taken_out_comes_back_the_same_with_its_totals():
    """`tests/conftest.py`'s `runtime_watchers` takes it out after a file;
    the next `watch_gc()` of the process puts the same hook back."""
    hook, before = tracing._gc_watch, tracing.gc_totals()
    gc.callbacks.remove(hook)
    try:
        gc.collect()
        assert _spans("worker.gc") == [] and tracing.gc_totals() == before
    finally:
        tracing.watch_gc()
    assert [c for c in gc.callbacks if isinstance(c, tracing._GcWatch)] == [hook]
    gc.collect()
    assert tracing.gc_totals()[0][2] >= before[0][2] + 1
    assert len(_spans("worker.gc")) >= 1


def test_a_collection_the_hook_joined_halfway_is_not_counted():
    """A finalizer may let another thread install the hook in the middle
    of a collection: its end arrives with no start to measure from."""
    hook = tracing._GcWatch()
    hook("stop", {"generation": 2, "collected": 0, "uncollectable": 0})
    assert hook.collections == [0, 0, 0] and hook.pause_s == 0.0
    assert _spans("worker.gc") == []


def test_scrapes_at_once_add_a_collection_once():
    """`/metrics` and a flight snapshot both bring the totals into the
    registry: read and add is one step, or both add the same difference."""
    reg = metrics.Registry()
    gc.collect()
    together = threading.Barrier(8)

    def scrape():
        together.wait(30)
        for _ in range(40):
            metrics.update_process_health(reg)

    threads = [threading.Thread(target=scrape, daemon=True) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    metrics.update_process_health(reg)
    collections, paused = tracing.gc_totals()
    family = reg.get("kungfu_gc_collections_total")
    assert [family.labels(g).value for g in range(3)] == list(collections)
    assert reg.get("kungfu_gc_pause_seconds_total").value == pytest.approx(paused)


@pytest.mark.parametrize("held", ["the_rings_lock", "the_counters_lock"])
def test_a_collection_under_a_held_lock_does_not_wait_for_it(held):
    """The collector runs wherever the interpreter checks for it, also in a
    thread that holds the ring's lock or a metric family's: the hook takes
    neither."""
    metrics.update_process_health()
    family = metrics.get_registry().get("kungfu_gc_collections_total")
    lock = tracing._lock if held == "the_rings_lock" else family._lock

    def collect_under_it():
        with lock:
            gc.collect()

    t = threading.Thread(target=collect_under_it, daemon=True)
    t.start()
    t.join(30)
    assert not t.is_alive()
    assert len(_spans("worker.gc")) == 1


def test_metrics_shows_both_families_to_a_scraper():
    from kungfu_tpu.telemetry.http import TelemetryServer

    _raise(BACKEND, 0.1, "jit(step)")
    gc.collect()
    srv = TelemetryServer(0, host="127.0.0.1")
    srv.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=5) as r:
            body = r.read().decode()
    finally:
        srv.stop()
    lines = dict(line.rsplit(" ", 1) for line in body.splitlines()
                 if line.startswith(("kungfu_compile_", "kungfu_gc_")))
    for cache in device.CACHE_SAID:
        assert f'kungfu_compile_requests_total{{cache="{cache}"}}' in lines
    assert float(lines['kungfu_compile_seconds_total{stage="backend"}']) >= 0.1
    collections, paused = tracing.gc_totals()
    assert float(lines['kungfu_gc_collections_total{generation="2"}']) >= 1
    assert float(lines["kungfu_gc_pause_seconds_total"]) > 0
    # a second scrape adds what happened since and never counts twice
    gc.collect()
    metrics.update_process_health()
    metrics.update_process_health()
    family = metrics.get_registry().get("kungfu_gc_collections_total")
    assert family.labels(2).value == tracing.gc_totals()[0][2]


# -- imports ------------------------------------------------------------------

def test_the_packages_imports_are_spans_over_their_own_bodies():
    code = (
        "import json, time\n"
        "t0 = time.perf_counter()\n"
        "from kungfu_tpu.parallel.chip import enable_compile_cache\n"
        "t1 = time.perf_counter()\n"
        "from kungfu_tpu.telemetry import tracing\n"
        "print(json.dumps([[e.args['module'], e.start - t0, e.duration, t1 - t0]\n"
        "                  for e in tracing.full_events('worker.import')]))\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, cwd="/tmp",
                       env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO))
    assert r.returncode == 0, r.stderr[-2000:]
    spans = json.loads(r.stdout.splitlines()[-1])
    assert [s[0] for s in spans] == ["kungfu_tpu", "kungfu_tpu.parallel"]
    whole = spans[0][3]
    for _, start, duration, _ in spans:
        assert 0 <= start and start + duration <= whole
    # the one-process launcher's `join()` is these two imports
    assert sum(s[2] for s in spans) >= 0.9 * whole
