"""The device plane of the one tracing system: a profile an operator takes.

`telemetry/tracing.py` times the host; the scopes in the program
(`jax.named_scope`, vocabulary in docs/telemetry.md) name what the chip
runs. This module joins them on one clock:

    from kungfu_tpu.telemetry import device, tracing
    compiled = step.lower(params, opt_state, batch).compile()
    with device.profile("/tmp/prof"):
        with tracing.span("train.step"):
            params, opt_state, loss = compiled(params, opt_state, batch)
            loss.block_until_ready()
    print(device.phase_ms(device.find_xplane("/tmp/prof"),
                          device.scope_table(compiled)))

While `profile()` is open every `tracing.span` also enters a
`jax.profiler.TraceAnnotation` of its name, so the ring's spans sit in the
`.xplane.pb` beside the device's ops, on the profiler's clock. The Python
tracer is off and the host tracer at its lowest level that keeps user
annotations (`host_tracer_level = 1`). That level still records PJRT's
per-batch `Transpose` calls: free for a step that moves kilobytes, 9 % of
a ResNet step that moves 38.5 MB a batch (chip run, PERF.md, PR 24).

`watch_compiles()` also needs jax: JAX's own account of every compile
request (`jax.monitoring`) as spans of the ring and counters, so that
`/trace` says which function compiled and whether the cache served it.

`parallel/chip.py` imports this module, so a worker has it from its start;
jax itself is imported only inside the functions that need it, and the
runner and `tracing` import neither.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import glob
import os
import re
import statistics
import threading
from typing import Dict, Iterator, List, Tuple

from kungfu_tpu.telemetry import metrics, tracing

PHASES = ("forward", "backward", "optimizer", "all_reduce", "unattributed")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
# jit(f), shard_map, while/body/closed_call, checkpoint/rematted_computation
# and the primitive at the path's end are how JAX got there, not where in
# the program the op belongs
_PLUMBING = re.compile(r"^(jit\(.*\)|pjit|shard_map|while|body|cond|branch_\d+|"
                       r"closed_call|checkpoint|remat|rematted_computation|"
                       r"custom_jvp_call|custom_vjp_call.*)$")


@contextlib.contextmanager
def profile(log_dir: str) -> Iterator[str]:
    """Take a `jax.profiler` trace into `log_dir` with the Python tracer
    off and the host tracer at the level of user annotations, and mirror every
    `tracing.span` into it while it is open."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=options)
    tracing._mirror = jax.profiler.TraceAnnotation
    try:
        yield log_dir
    finally:
        tracing._mirror = None
        jax.profiler.stop_trace()


# JAX raises each of these through `dispatch.log_elapsed_time`: a scalar on
# entry, a duration and a time span on exit, all with `fun_name`
_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_SAID = ("hit", "miss", "off")  # the `cache` of a compile request
OWN_ROWS = 8  # rows of `own` on an outermost trace or lowering's span


class _CompileWatch(threading.local):
    """What a thread's compile requests are in the middle of, and the three
    `jax.monitoring` listeners that keep it. A compile request (`backend`:
    an XLA compile or a load from the persistent cache) is a span each,
    with the cache's answer: inside it JAX raises
    `compile_requests_use_cache` where it asks the cache and `cache_hits`
    where that served it (`cache_misses` only where it also writes the
    entry: JAX itself writes from process 0 of a world alone, and any other
    process writes the programs that are its own through the seam of
    `parallel/chip.enable_compile_cache()`, which says so here by
    `wrote_as_peer()`: `written: "peer"` on the request's span and
    `kungfu_compile_cache_peer_writes_total`). Tracing and lowering nest,
    in themselves and in each other (2,471 trace events in ResNet's first
    step, 14,697 in the Kimi-Linear cell's, PERF.md; a lowering rule may
    trace), so only the outermost of a thread is a span, or one model floods
    the ring. What ended inside it is folded into it by name: every event's
    **own** seconds (its duration less that of the events and compile
    requests inside it), summed by (`fun_name`, stage), and the span leaves
    with `events`, how many there were with itself, and `own`, the
    `OWN_ROWS` largest of those sums. `events` is the program's alone, the
    same on any host; the seconds are the host's. The counters take own
    seconds too, so the stages sum to the wall. The listeners run inside
    JAX's compile path: they raise nothing."""

    def __init__(self):
        # the seconds of what ended inside each trace and lower event this
        # thread is inside, the outermost first
        self.inside: List[float] = []
        # {(fun_name, stage): [own seconds, events]} since the outermost began
        self.own: Dict[Tuple[str, str], list] = {}
        self.cache = "off"
        self.written = ""  # "peer" where this process wrote the entry as one
        requests = metrics.counter(
            "kungfu_compile_requests_total",
            "Compile requests by the persistent cache's answer", ("cache",))
        seconds = metrics.counter(
            "kungfu_compile_seconds_total",
            "Seconds in JAX's compile stages, each event's own: the stages "
            "sum to the wall", ("stage",))
        # every series from the start: a request count of 0 is information
        self.requests = {c: requests.labels(c) for c in CACHE_SAID}
        self.seconds = {s: seconds.labels(s) for s in _STAGES.values()}
        self.peer_writes = metrics.counter(
            "kungfu_compile_cache_peer_writes_total",
            "Cache entries this process wrote though it is not process 0 "
            "of its world: programs of its own devices alone")

    def entered(self, event: str, value, **kw) -> None:
        stage = _STAGES.get(event)
        if stage == "backend":
            self.cache, self.written = "off", ""
        elif stage is not None:
            self.inside.append(0.0)

    def cache_said(self, event: str, **kw) -> None:
        if event == _CACHE_ASKED:
            self.cache = "miss"
        elif event == _CACHE_HIT:
            self.cache = "hit"

    def left(self, event: str, start: float, end: float, fun_name: str = "",
             **kw) -> None:
        stage = _STAGES.get(event)
        if stage is None:
            return
        took = max(0.0, end - start)  # the wall clock may step
        if stage == "backend":
            self.seconds[stage].inc(took)
            self.requests[self.cache].inc()
            if self.inside:  # an eager op while tracing: not the trace's own
                self.inside[-1] += took
            written = {"written": self.written} if self.written else {}
            tracing.record("device_plane.compile.backend", took,
                           fun_name=fun_name, cache=self.cache, **written)
            return
        # an exit with no entry: the watch began inside it
        own = max(0.0, took - (self.inside.pop() if self.inside else 0.0))
        self.seconds[stage].inc(own)
        row = self.own.setdefault((fun_name, stage), [0.0, 0])
        row[0] += own
        row[1] += 1
        if self.inside:
            self.inside[-1] += took
            return
        rows, self.own = self.own, {}
        events = sum(count for _, count in rows.values())
        largest = sorted(rows.items(), key=lambda row: -row[1][0])[:OWN_ROWS]
        tracing.record(
            "device_plane.compile." + stage, took, fun_name=fun_name,
            nested=events - 1, events=events,
            own=[[name, of, round(seconds, 6), count]
                 for (name, of), (seconds, count) in largest])


_compile_watch = None


def watch_compiles() -> None:
    """Every compile request of this process into the ring and the registry
    from now on (the spans and counters of `_CompileWatch`). Once a process,
    however often it is called; call it before the first compile. The one
    place in `kungfu_tpu/` that registers `jax.monitoring` listeners."""
    global _compile_watch
    if _compile_watch is not None:
        return
    from jax import monitoring

    watch = _compile_watch = _CompileWatch()
    monitoring.register_scalar_listener(watch.entered)
    monitoring.register_event_listener(watch.cache_said)
    monitoring.register_event_time_span_listener(watch.left)


def wrote_as_peer() -> None:
    """The compile request this thread is inside missed, and this process,
    not process 0 of its world, wrote the entry (`parallel/chip.py`)."""
    watch = _compile_watch
    if watch is not None:
        watch.written = "peer"
        watch.peer_writes.inc()


def compile_requests() -> Dict[str, int]:
    """{"hit" | "miss" | "off": compile requests since `watch_compiles()`}."""
    watch = _compile_watch
    return {c: int(watch.requests[c].value) if watch else 0 for c in CACHE_SAID}


def find_xplane(log_dir: str) -> str:
    """The newest `.xplane.pb` a profile left under `log_dir`."""
    found = sorted(glob.glob(
        os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def scope_table(compiled) -> Dict[str, str]:
    """{HLO instruction name: op_name} of a compiled program, from
    `compiled.as_text()`: the scope path JAX wrote on each instruction,
    `jit(local_step)/shard_map/transpose(jvp())/while/body/closed_call/attn/attn_core/dot_general`.
    An instruction the compiler made itself (a copy, a bitcast) has none
    and is left out."""
    table = {}
    for line in compiled.as_text().splitlines():
        name = _INSTRUCTION.match(line)
        scope = _OP_NAME.search(line)
        if name and scope:
            table[name.group(1)] = scope.group(1)
    return table


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for sub in value if isinstance(value, (tuple, list)) else (value,):
            inner = getattr(sub, "jaxpr", sub)
            if hasattr(inner, "eqns"):
                yield inner


def saved_bytes(fn, *abstract_args) -> List[Tuple[Tuple[int, ...], str, int]]:
    """(shape, dtype, bytes) of every array that a forward `lax.scan` of
    `value_and_grad(fn)` stacks for a reversed one: what the layer scan of
    a model saves for its backward pass, largest first. Read off
    `jax.make_jaxpr` over `jax.ShapeDtypeStruct`s, so nothing runs and
    nothing is compiled: a budget a test can hold on the CPU (PERF.md,
    PR 25)."""
    import jax

    saved = []

    def walk(jaxpr):
        read_reversed = set()
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan" and eqn.params["reverse"]:
                skip = eqn.params["num_consts"] + eqn.params["num_carry"]
                read_reversed.update(map(id, eqn.invars[skip:]))
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan" and not eqn.params["reverse"]:
                for v in eqn.outvars[eqn.params["num_carry"]:]:
                    if id(v) in read_reversed:
                        a = v.aval
                        saved.append((tuple(a.shape), a.dtype.name,
                                      a.size * a.dtype.itemsize))
            for inner in _sub_jaxprs(eqn):
                walk(inner)

    walk(jax.make_jaxpr(jax.value_and_grad(fn))(*abstract_args).jaxpr)
    return sorted(saved, key=lambda s: -s[2])


def scope_parts(op_name: str) -> List[str]:
    """The components of an `op_name` that say where in the program an
    instruction belongs: plumbing and the primitive's own name taken out."""
    parts = op_name.split("/")[:-1] if "/" in op_name else []
    return [p for p in parts if not _PLUMBING.match(p)]


def phase_of(op_name: str) -> str:
    """Forward, backward, optimizer, all-reduce or unattributed, by path
    component: `grad_allreduce` first, then `optimizer` /
    `optimizer_update`, then any `transpose(`, then any `jvp(` or other
    scope of the model."""
    parts = scope_parts(op_name or "")
    # also as JAX writes it at the top of a transposed function,
    # `transpose(jvp(grad_allreduce))`: the leaves a loss reduces itself
    if any("grad_allreduce" in p for p in parts):
        return "all_reduce"
    if "optimizer" in parts or "optimizer_update" in parts:
        return "optimizer"
    if any(p.startswith("transpose(") for p in parts):
        return "backward"
    if parts:
        return "forward"
    return "unattributed"


def _instruction(event_name: str) -> str:
    """The TPU's trace names an op by its whole instruction text,
    `%fusion.13 = ...`; the CPU's by the instruction's name."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _self_ns(events: List[Tuple[str, int, int]]) -> List[Tuple[str, int, int]]:
    """(name, start, own nanoseconds) of each event of one line: its
    duration less that of the events it encloses (a `while` encloses the
    ops of its body)."""
    out, stack = [], []  # stack of [name, start, end, enclosed_ns]
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= a:
            n, s, e, inner = stack.pop()
            out.append((n, s, e - s - inner))
        if stack and b <= stack[-1][2]:
            stack[-1][3] += b - a
        stack.append([name, a, b, 0])
    for n, s, e, inner in stack:
        out.append((n, s, e - s - inner))
    return out


def phase_ms(xplane_path: str, table: Dict[str, str]) -> Dict[str, float]:
    """{phase: milliseconds a step} from a profile of the program whose
    `scope_table` is `table`: every op's own time, classed by `phase_of`,
    summed within each run of the program on the first device, median over
    the runs. The program is the one that took most of the profile's
    device time. On a TPU the ops are the device plane's "XLA Ops" line and
    a run is an event of "XLA Modules"; the CPU backend puts its ops on the
    host plane's thread lines, stamped with `hlo_module`, `run_id` and
    `device_ordinal`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    planes = sorted(data.planes, key=lambda p: p.name)
    for plane in planes:
        lines = {line.name: line for line in plane.lines}
        if not (plane.name.startswith("/device:") and "XLA Ops" in lines
                and "XLA Modules" in lines):
            continue
        programs = collections.defaultdict(list)
        for e in lines["XLA Modules"].events:
            programs[e.name].append(
                (int(e.start_ns), int(e.start_ns + e.duration_ns)))
        if not programs:
            continue
        runs = sorted(max(programs.values(),
                          key=lambda rs: sum(b - a for a, b in rs)))
        starts = [a for a, _ in runs]
        steps = [collections.Counter() for _ in runs]
        ops = [(_instruction(e.name), int(e.start_ns),
                int(e.start_ns + e.duration_ns)) for e in lines["XLA Ops"].events]
        for name, start, own in _self_ns(ops):
            i = bisect.bisect_right(starts, start) - 1
            if i >= 0 and start < runs[i][1]:
                steps[i][phase_of(table.get(name, ""))] += own
        return _median_ms(steps)
    # the CPU backend: no device plane
    programs = collections.defaultdict(lambda: collections.defaultdict(collections.Counter))
    for plane in planes:
        for line in plane.lines:
            found = []
            for e in line.events:
                stats = dict(e.stats)
                # an op's event is named by its instruction; the thunk
                # executor's own events carry the stats too
                if "hlo_module" in stats and stats.get("hlo_op") == e.name:
                    key = (stats["hlo_module"], stats.get("device_ordinal", 0),
                           stats.get("run_id"), e.name)
                    found.append((key, int(e.start_ns),
                                  int(e.start_ns + e.duration_ns)))
            for (module, device, run, name), _, own in _self_ns(found):
                programs[module][(device, run)][phase_of(table.get(name, ""))] += own
    if not programs:
        return {}
    steps = max(programs.values(),
                key=lambda runs: sum(sum(c.values()) for c in runs.values()))
    first = min(device for device, _ in steps)
    return _median_ms([c for (device, _), c in steps.items() if device == first])


def _median_ms(steps: List[collections.Counter]) -> Dict[str, float]:
    if not steps:
        return {}
    return {phase: statistics.median(s[phase] for s in steps) / 1e6
            for phase in PHASES}
