"""From a profiler trace to the numbers the per-layer metrics read.

Two halves. `read_xplane` runs in the process that traced (it needs jax for
`jax.profiler.ProfileData`) and cuts the `.xplane.pb` down to a small JSON
object, the *reduced trace*:

    {"chips": [{"plane": "/device:TPU:0",
                "steps": [[start_ns, end_ns], ...],      the step program's runs
                "ops":   [[name, start_ns, end_ns], ...],    device ops in them
                "async": [[name, start_ns, end_ns], ...],    -start to -done
                "labels": {name: "result type and operation"},
                "kinds":  {name: "all-reduce" | "fusion" | ...}}],
     "host":  [[name, start_ns, end_ns], ...],           the benchmark's spans,
                                                         put there by place_spans
     "lines": {plane: {line name: events}}}              what the trace held

Everything else here is arithmetic on that object, with no jax, so the
parent of a run and the tests use it as it is. The traced run's record also
holds the step program's scope table (`scope_table`, read off
`compiled.as_text()` in `harness.measure`): `scope_ms` splits each step's
device time by it.

How the TPU's trace is laid out (read by hand, chip run of PR 23, jax 0.9.0,
libtpu 0.0.34): each chip is a plane "/device:TPU:<n>" with the lines
"Steps" (one event a step, named by its number), "XLA Modules" (one event
per run of a program, `jit_local_step(<fingerprint>)`), "XLA Ops" (one event
per HLO operation, named by its whole instruction text; a `while`, the scan
over layers, encloses the operations of its body), "Async XLA Ops" (one
event from an asynchronous operation's `-start` to the end of its `-done`:
`copy-start`, `slice-start`; the four-chip step's all-reduces were synchronous
ops on "XLA Ops", named `all-reduce` and `psum.<n>`), "XLA TraceMe" (across
chips, `barrier-cores`) and an empty "TC Overlay". The plane "/host:CPU" has a line a thread with the runtime's
own events (the traced run switches them off, see harness.measure). The planes "#Chip0 Host Interface",
"#Chip0 Misc", "/device:CUSTOM:Megascale Trace", "/host:metadata" and "Task
Environment" held nothing. All planes share one clock, in nanoseconds.
"""

from __future__ import annotations

import collections
import glob
import math
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"
ALL_REDUCE = "all-reduce"


# -- the process that traced ------------------------------------------------


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(event_name: str) -> str:
    """The trace prints an op as its whole HLO instruction,
    `%fusion.13 = (f32[256]{...}, ...) fusion(...)`; its name is the part
    before the `=`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def op_label(event_name: str, limit: int = 56) -> str:
    """What an op computes, as far as its instruction text says in a few
    characters: result type and operation, layouts taken out."""
    text = event_name.split(" = ", 1)[-1]
    return re.sub(r"\{[^{}]*\}", "", text)[:limit]


def op_kind(event_name: str) -> str:
    """The HLO operation of an op, `all-reduce` or `fusion`: what follows
    the result type in its instruction text. The name does not say it: the
    all-reduces of `lax.pmean` are called `psum.73` (chip run, PR 23)."""
    text = event_name.split(" = ", 1)[-1]
    if text.startswith("("):  # a tuple type: skip to its closing parenthesis
        depth = 0
        for i, ch in enumerate(text):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                text = text[i + 1:]
                break
    else:
        text = text.partition(" ")[2]
    return text.strip().split("(", 1)[0]


def events_of(line) -> list:
    """[(instruction text, start_ns, end_ns)] of a line, read once: every
    access to a profile event builds a Python object."""
    if line is None:
        return []
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def inside(events, lo: int, hi: int) -> list:
    """[[name, start_ns, end_ns], ...] of the events inside [lo, hi], by
    start, an enclosing event before those it encloses."""
    found = [[op_name(text), a, b] for text, a, b in events
             if lo <= a and b <= hi]
    return sorted(found, key=lambda o: (o[1], -o[2]))


def read_xplane(path: str, n_steps: int) -> dict:
    """Reduce one trace file. The step program is the module that ran at
    least `n_steps` times and took the most device time; its last `n_steps`
    runs are the steps."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    reduced = {"chips": [], "host": [], "lines": {}}
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            reduced["lines"][plane.name] = {
                "events": sum(len(events_of(line)) for line in plane.lines)}
            continue
        lines = {line.name: events_of(line) for line in plane.lines}
        reduced["lines"][plane.name] = {k: len(v) for k, v in lines.items()}
        if MODULES_LINE not in lines or OPS_LINE not in lines:
            continue
        runs = collections.defaultdict(list)
        for text, a, b in lines[MODULES_LINE]:
            runs[text].append((a, b))
        often = {k: v for k, v in runs.items() if len(v) >= n_steps}
        if not often:
            continue
        name = max(often, key=lambda k: sum(b - a for a, b in often[k]))
        steps = sorted(often[name])[-n_steps:]
        lo, hi = steps[0][0], steps[-1][1]
        texts = {op_name(text): text for text, _, _ in lines[OPS_LINE]}
        reduced["chips"].append({
            "plane": plane.name, "program": name,
            "steps": [list(s) for s in steps],
            "ops": inside(lines[OPS_LINE], lo, hi),
            "async": inside(lines.get(ASYNC_LINE, []), lo, hi),
            "labels": {k: op_label(v) for k, v in texts.items()},
            "kinds": {k: op_kind(v) for k, v in texts.items()},
        })
    reduced["chips"].sort(key=lambda c: c["plane"])
    return reduced


def place_spans(reduced: dict, window: dict) -> None:
    """Put the run's host spans (`window["spans"]`, seconds on the host's
    clock) on the trace's clock, as `reduced["host"]`. The host sees the
    loss of step i ready a moment after the device ends step i, so the
    median over the steps of (device end - host's `t_done`) is the offset
    between the clocks, out by that moment: some tenths of a millisecond,
    which is enough to say under which span a long gap fell."""
    if not reduced["chips"]:
        return
    ends = [b for _, b in chip(reduced)["steps"]]
    offset = median([end - t * 1e9 for end, t in zip(ends, window["t_done"])])
    reduced["host"] = [[name, round(a * 1e9 + offset), round(b * 1e9 + offset)]
                       for name, a, b in window["spans"]]


# -- arithmetic on intervals (no jax) ----------------------------------------


def union(intervals) -> list:
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def length(intervals) -> int:
    return sum(b - a for a, b in union(intervals))


def clip(intervals, lo, hi) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(intervals, holes) -> list:
    """The parts of `intervals` that no interval of `holes` covers."""
    out = []
    holes = union(holes)
    for a, b in union(intervals):
        at = a
        for c, d in holes:
            if d <= at or c >= b:
                continue
            if c > at:
                out.append([at, c])
            at = max(at, d)
        if at < b:
            out.append([at, b])
    return out


def self_segments(ops) -> list:
    """[(name, [[a, b], ...])] for each op: its interval less the intervals
    of the ops it encloses. A `while` that spans its body's operations
    keeps only the time in which none of them runs."""
    out = []
    stack = []  # [name, end, cursor, segments]

    def close(frame):
        name, end, cursor, segments = frame
        if end > cursor:
            segments.append([cursor, end])
        out.append((name, segments))

    for name, a, b in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= a:
            close(stack.pop())
        if stack and b <= stack[-1][1]:  # enclosed; an overlap is a sibling
            parent = stack[-1]
            if a > parent[2]:
                parent[3].append([parent[2], a])
            parent[2] = max(parent[2], b)
        stack.append([name, b, a, []])
    while stack:
        close(stack.pop())
    return out


def is_all_reduce(name: str, kinds: dict) -> bool:
    """By the op's HLO operation where the trace gave one, else by its
    name; `all-reduce-start` and `all-reduce-done` count."""
    return kinds.get(name, name).startswith(ALL_REDUCE)


def chip(trace: dict) -> dict:
    """The traced chip the per-step metrics read: the first one."""
    return trace["chips"][0]


def window(c: dict) -> tuple:
    return c["steps"][0][0], c["steps"][-1][1]


def busy(c: dict) -> list:
    """The union of the intervals in which an operation ran on the chip."""
    lo, hi = window(c)
    return clip(union([o[1], o[2]] for o in c["ops"]), lo, hi)


def device_busy_and_window_s(trace: dict) -> tuple:
    """(busy seconds averaged over the traced chips, window seconds)."""
    chips = trace["chips"]
    busy_s = sum(length(busy(c)) for c in chips) / len(chips) / 1e9
    window_s = sum(window(c)[1] - window(c)[0] for c in chips) / len(chips) / 1e9
    return busy_s, window_s


def per_step(c: dict, intervals) -> list:
    """Nanoseconds of `intervals` that fall inside each step."""
    covered = union(intervals)
    return [length(clip(covered, a, b)) for a, b in c["steps"]]


def all_reduce_segments(c: dict) -> tuple:
    """(the intervals in which an all-reduce is under way, every other
    op's self segments). An asynchronous all-reduce is under way from its
    `-start` to the end of its `-done`: the event the "Async XLA Ops" line
    holds; a synchronous one is an op like any other."""
    mine, others = [], []
    kinds = c.get("kinds", {})
    for name, segments in self_segments(c["ops"]):
        (mine if is_all_reduce(name, kinds) else others).extend(segments)
    mine.extend([a, b] for name, a, b in c.get("async", [])
                if is_all_reduce(name, kinds))
    return union(mine), others


# -- scopes: which part of the program an op belongs to ----------------------
# The benchmark's own copy of what `kungfu_tpu.telemetry.device` does for an
# operator: later PRs change the program, not the yardstick.

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
# jit(f), shard_map, while/body/closed_call and checkpoint/rematted_computation
# are how JAX got to an op, not where in the program it belongs
_PLUMBING = re.compile(r"(jit\(.*\)|pjit|shard_map|while|body|cond|branch_\d+|"
                       r"closed_call|checkpoint|remat|rematted_computation|"
                       r"custom_jvp_call|custom_vjp_call.*)\Z")


def scope_table(hlo_text: str) -> dict:
    """{instruction name: op_name} of a compiled program's text
    (`compiled.as_text()`): the scope path JAX wrote on each instruction,
    `jit(local_step)/shard_map/transpose(jvp())/while/body/closed_call/attn/attn_core/dot_general`.
    An instruction the compiler made itself (a copy, a bitcast) has none
    and is left out."""
    table = {}
    for line in hlo_text.splitlines():
        name = _INSTRUCTION.match(line)
        scope = _OP_NAME.search(line)
        if name and scope:
            table[name.group(1)] = scope.group(1)
    return table


def scope_parts(op_name: str) -> list:
    """The components of an `op_name` that say where in the program an
    instruction belongs: the plumbing and, at the path's end, the
    primitive's own name taken out."""
    return [p for p in op_name.split("/")[:-1] if not _PLUMBING.match(p)]


def scope_names(parts: list) -> set:
    """Every word of a scope path, the transforms' wrappers opened:
    `transpose(jvp(head_loss))` holds `head_loss` as `attn/attn_core`
    holds `attn_core`. JAX writes some scopes into the wrapper
    (`jvp(head_loss)`, `jvp(embed)`, `jvp(ResNet)`) and others as
    components of their own (`jvp()/while/body/closed_call/attn`,
    `jvp(ResNet)/BottleneckBlock_0`): read off the three cells' programs."""
    return {word for p in parts for word in re.split(r"[()]", p) if word}


def phase_of(parts: list) -> str:
    """The phase of an op that is no all-reduce itself, by the components
    of its scope path (the rule of PR 24, written once): `optimizer`,
    `optimizer_update` or `grad_allreduce` -> optimizer (what `pmean`
    leaves beside its collective, a division, is the optimizer wrapper's
    arithmetic); else any `transpose(` -> backward; else any other scope,
    `jvp(` or the model's own -> forward; else unattributed."""
    if any(p in ("optimizer", "optimizer_update", "grad_allreduce")
           for p in parts):
        return "optimizer"
    if any(p.startswith("transpose(") for p in parts):
        return "backward"
    return "forward" if parts else "unattributed"


def scope_ms(record: dict, trace, wanted):
    """Milliseconds a step, the median over the traced steps, of the own
    time (`self_segments`: a `while` less its body) of the traced chip's
    ops for which `wanted(phase, names)` holds (`phase_of` and
    `scope_names` of the op's scope path); `record["scopes"]` is the
    step program's `scope_table`. An all-reduce, found by its HLO
    operation whatever its scope, is `allreduce_ms`' and is in no scope's
    time, so forward, backward, optimizer, unattributed and the
    all-reduces together are every op's own time once. An op the table
    does not name is unattributed. None without a table, a traced chip or
    one such op."""
    scopes = record.get("scopes")
    if not scopes or not trace or not trace["chips"]:
        return None
    c = chip(trace)
    kinds = c.get("kinds", {})
    verdict = {}  # by name: a step's few hundred ops run in every step

    def is_mine(name):
        if is_all_reduce(name, kinds):
            return False
        parts = scope_parts(scopes.get(name, ""))
        return wanted(phase_of(parts), scope_names(parts))

    mine = []
    for name, segments in self_segments(c["ops"]):
        if name not in verdict:
            verdict[name] = is_mine(name)
        if verdict[name]:
            mine.extend(segments)
    return median(per_step(c, mine)) / 1e6 if mine else None


def percentile(values, q: float):
    """Linear interpolation between order statistics, as numpy's default;
    None of nothing."""
    v = sorted(values)
    if not v:
        return None
    at = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(at), math.ceil(at)
    return v[lo] + (v[hi] - v[lo]) * (at - lo)


def median(values):
    return percentile(values, 50)


def breakdown(trace: dict, top: int = 10, gaps: int = 5) -> dict:
    """The device ops that took most time (their own time, enclosed ops
    taken out, summed over the traced steps) and the longest idle gaps,
    each under the benchmark span that covered most of it."""
    c = chip(trace)
    labels = c.get("labels", {})
    total = collections.Counter()
    for name, segments in self_segments(c["ops"]):
        total[name] += sum(b - a for a, b in segments)
    lo, hi = window(c)
    idle = subtract([[lo, hi]], busy(c))
    idle.sort(key=lambda g: g[0] - g[1])
    named = []
    for a, b in idle[:gaps]:
        cover = collections.Counter()
        for name, s, e in trace["host"]:
            overlap = min(b, e) - max(a, s)
            if overlap > 0:
                cover[name] += overlap
        span = cover.most_common(1)[0][0] if cover else "no_span"
        named.append([span, (b - a) / 1e9])
    return {
        "device_ops": [[f"{n} = {labels[n]}" if n in labels else n, t / 1e9]
                       for n, t in total.most_common(top)],
        "idle_gaps": named,
    }
