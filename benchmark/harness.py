"""One cell, measured: the process that holds the chip runs `measure`.

It imports jax, so the parent (`run.py`) never imports this module. The
pieces are plain functions of the cell's files, so the tests drive them at
tiny sizes on the CPU mesh; only `child.py` decides that the devices are a
chip, and `end_to_end.result_line` refuses a record that is not from one.
"""

from __future__ import annotations

import collections
import hashlib
import json
import math
import os
import time

import numpy as np

from benchmark import manifest, trace_reduce

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

WARMUP_STEPS = 3  # after the first step, before the probe
PROBE_STEPS = 6  # five intervals, whose median sets the window's step count
TRACE_STEPS = 20  # what the traced run profiles, after its window
SAMPLE_INDEX = 1 << 20  # the reference sample's place in the seeded stream

# the boundaries of the set-up, in order, as wall-clock times (`time.time()`):
# the parent's, the child's four before `measure`, and `measure`'s own
MARKS = ("t_command", "t_child", "t_joined", "t_backend_0", "t_backend_1",
         "t_world", "t_init", "t_placed", "t_pool", "t_first_0", "t_first_1",
         "t_window")
# the spans of the program's ring that the record carries
SPAN_PREFIXES = ("worker.", "device_plane.", "broadcast.")


class EventCounter:
    """Counts jax.monitoring events by name from its construction on. Every
    compile request, XLA compile or persistent-cache load, raises one
    COMPILE_EVENT, so a difference of two readings counts compilations
    (copied from chip_smoke._jax_events). Beside each duration event's
    count it keeps the sum of the durations and, where JAX raises the
    event as a time span too (`dispatch.log_elapsed_time`: trace, lowering
    and compile-or-load, on `time.time()`), the spans."""

    def __init__(self):
        from jax import monitoring

        self.counts = collections.Counter()
        self.seconds = collections.Counter()
        self.spans = collections.defaultdict(list)
        monitoring.register_event_listener(
            lambda event, **kw: self.counts.update([event]))
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_time_span_listener(
            lambda event, start, end, **kw: self.spans[event].append((start, end)))

    def _duration(self, event, duration, **kw):
        self.counts[event] += 1
        self.seconds[event] += duration

    def __getitem__(self, event: str) -> int:
        return self.counts[event]

    def reading(self) -> tuple:
        """What `since` takes the difference to."""
        return (collections.Counter(self.counts), collections.Counter(self.seconds),
                {event: len(spans) for event, spans in self.spans.items()})

    def since(self, reading: tuple) -> dict:
        """The duration events raised since a reading, by name: how many,
        the sum of their durations, and their time spans merged. A traced
        function that calls jitted ones raises a trace event for each
        inside its own, so the sum counts nested seconds once a level and
        the merged spans once."""
        counts, seconds, spans = reading
        return {event: {"count": self.counts[event] - counts[event],
                        "sum_s": self.seconds[event] - seconds[event],
                        "spans": trace_reduce.union(
                            self.spans[event][spans.get(event, 0):])}
                for event in self.seconds if self.counts[event] > counts[event]}


def ring_spans(to_wall: float) -> list:
    """The complete spans of the program's ring under SPAN_PREFIXES as
    [name, start, end, depth, args], in order of their start. The ring is
    on `perf_counter`; `to_wall` is one reading of `time.time()` less one of
    `perf_counter()`, as `tracing.chrome_trace()`'s metadata pairs them."""
    from kungfu_tpu.telemetry import tracing

    return sorted(
        ([e.name, e.start + to_wall, e.start + e.duration + to_wall, e.depth,
          dict(e.args or {})]
         for e in tracing.full_events()
         if e.phase == "X" and e.name.startswith(SPAN_PREFIXES)),
        key=lambda span: span[1])


def family_of(config: dict):
    return manifest.plugin("families", config["family"])


def load_peaks(kind: str) -> dict:
    """The published peaks of a `device_kind`; an unknown kind is an error."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        chips = json.load(f)["chips"]
    if kind not in chips:
        raise RuntimeError(f"unknown device kind {kind!r}; peaks.json knows "
                           f"{sorted(chips)}")
    return chips[kind]


def require_chips(devices, chips: int) -> dict:
    """The peaks of the devices JAX found; raises unless all are TPUs of a
    known kind and there are at least as many as the cell asks for."""
    for d in devices:
        if d.platform != "tpu":
            raise RuntimeError(
                f"no TPU: JAX found {d.platform} device {d.device_kind!r}; "
                "the benchmark runs on the chip only")
    if len(devices) < chips:
        raise RuntimeError(f"the cell asks for {chips} chips, JAX found "
                           f"{len(devices)}")
    return load_peaks(devices[0].device_kind)


def params_digest(tree) -> bytes:
    import jax

    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.asarray(leaf).tobytes())
    return h.digest()


def run_steps(step, state, opt_state, pool, place, n: int, start: int = 0,
              clock=time.perf_counter):
    """n optimizer steps with one step of look-ahead: dispatch step i + 1,
    then block on the loss of step i, as a logging loop does. The device
    queue is never drained, and the completion times of successive losses
    give the step intervals. Batch `start + i` of the cycled pool feeds
    step i. The three host phases are recorded as spans [name, start, end]
    on `clock`."""
    rec = {"t_start": clock(), "t_done": [], "spans": []}
    spans = rec["spans"]
    losses = []

    def wait_for(loss):
        t = clock()
        loss.block_until_ready()
        done = clock()
        spans.append(["bench.wait", t, done])
        rec["t_done"].append(done)

    for i in range(n):
        t0 = clock()
        batch = place(pool[(start + i) % len(pool)])
        t1 = clock()
        state, opt_state, loss = step(state, opt_state, batch)
        t2 = clock()
        spans.append(["bench.input", t0, t1])
        spans.append(["bench.dispatch", t1, t2])
        if losses:
            wait_for(losses[-1])
        losses.append(loss)
    if losses:
        wait_for(losses[-1])
    rec["losses"] = [float(l) for l in losses]
    return state, opt_state, rec


def intervals(rec: dict) -> list:
    """Seconds between successive step completions."""
    done = rec["t_done"]
    return [b - a for a, b in zip(done, done[1:])]


def steps_for(seconds: float, probe: dict) -> int:
    """The window as a number of steps, fixed before it starts."""
    median = float(np.median(intervals(probe)))
    return max(2, int(math.floor(seconds / median)))


def program_memory(compiled) -> dict:
    """What the step program holds on one device while it runs."""
    m = compiled.memory_analysis()
    out = {
        "argument_bytes": int(m.argument_size_in_bytes),
        "output_bytes": int(m.output_size_in_bytes),
        "temp_bytes": int(m.temp_size_in_bytes),
        "alias_bytes": int(m.alias_size_in_bytes),
    }
    out["total_bytes"] = (out["argument_bytes"] + out["output_bytes"]
                          + out["temp_bytes"] - out["alias_bytes"])
    return out


def relative_error(got, want) -> float:
    """|got - want| / |want| over all leaves as one float32 vector."""
    import jax
    import jax.numpy as jnp

    num = den = 0.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        num += float(jnp.sum(jnp.square(g - w)))
        den += float(jnp.sum(jnp.square(w)))
    return math.sqrt(num / den)


def eqns_of(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it (scan, remat,
    pjit, cond, custom derivatives)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from eqns_of(inner)


def precision_faults(config: dict, head_width: int, jaxpr, state,
                     opt_state) -> list:
    """Where the program keeps or computes less than the configuration
    states, as sentences; empty when it holds to it. The numbers of the
    reference check cannot see these: bfloat16 parameters round to what
    the bfloat16 matmuls see anyway, and at the initial parameters the
    logits are too small for a bfloat16 head to move loss or gradients by
    more than the blocks' own rounding. So they are read off the program:
    every floating leaf of the state and the optimizer's state is of
    `param_dtype`; the loss, and every matmul and reduction with a
    dimension of the head's width (the logits, the softmax's sums, and
    their transposes in the backward pass), are of `head_dtype`. `jaxpr` is
    that of the family's `program_loss_and_grads`."""
    import jax
    import jax.numpy as jnp

    faults = []
    want = jnp.dtype(config["param_dtype"])
    for what, tree in (("state", state), ("optimizer state", opt_state)):
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
            if jnp.issubdtype(leaf.dtype, jnp.floating) and leaf.dtype != want:
                faults.append(f"{what}{jax.tree_util.keystr(path)} is "
                              f"{leaf.dtype}, not {want}")
    head = jnp.dtype(config["head_dtype"])
    if jaxpr.out_avals[0].dtype != head:
        faults.append(f"the loss is {jaxpr.out_avals[0].dtype}, not {head}")
    for eqn in eqns_of(jaxpr.jaxpr):
        name = eqn.primitive.name
        if not (name == "dot_general" or name.startswith(("reduce_", "arg"))):
            continue
        avals = [v.aval for v in (*eqn.invars, *eqn.outvars)
                 if hasattr(v.aval, "shape")]
        if not any(head_width in a.shape for a in avals):
            continue
        low = {str(a.dtype) for a in avals
               if jnp.issubdtype(a.dtype, jnp.floating) and a.dtype != head}
        if low:
            shapes = [tuple(a.shape) for a in avals]
            faults.append(f"{name} over the head's width {shapes} is in "
                          f"{sorted(low)}, not {head}")
    return faults


def reference_check(family, config: dict, seed: int, final_state,
                    opt_state) -> dict:
    """The program's loss and gradients against the plain float32
    reference, on a seeded sample and the seed's initial parameters (made
    here: the run's own were donated to its first step, and where the
    configuration fixes the timed state they were another seed's, so the
    comparison meets weights and ids no run has met), to the family's
    tolerances; and the program's declared precision,
    read from the same traced program and the run's final state, of which
    shapes and types are enough (`jax.ShapeDtypeStruct` trees). Three
    copies of the parameters are alive at the most here: the initial
    state, the program's gradients and the reference's."""
    state = family.init(config, seed)
    sample = family.host_batch(config, seed, SAMPLE_INDEX,
                               family.REFERENCE_SAMPLES)
    traced = family.program_loss_and_grads(config).trace(state, sample)
    faults = precision_faults(config, family.head_width(config), traced.jaxpr,
                              final_state, opt_state)
    loss, grads = traced.lower().compile()(state, sample)
    ref_loss, ref_grads = family.reference_loss_and_grads(config, state, sample)
    del state
    loss, ref_loss = float(loss), float(ref_loss)
    return {
        "loss": loss,
        "reference_loss": ref_loss,
        "loss_error": abs(loss - ref_loss) / abs(ref_loss),
        "grad_error": relative_error(grads, ref_grads),
        "loss_rtol": family.LOSS_RTOL,
        "grad_rtol": family.GRAD_RTOL,
        "precision_faults": faults,
    }


def timed_state(config: dict, traffic: dict, seed: int) -> tuple:
    """What the timed steps train from and on: (the seed of their state,
    [(seed, index) of each host batch of their pool, in the order it is
    cycled]). The run's seed and its batches 0 to `pool` - 1; but where the
    configuration's file writes `timed_state`, that key's `seed` and the
    batches `pool` of that seed's stream, as many as the traffic's `pool`:
    a cell whose step's work follows its weights and ids, as a share's
    grouped matmuls follow its routers' loads, fixes them (PERF.md, PR 67).
    The reference check stays the run's seed's either way."""
    timed = config.get("timed_state")
    if timed is None:
        return seed, [(seed, i) for i in range(traffic["pool"])]
    if len(timed["pool"]) != traffic["pool"]:
        raise ValueError(f"timed_state.pool names {len(timed['pool'])} batches, "
                         f"the traffic's pool is {traffic['pool']}")
    return timed["seed"], [(timed["seed"], i) for i in timed["pool"]]


def measure(cell: dict, mesh, world, peaks: dict, seed: int, seconds: float,
            trace_dir, events: EventCounter, t_command: float,
            marks: dict | None = None) -> dict:
    """Set up, warm up, run the window, check. Returns the run's record;
    every rank of a world computes it, the reporting rank writes it.
    `trace_dir`, where given, makes this the traced run: after the window,
    TRACE_STEPS steps more under `jax.profiler`. `marks` are the child's
    own from before this call (`t_child` to `t_backend_1`); the record's
    `marks` hold them with `t_command` and this function's, each taken
    after a `block_until_ready` on what its phase made, so that
    asynchronous dispatch hands no phase's seconds to the next."""
    import jax

    marks = {"t_command": t_command, **(marks or {}), "t_world": time.time()}
    to_wall = time.time() - time.perf_counter()
    config, traffic = cell["config"], cell["traffic"]
    family = family_of(config)
    factory = manifest.plugin("steps", traffic["step"])
    chips = mesh.devices.size
    samples_per_step = traffic["per_chip_batch"] * chips
    step_fn, init_opt_state = factory.build(family, config, traffic, mesh)

    state_seed, batches = timed_state(config, traffic, seed)
    state = jax.block_until_ready(family.init(config, state_seed))
    marks["t_init"] = time.time()
    state = world.place_state(state, mesh)
    state, opt_state = jax.block_until_ready(
        factory.place(state, init_opt_state(state), mesh))
    marks["t_placed"] = time.time()
    pool = [family.host_batch(config, of, i, samples_per_step)
            for of, i in batches]
    place = manifest.plugin("placements", traffic["placement"]).make(
        mesh, factory.BATCH_AXIS)
    marks["t_pool"] = time.time()

    # the first step: lower, compile (or load from the cache), run
    before_first = events.reading()
    marks["t_first_0"] = time.time()
    t0 = time.perf_counter()
    batch = place(pool[0])
    step = step_fn.lower(state, opt_state, batch).compile()
    state, opt_state, loss = step(state, opt_state, batch)
    first_loss = float(loss)
    first_step_s = time.perf_counter() - t0
    marks["t_first_1"] = time.time()
    first_step_events = events.since(before_first)
    memory = program_memory(step)

    # warm up the one shape, then time a few steps to size the window
    at = 1
    state, opt_state, warm = run_steps(
        step, state, opt_state, pool, place, WARMUP_STEPS, at)
    at += WARMUP_STEPS
    state, opt_state, probe = run_steps(
        step, state, opt_state, pool, place, PROBE_STEPS, at)
    at += PROBE_STEPS
    # The traced run measures the same window first, untraced, and then
    # profiles TRACE_STEPS steps more. Rank 0 alone knows that it traces:
    # the other ranks take its whole count as one window.
    n_traced = TRACE_STEPS if trace_dir else 0
    n = world.agree_steps(steps_for(seconds, probe) + n_traced) - n_traced

    compiles_before = events[COMPILE_EVENT]
    # nothing is switched off for the window: what stops a user's loop (the
    # host's pauses, Python's collector) stops this one
    marks["t_window"] = time.time()
    state, opt_state, window = run_steps(
        step, state, opt_state, pool, place, n, at)
    at += n
    window["compiles"] = events[COMPILE_EVENT] - compiles_before
    traced_window = None
    if trace_dir:
        # The device alone. With the runtime's host events on, the profiler
        # records every one of the 400,000 small `Transpose` calls by which
        # PJRT linearizes a 38.5 MB image batch on the host, and each ResNet
        # step then waits 0.77 s for its input (chip runs, PR 23); the
        # benchmark's own spans are put on the trace's clock afterwards
        # (trace_reduce.place_spans).
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        compiles_before = events[COMPILE_EVENT]
        try:
            state, opt_state, traced_window = run_steps(
                step, state, opt_state, pool, place, n_traced, at)
        finally:
            jax.profiler.stop_trace()
        window["compiles"] += events[COMPILE_EVENT] - compiles_before
    # which scope of the program each device op belongs to, for the
    # per-layer metrics that split the traced steps by it
    scopes = trace_reduce.scope_table(step.as_text()) if trace_dir else None
    # the program's own spans of the launch and the placement, read once,
    # after the window: nothing timed pays for the reading
    spans = ring_spans(to_wall)

    # checks, outside the window
    before = [first_loss] + warm["losses"] + probe["losses"]
    k = min(len(pool), len(before), n)
    losses = window["losses"] + (traced_window["losses"] if traced_window else [])
    failed = sum(1 for l in losses if not math.isfinite(l))
    # one pass over the pool at the start against one at the end
    loss_passes = [float(np.mean(before[:k])), float(np.mean(losses[-k:]))]
    checks = {
        "no_compile_in_window": window["compiles"] == 0,
        "no_step_failed": failed == 0,
        "loss_fell": loss_passes[1] < loss_passes[0],
        "state_spans_mesh": all(len(l.sharding.device_set) == chips
                                for l in jax.tree.leaves(state)),
        "one_process_a_worker": jax.process_count() == world.size,
        "workers_agree_on_state": world.agree_digest(state),
    }
    # The run's arrays end here. The reference check reads only the shapes
    # and types of the final state and the optimizer's, and holds three
    # copies of the parameters of its own; with these four beside them a
    # configuration that fills the chip in its window could not be checked.
    state, opt_state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), (state, opt_state))
    # The reporting rank alone compares with the reference, on its own
    # chip: in a joined world only rank 0 writes the compile cache, so the
    # other workers would compile both programs again in every run (68 s
    # each for the reference, on three workers; chip run, PR 23).
    reference = None
    if world.rank == 0:
        reference = reference_check(family, config, seed, state, opt_state)
        checks["reference_loss"] = reference["loss_error"] <= reference["loss_rtol"]
        checks["reference_grads"] = reference["grad_error"] <= reference["grad_rtol"]
        checks["declared_precision"] = not reference["precision_faults"]
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    device = jax.devices()[0]
    return {
        "workload": cell["name"],
        "seed": seed,
        "timed_state_seed": state_seed,
        "traced": bool(trace_dir),
        "rank": world.rank,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices())},
        "chips": chips,
        "samples_per_step": samples_per_step,
        "flops_per_sample": family.flops_per_sample(config),
        "peak_flops": peaks["bf16_flops"],
        "t_command": t_command,
        "t_world": marks["t_world"],
        "t_window": marks["t_window"],
        "marks": marks,
        "spans": spans,
        "first_step_s": first_step_s,
        "first_step_events": first_step_events,
        "program_memory": memory,
        "memory_stats_peak_bytes": max(
            (s.get("peak_bytes_in_use") or 0 for s in stats), default=0),
        "window": window,
        "traced_window": traced_window,
        "losses_before": before,
        "loss_passes": loss_passes,
        "probe_intervals_s": intervals(probe),
        "attempted": n + n_traced,
        "failed": failed,
        "checks": checks,
        "correct": all(checks.values()),
        "reference": reference,
        "scopes": scopes,
        "cache": {"hits": events[CACHE_HIT_EVENT],
                  "misses": events[CACHE_MISS_EVENT]},
        "versions": {"jax": jax.__version__},
    }
