"""ElasticState: progress-based elastic training loop driver.

Capability parity: srcs/python/kungfu/python/elastic_state.py:4-79 +
KungFuElasticTrainHook's state re-sync (hooks/elastic.py:46-57) —
  es = ElasticState(max_progress)
  es.register_state(get_state, set_state)   # joiner weight re-sync
  while not es.stopped():
      with es.scope():          # begin(): sync progress + state after resize
          train_one_batch()
          es.end(batch_size)    # progress += n, maybe resize
                                # (es.advance is an alias for es.end)
Stop reasons: 'finished' | 'detached' | 'reload'.

A worker that a reload started also accounts for the pause the reload
cost: `pause_parts` below, from the marks that came with it
(KF_RESIZE_MARKS) and its own span ring, at its first `end()`.

After every membership change begin() (a) adopts the cluster-max progress
via an int-max allreduce and (b) if state callbacks are registered,
broadcasts rank-0's training state over the host plane so joining workers
inherit live weights instead of fresh-initialized ones (the reference
re-broadcasts variables + re-syncs progress in its elastic hook).
"""

from __future__ import annotations

import contextlib
import time
from typing import Callable, Iterable, List, Optional, Tuple

import numpy as np

from kungfu_tpu import api
from kungfu_tpu.base.serialize import pack_leaves as _pack_leaves
from kungfu_tpu.base.serialize import unpack_leaves as _unpack_leaves


# -- a reload's pause, from the marks and one worker's ring ------------------

Interval = Tuple[float, float]

# the marks in the order they are taken: the proposer's call of
# change_cluster, the runner has the Stage, the runner's last old worker
# is gone, the runner spawned this worker
_MARKS = ("t_propose", "t_stage", "t_killed", "t_spawn")
# the new worker's own parts by span name, in the order they claim time:
# a compile inside a broadcast is a compile, an import inside
# `worker.startup` (spawn -> host plane up) an import
_RING_PARTS = (
    ("compile_ms", ("device_plane.compile.",)),
    ("import_ms", ("worker.import",)),
    ("device_plane_ms", (
        "device_plane.bootstrap", "device_plane.distributed_initialize",
        "device_plane.backend_start", "device_plane.compile_cache",
    )),
    ("restore_ms", ("checkpoint.open", "checkpoint.restore")),
    ("broadcast_ms", ("broadcast.", "elastic.sync_state")),
    # spawn -> host plane up, then its join: `worker.start.update` ends in
    # a barrier, where the first worker up waits for the last
    ("startup_ms", ("worker.startup", "worker.parse_config",
                    "worker.peer_init", "worker.start.")),
)


def _union(intervals: Iterable[Interval]) -> List[Interval]:
    """Overlapping and nested intervals merged: sorted, disjoint."""
    out: List[Interval] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def _minus(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """What of the union `a` no interval of the union `b` covers."""
    out: List[Interval] = []
    for lo, hi in a:
        for b_lo, b_hi in b:
            if b_hi <= lo or b_lo >= hi:
                continue
            if b_lo > lo:
                out.append((lo, b_lo))
            lo = max(lo, b_hi)
        if lo < hi:
            out.append((lo, hi))
    return out


def _ms(intervals: Iterable[Interval]) -> float:
    return sum(hi - lo for lo, hi in intervals) * 1e3


def pause_parts(marks: dict, events, now: float,
                first_step_began: Optional[float] = None) -> dict:
    """Where the pause of one reload went, as one new worker saw it.

    `marks` are the wall-clock readings that came with the worker
    (runner/watch.Stage.marks: `t_propose`, `phases_ms`, `mode`,
    `old_size`, `t_stage`, `t_killed`, `t_spawn`, and `version` and
    `new_size` as the worker knows them); `events` its ring as
    `(name, start, seconds, args)` with `start` on the marks' clock;
    `now` the end of its first step, `first_step_began` that step's
    start. A pure function: no clock, no ring, nothing of the benchmark.

    The pause runs from the first mark there is to `now`. Its parts, in
    ms: `agree_ms` (proposer's mark -> the runner has the Stage; inside
    it the old workers' own `wait_config_ms`, `consensus_ms`,
    `on_reload_ms` and `notify_ms`, the last, where the Stage could not
    carry it, what they leave of `agree_ms`), `kill_ms` (-> the last old
    worker is gone), `spawn_ms` (-> this worker spawned); then from the
    ring between the spawn and `now`, spans cut to that window and each
    name's overlapping and nested events merged, not summed:
    `import_ms`, `startup_ms` (`worker.startup` less the imports, and the
    host plane's join, `worker.start.*`, whose barrier waits for the
    slowest new worker), `device_plane_ms`, `restore_ms`, `broadcast_ms`,
    `compile_ms` with `compile_hits` and `compile_misses` (compile
    requests by what the cache did), and `first_step_ms`, what is left of
    the first step. A moment belongs to one part only, by `_RING_PARTS`'
    order. A part
    whose mark is missing reads None, and `unaccounted_ms`, `pause_ms`
    less the parts, holds its time: a part that is not measured is not
    hidden. `unaccounted_largest` names the longest stretch after the
    spawn that no part claims: its ms, the claimed spans that end and
    start it (`after`, `before`) and the other spans of the ring that
    lie in it (`under`). {} where no mark came: a first incarnation."""
    t = [marks.get(k) for k in _MARKS]
    began = next((x for x in t if x is not None), None)
    if began is None:
        return {}
    out = {k: marks.get(k) for k in ("mode", "version", "old_size", "new_size")}
    agree_ms, kill_ms, spawn_ms = (
        None if a is None or b is None else round((b - a) * 1e3, 3)
        for a, b in zip(t, t[1:])
    )
    out["agree_ms"] = agree_ms
    inner = marks.get("phases_ms") or {}
    out.update(inner)
    out.setdefault("notify_ms", None if agree_ms is None else round(
        agree_ms - sum(inner.values()), 3))
    out["kill_ms"], out["spawn_ms"] = kill_ms, spawn_ms

    lo = max(x for x in t if x is not None)
    window = [(lo, now)]
    events = [e for e in events if e[1] + e[2] > lo and e[1] < now]

    def cut(prefixes) -> List[Interval]:
        return _union(
            (max(start, lo), min(start + seconds, now))
            for name, start, seconds, _ in events if name.startswith(prefixes)
        )

    claimed: List[Interval] = []
    ring_ms = {}
    for part, prefixes in _RING_PARTS:
        own = _minus(cut(prefixes), claimed)
        ring_ms[part] = round(_ms(own), 3)
        claimed = _union(claimed + own)
    ring_ms["first_step_ms"] = None
    if first_step_began is not None:
        own = _minus(_union([(max(first_step_began, lo), now)]), claimed)
        ring_ms["first_step_ms"] = round(_ms(own), 3)
        claimed = _union(claimed + own)
    for part in ("import_ms", "startup_ms", "device_plane_ms", "restore_ms",
                 "broadcast_ms", "compile_ms"):
        out[part] = ring_ms[part]
    requests = [e[3] or {} for e in events
                if e[0] == "device_plane.compile.backend"]
    out["compile_hits"] = sum(1 for a in requests if a.get("cache") == "hit")
    out["compile_misses"] = sum(1 for a in requests if a.get("cache") == "miss")
    out["first_step_ms"] = ring_ms["first_step_ms"]
    out["pause_ms"] = round((now - began) * 1e3, 3)
    out["unaccounted_ms"] = round(
        out["pause_ms"]
        - sum(v or 0.0 for v in (agree_ms, kill_ms, spawn_ms))
        - sum(v or 0.0 for v in ring_ms.values()), 3)

    out["unaccounted_largest"] = None
    gaps = _minus(window, claimed)
    if gaps:
        g_lo, g_hi = max(gaps, key=lambda g: g[1] - g[0])
        parts = tuple(p for _, prefixes in _RING_PARTS for p in prefixes)
        named = [e for e in events if e[0].startswith(parts)]
        if first_step_began is not None:
            named.append(("first step", first_step_began, now - first_step_began, None))
        after = max((e for e in named if e[1] + e[2] <= g_lo + 1e-6),
                    key=lambda e: e[1] + e[2], default=None)
        before = min((e for e in named if e[1] >= g_hi - 1e-6),
                     key=lambda e: e[1], default=None)
        under = sorted(
            ((min(e[1] + e[2], g_hi) - max(e[1], g_lo), e[0]) for e in events
             if not e[0].startswith(parts)
             and e[1] < g_hi and e[1] + e[2] > g_lo),
            reverse=True,
        )
        out["unaccounted_largest"] = {
            "ms": round((g_hi - g_lo) * 1e3, 3),
            "after": after[0] if after else None,
            "before": before[0] if before else None,
            "under": list(dict.fromkeys(name for _, name in under))[:4],
        }
    return out


class ElasticState:
    def __init__(self, max_progress: Optional[int] = None, reload_mode: bool = False):
        from kungfu_tpu.peer import get_default_peer

        self.max_progress = max_progress
        self.reload_mode = reload_mode
        self._peer = get_default_peer()
        self.progress = self._peer.config.init_progress
        self._synced = False
        self._stop_reason: Optional[str] = None
        self._get_state: Optional[Callable] = None
        self._set_state: Optional[Callable] = None
        # last checkpoint version this driver saved/restored (stamped
        # onto resize audit records); None until note_checkpoint()
        self._checkpoint_version: Optional[int] = None
        self._on_reload: Optional[Callable[[int], None]] = None
        # the marks of the reload that started this worker, until its
        # first end() has made the pause's parts of them; {} otherwise
        self._marks = dict(self._peer.config.resize_marks)
        self._first_step_began: Optional[float] = None

    def on_reload(self, fn: Callable[[int], None]) -> None:
        """`fn(progress)` runs on every worker once a reload is agreed and
        before the runners hear of it (they stop their workers as soon as
        they do): the place to save what the next incarnation restores.
        Its time is inside the pause, as `on_reload_ms`."""
        self._on_reload = fn

    def _note_pause(self) -> None:
        """The first step of this incarnation has ended: record
        `resize.pause` and leave the pause's parts where
        `api.last_resize_phases()` reads them."""
        from kungfu_tpu.telemetry import tracing as trace

        now = time.time()
        to_wall = now - time.perf_counter()  # the ring's clock -> the marks'
        marks = dict(
            self._marks,
            version=self._peer.cluster_version,
            new_size=self._peer.size,
        )
        self._marks = {}
        parts = pause_parts(
            marks,
            [(e.name, e.start + to_wall, e.duration, e.args)
             for e in trace.full_events() if e.phase == "X"],
            now,
            self._first_step_began,
        )
        trace.record(
            "resize.pause", parts["pause_ms"] / 1e3,
            **{k: parts[k] for k in ("mode", "version", "old_size", "new_size")},
        )
        self._peer.last_resize_phases = parts

    def note_checkpoint(self, version: int) -> None:
        """Tell the elastic driver which checkpoint version now covers
        `progress` — recorded on the next resize's audit entry."""
        self._checkpoint_version = int(version)

    def register_state(self, get_state: Callable, set_state: Callable) -> None:
        """Register training-state callbacks for joiner re-sync.

        get_state() -> pytree of arrays (params + optimizer state);
        set_state(pytree) installs the received values. Called only after
        membership changes, never in the steady-state step path.
        """
        self._get_state = get_state
        self._set_state = set_state

    def _sync_state(self) -> None:
        if self._get_state is None:
            return
        from kungfu_tpu.telemetry import tracing as trace

        with trace.span("elastic.sync_state"):
            self._sync_state_traced()

    def _sync_state_traced(self) -> None:
        import jax

        from kungfu_tpu.base.ops import ReduceOp
        from kungfu_tpu.base.workspace import Workspace

        sess = self._peer.current_session()
        if sess.size == 1:
            return
        # Pick a provably SURVIVING broadcast root: the new cluster's order
        # comes verbatim from the user's config PUT, so rank 0 may be a
        # fresh joiner whose state must never overwrite the survivors'.
        # Each peer votes (its rank if it lived through a previous epoch);
        # the min survivor rank becomes the root. Two more scalars ride the
        # same vote: the joiner count (a pure shrink has none -> skip the
        # broadcast entirely) gated by the MIN below.
        big = np.int64(1 << 30)
        survivor = self._peer.epoch_count > 1
        v = f"v{self._peer.cluster_version}"
        root_in = np.array([sess.rank if survivor else big], np.int64)
        root_out = np.zeros(1, np.int64)
        sess.all_reduce(
            Workspace(root_in, root_out, ReduceOp.MIN, f"kungfu::syncroot:{v}")
        )
        fresh_in = np.array([0 if survivor else 1], np.int64)
        fresh_out = np.zeros(1, np.int64)
        sess.all_reduce(
            Workspace(fresh_in, fresh_out, ReduceOp.SUM, f"kungfu::syncfresh:{v}")
        )
        n_fresh = int(fresh_out[0])
        if n_fresh == 0:
            return  # pure shrink: survivors are already in sync
        # fresh world (startup / reload): root 0 = initializer broadcast
        root = int(root_out[0]) if root_out[0] < big else 0
        tree = self._get_state()
        leaves, treedef = jax.tree.flatten(tree)
        blob = _pack_leaves(leaves) if sess.rank == root else b""
        got = sess.broadcast_bytes(blob, f"kungfu::statesync:{v}", root=root)
        if sess.rank != root and self._set_state is not None:
            new_leaves = _unpack_leaves(got, len(leaves))
            new_leaves = [
                np.asarray(nl).astype(np.asarray(ol).dtype).reshape(np.shape(ol))
                for nl, ol in zip(new_leaves, leaves)
            ]
            self._set_state(jax.tree.unflatten(treedef, new_leaves))

    def begin(self) -> None:
        if self._marks and self._first_step_began is None:
            self._first_step_began = time.time()
        if not self._synced:
            # after a membership change, everyone adopts the max progress
            # and rank-0's live training state
            self.progress = api.all_reduce_int_max(self.progress)
            self._sync_state()
            self._synced = True

    def end(self, delta: int = 1) -> None:
        """The step is over: count it, and resize if one is due. The
        caller has awaited its step (`jax.block_until_ready`) before
        this: the first `end()` of a worker that a reload started closes
        that reload's pause here, at entry, before the poll that belongs
        to the next resize."""
        if self._marks:
            self._note_pause()
        self.progress += delta
        if self.max_progress is not None and self.progress >= self.max_progress:
            self._stop_reason = "finished"
            return
        if self.reload_mode:
            hook = self._on_reload
            changed, _ = api.change_cluster(
                self.progress,
                before_notify=hook and (lambda: hook(self.progress)),
            )
            if changed:
                self._stop_reason = "reload"
            return
        changed, detached = api.resize()
        if changed:
            # the resize audit record was written deep in the peer
            # protocol; only the elastic driver knows the training
            # progress (and checkpoint version) it happened at
            from kungfu_tpu.telemetry import audit

            audit.annotate_last(
                peer=str(self._peer.self_id),
                progress=self.progress,
                checkpoint_version=self._checkpoint_version,
            )
        if detached:
            self._stop_reason = "detached"
        elif changed:
            self._synced = False

    advance = end  # documented alias

    @contextlib.contextmanager
    def scope(self):
        self.begin()
        yield

    def stopped(self) -> bool:
        return self._stop_reason is not None

    @property
    def stop_reason(self) -> Optional[str]:
        return self._stop_reason
