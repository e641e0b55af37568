"""Async collective scheduler: readiness-ordered, backprop-overlapped
group allreduce (ISSUE 10 tentpole).

The synchronous step loop launches `group_all_reduce` at step end, so
the engine idles through the whole backprop and then burns a serial
walk (PR 4-5, CPU loopback: the bert walk's 43s→27s came entirely from
engine work, none from overlap; PERF.md, history). The reference's L4
NCCL scheduler (PAPER.md §1) orders collectives by gradient readiness
and overlaps them with backprop; arXiv:1810.11112 measures that overlap
as the dominant scale lever. This is the host-plane equivalent:

- callers :meth:`~CollectiveScheduler.submit` one workspace per tensor
  as its gradient becomes ready and :meth:`~CollectiveScheduler.flush`
  once per step;
- a background launcher assembles the SAME deterministic buckets the
  fused pipeline builds (pipeline.py `_make_buckets`, driven by
  ``KF_CONFIG_GROUP_BUCKET_BYTES``/``KF_CONFIG_GROUP_FUSE_MIN``) and
  launches each bucket's pack → walk → unpack as soon as its members
  arrived — while the caller is still producing later gradients.

**Ordering guarantee.** Readiness order is local (peers' backprops
interleave differently), but peers must walk identical bucket
sequences. So the launch order is negotiated ONCE per session epoch:
the first round's submission order (shaped by the optional ``priority``
argument) becomes the **registered tensor order**, the bucket plan is
derived from it exactly like the synchronous path, and a consensus
assert (the `check_knob_consensus` machinery: `_bytes_agree` over the
knob-independent star walk) verifies every peer registered the
identical ordered set — a diverging peer raises a named RuntimeError
instead of deadlocking on mismatched rendezvous names. After
registration, submissions may arrive in ANY order; buckets launch in
registered order as they complete, with walk names stamped by a round
counter so back-to-back rounds can never collide on the wire.

**Results are bit-identical to the synchronous path**: same bucket
membership, same pack layout, same walk engine, same unpack — only the
launch *time* moves (asserted by tests/test_scheduler.py at
np ∈ {2,3,4} on exact payloads under out-of-order submission).

**Epoch lifecycle.** The scheduler lives exactly as long as its
session: `Peer._update_to` calls `HostSession.close()` before swapping
sessions, which drains in-flight buckets (bounded) and cancels the
rest, so nothing from the old epoch keeps walking — or writing caller
buffers — once the new session exists. Adaptive votes apply at bucket
boundaries by construction: walks launch one at a time from the walker
thread and re-read the active (strategy, wire) candidate per workspace,
and every vote runs at a step boundary (after `flush()`), when no
bucket is in flight.

**Sharded (ZeRO-1) units** (ISSUE 11). A submission carrying a
``handler`` (a :class:`~kungfu_tpu.collective.zero.ShardedUpdateSession`)
registers as a *sharded* tensor: its buckets run
reduce-scatter → shard-optimizer-update → weight-all-gather → scatter
instead of allreduce → unpack, driven across a 4-stage pipeline
(launcher packs, walker reduce-scatters and updates, a dedicated
gatherer walks the weight all-gather, the unpacker scatters weights).
Completion splits in two: ``flush()`` returns once every sharded
bucket's SHARD has updated (gradients consumed — the step barrier),
while weight all-gathers keep walking and overlap the caller's
next-step compute; :meth:`~CollectiveScheduler.wait_gather` is the
barrier for those (call it before the next forward consumes the
params). The submission kind is part of the registered identity and the
registration consensus, and sharded walk names carry their own
round-stamped wire names (``:zrs:r{n}`` / ``:zag:r{n}``), so sharded
and allreduce traffic of adjacent rounds can never collide.

Telemetry: `kungfu_scheduler_queued_buckets` /
`kungfu_scheduler_overlap_seconds_total` /
`kungfu_scheduler_flush_wait_seconds` plus `sched.pack` / `sched.walk`
/ `sched.gather` / `sched.unpack` / `sched.flush` spans
(docs/telemetry.md).
"""

from __future__ import annotations

import threading
import time
import weakref
from typing import Dict, List, Optional, Tuple

import numpy as np

from kungfu_tpu import knobs
from kungfu_tpu.base.workspace import Workspace
from kungfu_tpu.telemetry import config as tconfig
from kungfu_tpu.telemetry import metrics as tmetrics
from kungfu_tpu.telemetry import steptrace
from kungfu_tpu.telemetry import tracing as trace
from kungfu_tpu.utils.handoff import HandoffQueue
from kungfu_tpu.utils.stall import stall_detect

# kfcheck KF303: every thread this module starts must be declared here
# (the abort-protocol joinable set) — close() joins exactly these, so a
# future stage cannot silently outlive a session epoch.
_KF_JOINABLE_THREADS = (
    "kf-sched-launch", "kf-sched-walk", "kf-sched-gather", "kf-sched-unpack",
)

# registered-tensor identity: rendezvous-relevant properties only (the
# consensus digest is built from these, so any cross-peer divergence in
# name, length, dtype, op or submission KIND — "ar" allreduce vs "zero"
# sharded-update, which walk entirely different dataflows — is caught
# at registration)
_Key = Tuple[str, int, str, int, str]


def _key_of(w: Workspace, kind: str = "ar") -> _Key:
    return (w.name, int(w.send.size), w.send.dtype.str, int(w.op), kind)


class SchedulerClosed(RuntimeError):
    """Raised by submit/flush after the session epoch ended (resize or
    explicit close): the caller must fetch the NEW session's scheduler."""


class _Unit:
    """One launch unit of the negotiated plan: a fused allreduce bucket
    (>= the fusion threshold, same dtype/op, <= the bucket byte cap), a
    single workspace, or a sharded-update (ZeRO-1) bucket whose layout
    the registered handler owns. Derived purely from the registered
    order, the cluster-agreed knobs and the handler's deterministic
    bucket layout, so every peer computes the identical plan."""

    __slots__ = ("index", "keys", "fused", "kind", "zindex")

    def __init__(self, index: int, keys: List[_Key], fused: bool,
                 kind: str = "ar", zindex: int = -1):
        self.index = index
        self.keys = keys
        self.fused = fused
        self.kind = kind  # "ar" | "zero"
        self.zindex = zindex  # handler bucket index for zero units


class CollectiveScheduler:
    """Per-session background scheduler for asynchronous group
    allreduce. Thread-safe submit; one flush caller per round."""

    def __init__(self, sess):
        self.sess = sess
        self.queue_depth = max(1, int(knobs.get("KF_CONFIG_ASYNC_QUEUE")))
        # step plane (ISSUE 13): the session epoch every timeline and
        # step-stamped span carries — the CLUSTER version, identical on
        # every peer of the epoch, so the aggregator can group timelines
        # cross-peer (a local session counter would diverge for joiners)
        self.epoch_id = int(getattr(sess, "cluster_version", 0))
        # current round's step recorder (None: sampled out / round 0 /
        # plane off); per-unit metadata derived from the plan so lanes
        # can be labelled without touching workspaces off-thread
        self._steprec: Optional[steptrace.StepRecorder] = None
        self._key_unit: Dict[_Key, int] = {}
        self._unit_meta: Dict[int, Tuple[str, str, int, int]] = {}
        self._cond = threading.Condition()
        self._abort = threading.Event()
        self._errors: List[BaseException] = []
        self._closed = False
        # registration (per session epoch, negotiated at first flush)
        self._registry: Optional[List[_Key]] = None
        self._known: set = set()
        self._plan: List[_Unit] = []
        # (prio, seq, workspace, kind) of pre-registration submissions
        self._first_round: List[Tuple[int, int, Workspace, str]] = []
        # the sharded-update handler (ZeRO-1): one per scheduler epoch,
        # bound by the first submit that carries it; owns the sharded
        # buckets' layout, buffers and optimizer state
        self._handler = None
        # per-round state (all under _cond)
        self._round = 0
        self._pending: Dict[_Key, Workspace] = {}
        self._submitted: set = set()
        self._next_unit = 0
        # flush barrier: units whose GRADIENT work finished this round —
        # allreduce units at unpack, sharded units once their shard
        # updated (their weight all-gather keeps walking past flush)
        self._grad_done = 0
        # sharded units whose weight all-gather + scatter has not landed
        # yet (spans round boundaries; wait_gather's barrier)
        self._gather_outstanding = 0
        self._busy_s = 0.0  # pack+walk+gather+unpack seconds this round
        self._queued = 0  # units packed but not yet unpacked (gauge)
        self._inflight_bytes = 0  # payload bytes of those queued units
        # lifetime stats (for the bench OVERLAP report)
        self._stat = {
            "rounds": 0, "units": 0, "buckets": 0, "zero_units": 0,
            "flush_wait_s": 0.0, "busy_s": 0.0, "overlap_s": 0.0,
        }
        self._threads: List[threading.Thread] = []
        self._walkq = HandoffQueue(maxsize=self.queue_depth, abort=self._abort)
        self._gatherq = HandoffQueue(maxsize=1, abort=self._abort)
        self._unpackq = HandoffQueue(maxsize=1, abort=self._abort)
        if tconfig.metrics_enabled():
            self._queued_gauge = tmetrics.gauge(
                "kungfu_scheduler_queued_buckets",
                "Async-scheduler launch units currently packed or "
                "walking (not yet unpacked)",
            )
            self._overlap_ctr = tmetrics.counter(
                "kungfu_scheduler_overlap_seconds_total",
                "Scheduler engine-busy seconds that overlapped caller "
                "compute (busy time minus flush wait, per round)",
            )
            self._flush_wait_ctr = tmetrics.counter(
                "kungfu_scheduler_flush_wait_seconds",
                "Seconds flush() blocked waiting for in-flight buckets",
            )
        else:
            self._queued_gauge = None
            self._overlap_ctr = None
            self._flush_wait_ctr = None
        # memory plane (ISSUE 17): in-flight unit payloads are the
        # scheduler's share of RSS. Weakref — the registry must never
        # pin a closed scheduler epoch past its resize.
        try:
            from kungfu_tpu.telemetry import memory as _tmem

            def _acct(ref=weakref.ref(self)) -> Optional[int]:
                sched = ref()
                return (
                    sched.inflight_bytes() if sched is not None else None
                )

            _tmem.register_accountant(
                f"scheduler:e{self.epoch_id}", "sched_inflight", _acct
            )
        # kfcheck: disable=KF400 — byte accounting is best-effort;
        # it must never kill the engine
        except Exception:  # noqa: BLE001
            pass

    def inflight_bytes(self) -> int:
        """Payload bytes of units packed but not yet unpacked (the
        memory plane's `sched_inflight` bucket)."""
        with self._cond:
            return self._inflight_bytes

    def _unit_nbytes(self, unit) -> int:
        meta = self._unit_meta.get(unit.index)
        return meta[2] if meta else 0

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def submit(self, w: Workspace, priority: Optional[int] = None,
               handler=None) -> None:
        """Hand one tensor's workspace to the scheduler as it becomes
        ready. Thread-safe; returns immediately (the walk happens on the
        scheduler threads). `w.recv` must stay valid until the round's
        `flush()` returns, and `w.name` must be STABLE across rounds —
        it is this tensor's registered identity (the scheduler stamps
        its own round counter into wire names).

        `priority` shapes the negotiated launch order during the FIRST
        round only (lower launches earlier, default = arrival order);
        after registration the cluster-wide registered order governs and
        the argument is ignored.

        `handler` (a ShardedUpdateSession) marks this tensor as a
        sharded-update (ZeRO-1) gradient: its bucket runs
        reduce-scatter → shard update → weight all-gather instead of an
        allreduce, and `w.recv` is NOT written (the deliverable is the
        updated params, scattered by the handler). The kind is part of
        the registered identity — pass the handler on EVERY submit of a
        sharded tensor."""
        if w.is_empty:
            return
        kind = "ar" if handler is None else "zero"
        key = _key_of(w, kind)
        with self._cond:
            self._raise_if_dead_locked()
            if handler is not None:
                if self._handler is None:
                    self._handler = handler
                elif self._handler is not handler:
                    raise ValueError(
                        "a scheduler epoch supports ONE sharded-update "
                        "handler — rebuild the ShardedUpdateSession "
                        "instead of mixing two"
                    )
            if self._registry is None:
                seq = len(self._first_round)
                prio = seq if priority is None else int(priority)
                self._first_round.append((prio, seq, w, kind))
                return
            if key not in self._known:
                raise ValueError(
                    f"submit of unregistered tensor {key[0]!r} "
                    f"(size={key[1]}, dtype={key[2]}, op={key[3]}, "
                    f"kind={key[4]}) — the registered set is negotiated "
                    "at the first flush and fixed for the session epoch; "
                    "resize to change it"
                )
            if key in self._submitted:
                raise ValueError(
                    f"tensor {key[0]!r} submitted twice in round "
                    f"{self._round} — call flush() between rounds"
                )
            self._submitted.add(key)
            self._pending[key] = w
            # step plane: the round's recorder begins at its FIRST
            # submission (subject to KF_TELEMETRY_SPAN_SAMPLE — a
            # sampled-out round allocates nothing and every note below
            # is a no-op via the None guard)
            if len(self._submitted) == 1 and self._plan:
                self._steprec = steptrace.get_store().begin_step(
                    self.epoch_id, self._round
                )
            rec = self._steprec
            if rec is not None:
                ui = self._key_unit.get(key)
                if ui is not None:
                    kind, label, nbytes, nmem = self._unit_meta[ui]
                    rec.bucket(ui, kind, label, nbytes, nmem).note_submit(
                        time.perf_counter() * 1e6
                    )
            self._cond.notify_all()

    def flush(self, timeout: Optional[float] = None) -> None:
        """Block until every workspace submitted this round has been
        reduced and scattered back (`w.recv` holds the result), then
        advance the round. Re-raises the scheduler's REAL error (walk
        failure, abort) if one occurred. The first flush of a session
        epoch performs the registration handshake (see module doc)."""
        t0 = time.perf_counter()
        with trace.span("sched.flush"), stall_detect("scheduler.flush"):
            with self._cond:
                # a dead handle reports its real state even on would-be
                # no-op flushes: a cleanly-flushed round followed by a
                # resize must surface SchedulerClosed, not silence
                self._raise_if_dead_locked()
                if self._registry is None and not self._first_round:
                    # nothing was ever submitted: a defensive flush must
                    # NOT register an empty set (that would freeze the
                    # epoch's registry as {} and poison every later
                    # submit) — true no-op
                    return
                if self._registry is not None and not self._submitted:
                    # clean round boundary, zero submissions: no-op —
                    # "every registered tensor exactly once per round"
                    # applies to rounds, and an empty flush isn't one
                    return
            if self._registry is None:
                self._register()
            with self._cond:
                # a dead scheduler reports its REAL state (error /
                # closed epoch) before complaining about round shape
                self._raise_if_dead_locked()
                missing = self._known - self._submitted
                if missing:
                    names = sorted(k[0] for k in missing)[:8]
                    raise RuntimeError(
                        f"flush() with {len(missing)} registered tensors "
                        f"not submitted this round (e.g. {names}) — every "
                        "registered tensor must be submitted exactly once "
                        "per round"
                    )
            if timeout is None:
                timeout = self.sess.timeout * max(1, len(self._plan))
            deadline = time.monotonic() + timeout
            with self._cond:
                while True:
                    if self._errors:
                        raise self._errors[0]
                    if self._closed:
                        raise SchedulerClosed(
                            "collective scheduler closed (session epoch "
                            "ended) during flush"
                        )
                    if self._grad_done >= len(self._plan):
                        break
                    if time.monotonic() >= deadline:
                        self._abort.set()
                        raise TimeoutError(
                            f"scheduler flush timed out: "
                            f"{self._grad_done}/{len(self._plan)} units "
                            f"done in round {self._round}"
                        )
                    self._cond.wait(0.2)
                # advance the round (sharded units' weight all-gathers
                # may still be walking — wait_gather is their barrier;
                # round-stamped wire names keep them collision-free)
                wait = time.perf_counter() - t0
                busy = self._busy_s
                self._round += 1
                self._pending.clear()
                self._submitted.clear()
                self._next_unit = 0
                self._grad_done = 0
                self._busy_s = 0.0
                self._stat["rounds"] += 1
                self._stat["flush_wait_s"] += wait
                self._stat["busy_s"] += busy
                self._stat["overlap_s"] += max(0.0, busy - wait)
                # seal the step timeline (the ring holds the recorder,
                # so a ZeRO gather tail landing after this still writes
                # its lane — rendered at export time)
                rec, self._steprec = self._steprec, None
                if rec is not None:
                    rec.finish(flush_wait_s=wait, busy_s=busy)
                self._cond.notify_all()
        if self._flush_wait_ctr is not None:
            self._flush_wait_ctr.inc(wait)
        if self._overlap_ctr is not None:
            self._overlap_ctr.inc(max(0.0, busy - wait))

    def round_index(self) -> int:
        """The current (not-yet-flushed) round number. A submission
        made now belongs to this round; pair it with
        :meth:`flush_round`."""
        with self._cond:
            return self._round

    def flush_round(self, round_index: Optional[int],
                    timeout: Optional[float] = None) -> None:
        """Flush only if round `round_index` has not been flushed yet —
        the idempotent form behind AsyncGroupResult.wait(): several
        handles of one round each call this, the first flushes, the
        rest observe the advanced round and return. `None` flushes
        unconditionally."""
        if round_index is not None:
            with self._cond:
                if self._round > round_index:
                    return
        self.flush(timeout=timeout)

    def wait_gather(self, timeout: Optional[float] = None) -> None:
        """Barrier for the sharded units' weight all-gathers (ISSUE 11):
        block until every in-flight gather has walked and its weights
        have been scattered back. ``flush()`` deliberately does NOT wait
        for these — they overlap the caller's next-step compute the way
        gradient buckets overlap backward — so call this before the
        next forward consumes the params. No-op when nothing sharded is
        in flight; re-raises the scheduler's real error like flush."""
        if timeout is None:
            timeout = self.sess.timeout * max(1, len(self._plan))
        deadline = time.monotonic() + timeout
        with trace.span("sched.wait_gather"), stall_detect("scheduler.wait_gather"):
            with self._cond:
                while True:
                    if self._errors:
                        raise self._errors[0]
                    if self._gather_outstanding == 0:
                        return
                    if self._closed:
                        raise SchedulerClosed(
                            "collective scheduler closed (session epoch "
                            "ended) with weight all-gathers in flight — "
                            "the resize drained or cancelled them; "
                            "restore params via the elastic state sync"
                        )
                    if time.monotonic() >= deadline:
                        self._abort.set()
                        raise TimeoutError(
                            f"wait_gather timed out with "
                            f"{self._gather_outstanding} weight "
                            "all-gathers in flight"
                        )
                    self._cond.wait(0.2)

    def stats(self) -> dict:
        """Lifetime scheduler stats (bench OVERLAP report): rounds,
        units/buckets walked, flush-wait vs engine-busy seconds and the
        overlapped share."""
        with self._cond:
            out = dict(self._stat)
        busy = out["busy_s"]
        out["overlap_frac"] = out["overlap_s"] / busy if busy > 0 else 0.0
        return out

    def close(self, timeout: float = 30.0) -> None:
        """End the scheduler: drain in-flight units (bounded by
        `timeout`), cancel everything not yet launched, join the worker
        threads. Idempotent; called by `HostSession.close()` on every
        session swap (elastic resize) and at peer stop. Pending
        workspaces that never launched are dropped — the new epoch's
        caller resubmits against the new session."""
        with self._cond:
            if self._closed:
                started = False
            else:
                self._closed = True
                started = bool(self._threads)
            self._cond.notify_all()
        if not started:
            return
        deadline = time.monotonic() + max(1.0, timeout)
        for t in self._threads:
            t.join(max(0.1, deadline - time.monotonic()))
        if any(t.is_alive() for t in self._threads):
            # drain exceeded its budget: hard-cancel (in-flight walks
            # observe the abort before mutating caller buffers) and give
            # the threads a short grace to unwind
            self._abort.set()
            for t in self._threads:
                t.join(5.0)
        with self._cond:
            self._cond.notify_all()

    # ------------------------------------------------------------------
    # registration (once per session epoch)
    # ------------------------------------------------------------------

    def _register(self) -> None:
        """First flush: freeze the submission order into the registered
        tensor order, consensus-assert it across peers, derive the
        bucket plan, and start the worker threads."""
        with self._cond:
            self._raise_if_dead_locked()
            if self._registry is not None:
                return
            snapshot = list(self._first_round)
            entries = sorted(snapshot, key=lambda e: (e[0], e[1]))
            registry = [_key_of(w, kd) for _, _, w, kd in entries]
            if len(set(registry)) != len(registry):
                dupes = sorted(
                    {k[0] for k in registry if registry.count(k) > 1}
                )[:4]
                raise ValueError(
                    f"duplicate tensors in first round: {dupes} — "
                    "registered names must be unique"
                )
            if any(k[4] == "zero" for k in registry) and self._handler is None:
                raise ValueError(
                    "sharded tensors registered without a sharded-update "
                    "handler — submit them through "
                    "ShardedUpdateSession.submit_grad"
                )
        # consensus OUTSIDE the lock: this runs real collectives on the
        # knob-independent star walk (check_knob_consensus machinery) —
        # the walk must not serialize behind the scheduler's own lock
        digest = ";".join(
            f"{n}:{s}:{d}:{o}:{kd}" for n, s, d, o, kd in registry
        ).encode()
        if not self.sess._bytes_agree(
            digest, ":sched:registry", self.sess._fixed_allreduce
        ):
            raise RuntimeError(
                "async scheduler registration diverged across peers: the "
                "first round's (name, size, dtype, op) submission order "
                "must be identical cluster-wide — it becomes the "
                "negotiated launch order (check tensor naming and "
                "per-rank model divergence)"
            )
        plan = self._build_plan(registry)
        known = set(registry)
        # step-plane lane metadata: pure function of the plan, computed
        # once so the submit hot path only does dict lookups
        key_unit: Dict[_Key, int] = {}
        unit_meta: Dict[int, Tuple[str, str, int, int]] = {}
        for u in plan:
            label = u.keys[0][0]
            if len(u.keys) > 1:
                label += f"+{len(u.keys) - 1}"
            nbytes = sum(
                k[1] * np.dtype(k[2]).itemsize for k in u.keys
            )
            unit_meta[u.index] = (u.kind, label, nbytes, len(u.keys))
            for k in u.keys:
                key_unit[k] = u.index
        with self._cond:
            # validate EVERYTHING before committing any state: raising
            # after self._registry is set but before the threads start
            # would leave a registered scheduler whose flush() waits on
            # workers that do not exist. Submissions that raced into the
            # (unlocked) consensus window are checked against the
            # registry they were not part of — a silently dropped
            # tensor would leave stale recv data behind a clean flush.
            pending: Dict[_Key, Workspace] = {}
            submitted: set = set()
            for _, _, w, kd in snapshot:
                pending[_key_of(w, kd)] = w
                submitted.add(_key_of(w, kd))
            for _, _, w, kd in self._first_round[len(snapshot):]:
                key = _key_of(w, kd)
                if key not in known:
                    raise ValueError(
                        f"tensor {key[0]!r} submitted during the "
                        "registration handshake but absent from the "
                        "negotiated set — quiesce submissions around "
                        "the first flush()"
                    )
                if key in submitted:
                    raise ValueError(
                        f"tensor {key[0]!r} submitted twice in the "
                        "registration round"
                    )
                pending[key] = w
                submitted.add(key)
            self._registry = registry
            self._known = known
            self._plan = plan
            self._key_unit = key_unit
            self._unit_meta = unit_meta
            self._pending.update(pending)
            self._submitted |= submitted
            self._first_round.clear()
            self._start_threads_locked()
            self._cond.notify_all()

    def _build_plan(self, registry: List[_Key]) -> List[_Unit]:
        """The synchronous path's grouping, expressed over registered
        indices: same-(dtype, op) runs of >= FUSE_MIN_TENSORS fuse into
        <= GROUP_BUCKET_BYTES buckets (pipeline._make_buckets' greedy
        order-preserving packing); smaller groups launch as singles.
        Sharded ("zero") tensors instead map onto the handler's OWN
        deterministic bucket layout — the handler holds their persistent
        buffers and shard state, so its layout is authoritative and is
        validated against the registered set. Pure function of
        (registry, cluster-agreed knobs, handler layout) — every peer
        derives the identical plan from the consensus-checked registry.
        Units launch ordered by their first member's registered index
        (deterministic, and readiness-shaped: early-registered =
        early-ready gradients launch first)."""
        sess = self.sess
        groups: Dict[Tuple[str, int], List[_Key]] = {}
        zero_keys: List[_Key] = []
        for key in registry:
            if key[4] == "zero":
                zero_keys.append(key)
            else:
                groups.setdefault((key[2], key[3]), []).append(key)
        units: List[_Unit] = []
        singles: List[_Key] = []
        for members in groups.values():
            if len(members) < sess.FUSE_MIN_TENSORS:
                singles.extend(members)
                continue
            # greedy order-preserving byte-cap packing (mirrors
            # pipeline._make_buckets, over keys instead of workspaces)
            cur: List[_Key] = []
            cur_bytes = 0
            isize = np.dtype(members[0][2]).itemsize
            for key in members:
                nbytes = key[1] * isize
                if cur and cur_bytes + nbytes > sess.GROUP_BUCKET_BYTES:
                    units.append(_Unit(len(units), cur, fused=True))
                    cur, cur_bytes = [], 0
                cur.append(key)
                cur_bytes += nbytes
            if cur:
                units.append(_Unit(len(units), cur, fused=True))
        for key in singles:
            units.append(_Unit(len(units), [key], fused=False))
        if zero_keys:
            for zi, keys in enumerate(self._handler.plan_units(zero_keys)):
                units.append(
                    _Unit(len(units), list(keys), fused=False,
                          kind="zero", zindex=zi)
                )
        pos = {k: i for i, k in enumerate(registry)}
        units.sort(key=lambda u: pos[u.keys[0]])
        for i, u in enumerate(units):
            u.index = i
        return units

    # ------------------------------------------------------------------
    # worker threads (the KF303 joinable set)
    # ------------------------------------------------------------------

    def _start_threads_locked(self) -> None:
        self._spawn_registered("kf-sched-launch", self._launch_loop)
        self._spawn_registered("kf-sched-walk", self._walk_loop)
        self._spawn_registered("kf-sched-gather", self._gather_loop)
        self._spawn_registered("kf-sched-unpack", self._unpack_loop)

    def _spawn_registered(self, name: str, target) -> None:
        """The ONLY place this module may construct a thread (kfcheck
        KF303): the name must be declared in `_KF_JOINABLE_THREADS` and
        the thread lands in `self._threads`, which `close()` joins — so
        a future stage cannot silently outlive the session epoch."""
        t = threading.Thread(target=target, name=name, daemon=True)
        self._threads.append(t)
        t.start()

    def _record_error(self, e: BaseException) -> None:
        with self._cond:
            self._errors.append(e)
            self._cond.notify_all()
        self._abort.set()

    def _raise_if_dead_locked(self) -> None:
        if self._errors:
            raise self._errors[0]
        if self._closed:
            raise SchedulerClosed(
                "collective scheduler closed (session epoch ended) — "
                "fetch the current session's scheduler and resubmit"
            )

    def _claim_next(self):
        """Launcher: block until the next unit in plan order has all its
        members submitted; returns (unit, members) or None to exit
        (close/abort). Launch STRICTLY in registered order — that is the
        cross-peer determinism contract."""
        with self._cond:
            while True:
                if self._abort.is_set():
                    return None
                if self._closed:
                    # drain semantics: stop LAUNCHING; in-flight units
                    # finish downstream
                    return None
                if self._next_unit < len(self._plan):
                    unit = self._plan[self._next_unit]
                    if all(k in self._pending for k in unit.keys):
                        self._next_unit += 1
                        members = [self._pending.pop(k) for k in unit.keys]
                        # the recorder captured here travels WITH the
                        # unit through the stage queues: a ZeRO gather
                        # tail lands after flush advanced the round, and
                        # must still write the round it belongs to
                        return unit, members, self._round, self._steprec
                self._cond.wait(0.2)

    def _launch_loop(self) -> None:
        try:
            while True:
                claimed = self._claim_next()
                if claimed is None:
                    return
                unit, members, rnd, rec = claimed
                lane = (
                    rec.bucket(unit.index, *self._unit_meta[unit.index])
                    if rec is not None else None
                )
                if lane is not None:
                    lane.note_launch(time.perf_counter() * 1e6)
                t0 = time.perf_counter()
                with trace.step_scope(self.epoch_id, rnd):
                    if unit.kind == "zero":
                        with trace.span("sched.pack", unit=unit.index):
                            # the handler packs into its persistent
                            # bucket staging and stamps its own round-
                            # qualified wire names (:zrs:/:zag:)
                            item = self._handler.pack(
                                unit.zindex, members, rnd
                            )
                    elif unit.fused:
                        with trace.span("sched.pack", unit=unit.index):
                            # round-stamped fused name: back-to-back
                            # rounds must not collide on the wire (a
                            # fast peer's round r+1 sends must never be
                            # consumed by a slow peer still walking
                            # round r)
                            item = self.sess._pack_bucket(
                                unit.index, members, name_prefix=f"r{rnd}:"
                            )
                    else:
                        w = members[0]
                        item = (
                            Workspace(
                                send=w.send, recv=w.recv, op=w.op,
                                name=f"{w.name}::as:r{rnd}",
                            ),
                            None, None, members,
                        )
                self._add_busy(
                    time.perf_counter() - t0, queued=+1,
                    nbytes=self._unit_nbytes(unit),
                )
                if not self._walkq.put((unit, lane, rnd, item)):
                    return  # aborted while the queue was full
        except BaseException as e:  # noqa: BLE001 - channeled to flush()
            self._record_error(e)
        finally:
            self._walkq.put(None)

    def _walk_loop(self) -> None:
        try:
            while True:
                got = self._walkq.get()
                if got is None:
                    return
                if self._abort.is_set():
                    continue  # drain to the sentinel
                unit, lane, rnd, item = got
                t0 = time.perf_counter()
                if unit.kind == "zero":
                    with trace.step_scope(self.epoch_id, rnd), \
                            trace.span("sched.walk", unit=unit.index), \
                            steptrace.walk_sink(lane):
                        item = self._handler.reduce_and_update(
                            item, cancel=self._abort
                        )
                    dt = time.perf_counter() - t0
                    if lane is not None:
                        lane.note_walk_span(t0 * 1e6, dt * 1e6)
                    self._add_busy(dt)
                    # the shard is updated: gradients are consumed, so
                    # this unit passes the flush barrier NOW — its
                    # weight all-gather continues downstream and
                    # overlaps the caller's next-step compute
                    with self._cond:
                        self._grad_done += 1
                        self._gather_outstanding += 1
                        self._cond.notify_all()
                    if not self._gatherq.put((unit, lane, rnd, item)):
                        return
                    continue
                with trace.step_scope(self.epoch_id, rnd), \
                        trace.span("sched.walk", unit=unit.index), \
                        steptrace.walk_sink(lane):
                    if unit.fused:
                        deferred = self.sess._allreduce_ws(
                            item[0], cancel=self._abort, defer_decode=True
                        )
                    else:
                        self.sess._allreduce_ws(item[0], cancel=self._abort)
                        deferred = None
                dt = time.perf_counter() - t0
                if lane is not None:
                    lane.note_walk_span(t0 * 1e6, dt * 1e6)
                self._add_busy(dt)
                if not self._gatherq.put((unit, lane, rnd, item + (deferred,))):
                    return
        except BaseException as e:  # noqa: BLE001 - channeled to flush()
            self._record_error(e)
        finally:
            self._gatherq.put(None)

    def _gather_loop(self) -> None:
        """Weight all-gather stage (sharded units only; allreduce units
        pass straight through so the launch→walk→gather→unpack chain
        stays linear and sentinel propagation stays single-producer)."""
        try:
            while True:
                got = self._gatherq.get()
                if got is None:
                    return
                if self._abort.is_set():
                    continue  # drain to the sentinel
                unit, lane, rnd, item = got
                if unit.kind == "zero":
                    t0 = time.perf_counter()
                    with trace.step_scope(self.epoch_id, rnd), \
                            trace.span("sched.gather", unit=unit.index), \
                            steptrace.walk_sink(lane, gather=True):
                        item = self._handler.gather(item, cancel=self._abort)
                    dt = time.perf_counter() - t0
                    if lane is not None:
                        lane.note_gather_span(t0 * 1e6, dt * 1e6)
                    self._add_busy(dt)
                if not self._unpackq.put((unit, lane, rnd, item)):
                    return
        except BaseException as e:  # noqa: BLE001 - channeled to flush()
            self._record_error(e)
        finally:
            self._unpackq.put(None)

    def _unpack_loop(self) -> None:
        try:
            while True:
                got = self._unpackq.get()
                if got is None:
                    return
                if self._abort.is_set():
                    continue  # aborted: must not touch caller buffers
                unit, lane, rnd, item = got
                t0 = time.perf_counter()
                if unit.kind == "zero":
                    with trace.step_scope(self.epoch_id, rnd), \
                            trace.span("sched.unpack", unit=unit.index):
                        self._handler.scatter(item, cancel=self._abort)
                    dt = time.perf_counter() - t0
                    if lane is not None:
                        lane.note_unpack(dt * 1e6)
                    self._add_busy(
                        dt, queued=-1, nbytes=-self._unit_nbytes(unit)
                    )
                    with self._cond:
                        self._gather_outstanding -= 1
                        self._stat["units"] += 1
                        self._stat["zero_units"] += 1
                        self._cond.notify_all()
                    continue
                with trace.step_scope(self.epoch_id, rnd):
                    if unit.fused:
                        with trace.span("sched.unpack", unit=unit.index):
                            self.sess._unpack_bucket(item, self._abort)
                    else:
                        # single: the walk wrote w.recv in place (the
                        # wrapper workspace shares the caller's
                        # buffers); nothing to scatter
                        deferred = item[4]
                        if deferred is not None:
                            deferred.close()
                dt = time.perf_counter() - t0
                if lane is not None:
                    lane.note_unpack(dt * 1e6)
                self._add_busy(
                    dt, queued=-1, nbytes=-self._unit_nbytes(unit)
                )
                with self._cond:
                    self._grad_done += 1
                    self._stat["units"] += 1
                    if unit.fused:
                        self._stat["buckets"] += 1
                    self._cond.notify_all()
        except BaseException as e:  # noqa: BLE001 - channeled to flush()
            self._record_error(e)

    def _add_busy(
        self, seconds: float, queued: int = 0, nbytes: int = 0
    ) -> None:
        with self._cond:
            self._busy_s += seconds
            if queued:
                self._queued += queued
                # in-flight payload accounting rides the same mutation
                # sites (pack=+, unpack=-) so the byte gauge can never
                # drift from the unit gauge
                self._inflight_bytes = max(0, self._inflight_bytes + nbytes)
            q = self._queued
        if queued and self._queued_gauge is not None:
            self._queued_gauge.set(q)
