"""Host-side collective engine facade: one session per cluster epoch.

Capability parity: srcs/go/kungfu/session/session.go — an immutable
peer-list epoch running Barrier / Consensus / Reduce / Broadcast / Gather /
AllReduce by walking (reduce, bcast) graph pairs, with 1 MiB chunking
striped across multi-root strategies (runStrategies, session.go:301-330)
and SIMD reduction on receive (base.Transform2).

Role in the TPU build: this engine runs on HOSTS over DCN for control
collectives (consensus on cluster configs, barriers, progress sync) and for
CPU-only test clusters — the device data plane is XLA over ICI
(kungfu_tpu.ops). It is the direct replacement for the reference's
rchannel data plane.

Layering (ISSUE 10 refactor — this file is the facade, the engine lives
in sibling modules so the async scheduler composes instead of accretes):

- walks.py     — the walk engines (segmented ring, chunked graph walks)
  and shared receive/accounting plumbing (:class:`WalkEngine` mixin);
- codec.py     — wire-format policy: compress-or-bypass decisions,
  deferred decode (:class:`WireCodec` mixin);
- pipeline.py  — group fusion: deterministic bucketing and the 3-stage
  pack/walk/unpack pipeline (:class:`GroupFusion` mixin);
- profiler.py  — the process-global critical-path profiler and span
  sampler;
- scheduler.py — the async collective scheduler (per-session, lazily
  created; drives the same pack/walk/unpack stages by readiness order).

HostSession owns the per-epoch STATE (peers, strategies, adaptive
candidates, metric handles) and the public collective API; the mixins
own the mechanics.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple, TYPE_CHECKING

import numpy as np

from kungfu_tpu import knobs
from kungfu_tpu.base.ops import ReduceOp
from kungfu_tpu.base.strategy import Strategy
from kungfu_tpu.base.workspace import Workspace
from kungfu_tpu.collective import strategies as st
from kungfu_tpu.collective.adaptive import AdaptiveState
from kungfu_tpu.collective.codec import WIRE_MODES, WireCodec, wire_override
from kungfu_tpu.collective.pipeline import GroupFusion
from kungfu_tpu.collective.profiler import (  # noqa: F401 - back-compat re-exports
    SpanSampler,
    SpanSampler as _SpanSampler,
    WalkProfiler,
    get_walk_profiler,
)
from kungfu_tpu.collective.walks import (  # noqa: F401 - back-compat re-exports
    CHUNK_BYTES,
    DEFAULT_TIMEOUT,
    WalkEngine,
    algo_override,
    choose_chunk_bytes,
    _buf,
)
from kungfu_tpu.plan import replan as rp
from kungfu_tpu.plan import topology as topo
from kungfu_tpu.plan.graph import Graph
from kungfu_tpu.plan.peer import PeerID, PeerList
from kungfu_tpu.telemetry import config as tconfig
from kungfu_tpu.telemetry import link as tlink
from kungfu_tpu.telemetry import metrics as tmetrics
from kungfu_tpu.transport.client import Client
from kungfu_tpu.transport.handlers import CollectiveEndpoint
from kungfu_tpu.transport.message import ConnType
from kungfu_tpu.telemetry import tracing as trace
from kungfu_tpu.utils.handoff import parallel_run as _par
from kungfu_tpu.utils.stall import stall_detect

if TYPE_CHECKING:
    from kungfu_tpu.collective.scheduler import CollectiveScheduler


class _CollectiveScope:
    """Span + latency-histogram wrapper around one public collective
    (plain classes end to end — tracing._Span underneath is also
    class-based — so the per-call telemetry cost stays at two clock
    reads, a deque append and an optional histogram observe)."""

    __slots__ = ("_sess", "_kind", "_span", "_t0", "_prev_kind")

    def __init__(self, sess: "HostSession", kind: str, nbytes: int):
        self._sess = sess
        self._kind = kind
        self._span = trace.span(
            f"collective.{kind}", bytes=int(nbytes), size=sess.size
        )

    def __enter__(self):
        self._t0 = time.perf_counter()
        # label wire-byte counts with the public collective that caused
        # them (walks run on pool threads, so this lives on the session;
        # rare concurrent collectives of different kinds may cross-label
        # a few bytes, which accounting tolerates)
        self._prev_kind = self._sess._wire_kind
        self._sess._wire_kind = self._kind
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        self._sess._wire_kind = self._prev_kind
        hist = self._sess._coll_hist
        if hist is not None:
            hist.labels(self._kind).observe(time.perf_counter() - self._t0)
        return False


class HostSession(WalkEngine, WireCodec, GroupFusion):
    """One collective epoch over a fixed PeerList."""

    def __init__(
        self,
        strategy: Strategy,
        self_id: PeerID,
        peers: PeerList,
        client: Client,
        endpoint: CollectiveEndpoint,
        timeout: float = DEFAULT_TIMEOUT,
        cluster_version: int = 0,
    ):
        rank = peers.rank(self_id)
        if rank is None:
            raise ValueError(f"{self_id} not in peer list {peers}")
        self.self_id = self_id
        # the elastic cluster version this epoch serves (peer.py passes
        # it; 0 for bare sessions) — the step plane's session_epoch
        # stamp, identical on every peer of the epoch by construction
        self.cluster_version = int(cluster_version)
        self.peers = peers
        self.rank = rank
        self.local_rank = peers.local_rank(self_id)
        self.local_size = peers.local_size(self_id)
        self.host_count = peers.host_count()
        self.client = client
        self.endpoint = endpoint
        self.timeout = timeout
        forced = algo_override()
        if forced is not None:
            strategy = forced
        if strategy == Strategy.AUTO:
            strategy = st.auto_select(peers)
        self.strategy = strategy
        self.global_strategies = st.gen_global_strategies(peers, strategy)
        self.local_strategies = st.gen_local_strategies(peers)
        self.cross_strategies = st.gen_cross_strategies(peers, strategy)
        # ring order for the cross-host segmented walk (hierarchical mode)
        self._masters, _ = peers.partition_by_host()
        # per-root star graph cache (satellite: reduce/broadcast with
        # root != 0 regenerated star + default-reduce on every call);
        # sessions are rebuilt each epoch, so invalidation is automatic
        self._root_graphs: Dict[int, Tuple[Graph, Graph]] = {}
        # wire codec knob: resolved once per session epoch like the
        # strategy; the ACTIVE codec can differ when adaptation toggles it
        self.wire_mode = wire_override()
        # async scheduler knob: resolved once per session epoch; the
        # scheduler itself is created lazily on first use (most sessions
        # — control planes, tests — never submit asynchronously)
        self.async_mode = knobs.get("KF_CONFIG_ASYNC")
        # measured-topology re-planning knob (ISSUE 14): resolved once
        # per epoch like the other engine modes; the ADOPTED plan (ring
        # order + segment weights) starts naive and changes only through
        # the lockstep check_replan/adopt_replan rounds below. Cluster-
        # agreed — every peer must run the same re-plan rounds and the
        # plan decides every segmented walk's bounds.
        self.replan_mode = knobs.get("KF_CONFIG_REPLAN")
        self._ring_plan: Optional[rp.RingPlan] = None
        # two-level plan state (ISSUE 19): the adopted HierPlan (None =
        # flat), the cluster-agreed demoted set it carries, and the
        # demotion patience every peer must share (it gates the lockstep
        # demote rounds, so it rides the knob consensus)
        self._hier_plan: Optional[rp.HierPlan] = None
        self._demoted: Tuple[int, ...] = ()
        self.demote_patience = int(knobs.get("KF_REPLAN_DEMOTE_PATIENCE"))
        self._replan_seq = 0
        self._replan_listeners: List[object] = []
        # ZeRO-1 sharded-update knob (ISSUE 11): resolved once per epoch
        # like the strategy/wire/async modes; consulted by the frontends
        # (ShardedUpdateSession, torch ZeroSGDOptimizer, api helpers) to
        # pick sharded vs replicated updates. Cluster-agreed — it decides
        # the step's whole rendezvous dataflow (zrs/zag names vs fused
        # allreduce names), so it rides the knob consensus.
        self.zero_mode = knobs.get("KF_CONFIG_ZERO")
        self._scheduler: Optional["CollectiveScheduler"] = None
        self._scheduler_lock = threading.Lock()
        self._epoch_closed = False
        # adaptive control (parity: session/adaptiveStrategies.go): a
        # deterministic candidate order — identical on every peer — so a
        # majority vote can advance everyone in lockstep. Candidates are
        # (strategy, wire-mode) pairs: the first alternate toggles the
        # CODEC on the same graphs (the cheapest lever against a
        # congested/interfered link — half or restore the wire bytes
        # without re-pairing anyone), then the strategy alternates walk
        # under the configured codec, RING_SEGMENTED first so votes can
        # switch ONTO the bandwidth-optimal member (and off it, by
        # advancing again). Candidate graph lists are built lazily:
        # sessions are rebuilt every elastic epoch and most never adapt.
        wire_toggled = "off" if self.wire_mode != "off" else "bf16"
        self._candidates: List[Tuple[Strategy, str]] = (
            [(strategy, self.wire_mode), (strategy, wire_toggled)]
            + [
                (s, self.wire_mode) for s in (
                    Strategy.RING_SEGMENTED, Strategy.RING,
                    Strategy.BINARY_TREE_STAR, Strategy.STAR, Strategy.CLIQUE,
                ) if s != strategy
            ]
        )
        self._candidates_built: dict = {0: self.global_strategies, 1: self.global_strategies}
        self.adaptive = AdaptiveState(
            len(self._candidates),
            names=[f"{s.name}/{wm}" for s, wm in self._candidates],
        )
        self._tree_override = False
        # per-collective latency histogram (telemetry): one observe per
        # COLLECTIVE call (not per message), gated off with the rest of
        # the metrics so the steady-state walk stays untouched
        self._coll_hist = (
            tmetrics.histogram(
                "kungfu_collective_latency_seconds",
                "Host-plane collective latency by kind",
                ("collective",),
            )
            if tconfig.metrics_enabled()
            else None
        )
        # wire-byte accounting: bytes this peer SENDS into collective
        # walks, by (public collective, executing strategy, wire codec).
        # This is the counter the segmented engine's bandwidth-optimality
        # claim is asserted against (tests) and the A/B bench reports;
        # the codec dimension separates compressed from raw traffic.
        self._wire_ctr = (
            tmetrics.counter(
                "kungfu_collective_wire_bytes_total",
                "Host-plane collective payload bytes sent by this peer",
                ("collective", "strategy", "codec"),
            )
            if tconfig.metrics_enabled()
            else None
        )
        # bytes the codec kept OFF the wire: raw payload minus encoded
        # payload, summed over every compressed send
        self._wire_saved_ctr = (
            tmetrics.counter(
                "kungfu_collective_wire_saved_bytes_total",
                "Wire bytes saved by the collective codec on this peer",
                ("collective", "codec"),
            )
            if tconfig.metrics_enabled()
            else None
        )
        self._wire_kind = "raw"
        # audit dedup for codec bypasses: one event per (reason, dtype)
        # per session epoch, so consensus lanes don't flood the audit log
        self._codec_bypass_seen: set = set()
        # error-feedback residual store of the quantized wire codec
        # (ISSUE 20): per-workspace f32 remainders, flushed on wire-mode
        # changes and re-plan adoption (see WireCodec._flush_residuals);
        # dies with the session on elastic resize — deterministically
        # zero on every peer of the new epoch
        self._ef_store: Dict[str, np.ndarray] = {}
        self._ef_mode: Optional[str] = None
        self._ef_flush_listeners: List[object] = []
        self._unknown_wire_warned: set = set()
        # monotone count of adopted precision flips: names the vote
        # workspaces and stamps the consensus digest of each switch
        self._precision_flips = 0
        # link plane + walk profiler (ISSUE 6): the local link table
        # supplies per-destination bandwidth estimates the profiler
        # scores walks against; the sampler thins per-step spans
        self._links = tlink.get_table() if tlink.enabled() else None
        self._span_sampler = SpanSampler(tconfig.span_sample())
        # graph-fallback audit dedup (ISSUE 14 satellite): while
        # RING_SEGMENTED is active, non-allreduce graph consumers and
        # sub-threshold payloads run the rank-0 binary-tree pair — by
        # design, but previously silent. One audit event per session
        # epoch names the fallback the first time it executes.
        self._segmented_fallback_noted = False
        self._in_fixed_walk = False
        # active-ring observability (ISSUE 14): this peer's position in
        # the current ring order and its successor, exported so the
        # cluster aggregator can reconstruct (and `info links` render)
        # the ACTIVE ring next to the measured matrix
        if tconfig.metrics_enabled():
            self._ring_pos_g = tmetrics.gauge(
                "kungfu_topology_ring_position",
                "This peer's position in the active segmented-ring order "
                "(0-based; equals rank until a measured re-plan lands)",
            )
            self._ring_next_g = tmetrics.gauge(
                "kungfu_topology_ring_next",
                "The active ring successor of this peer (child per dst, "
                "value 1) — the edge every segmented send crosses",
                ("dst",),
            )
            self._replans_ctr = tmetrics.counter(
                "kungfu_topology_replans_total",
                "Measured-topology re-plans adopted by this peer's "
                "session epochs",
            )
            # two-level plan role (ISSUE 19): (level, role) of this peer
            # in the active hierarchy — level `flat` (no hierarchy) or
            # `intra`/`inter` (member vs elected head of the inter-host
            # ring), role `member`/`head`/`demoted`; the VALUE is the
            # peer's host-group index, so the aggregator can reconstruct
            # the full hierarchy like it does the flat ring
            self._ring_role_g = tmetrics.gauge(
                "kungfu_topology_ring_role",
                "Active two-level plan role of this peer (child per "
                "(level, role), value = host-group index)",
                ("level", "role"),
            )
            # active wire precision (ISSUE 20): the RUNNING codec mode
            # (config + lockstep precision/interference votes), exported
            # so `info links` can render what payloads actually cross
            # the transport as
            self._wire_mode_g = tmetrics.gauge(
                "kungfu_collective_wire_mode",
                "Active wire-codec mode of this peer's collective "
                "session (child per mode, value 1 on the running one)",
                ("mode",),
            )
        else:
            self._ring_pos_g = self._ring_next_g = self._replans_ctr = None
            self._ring_role_g = None
            self._wire_mode_g = None
        self._publish_ring_metrics()
        # collective-order sentinel (ISSUE 12): with the debug knob set,
        # protowatch wraps this instance's public entry points at bind
        # time. Unset = the module is never imported and the methods stay
        # the plain class functions — zero hot-path cost (asserted by
        # tests/test_protowatch.py, like lockwatch)
        self._protowatch = None
        if knobs.get("KF_DEBUG_PROTOCOL"):
            from kungfu_tpu.devtools import protowatch

            protowatch.attach(self)

    def _candidate(self, idx: int) -> List[st.StrategyPair]:
        if idx not in self._candidates_built:
            self._candidates_built[idx] = st.gen_global_strategies(
                self.peers, self._candidates[idx][0]
            )
        return self._candidates_built[idx]

    @property
    def size(self) -> int:
        return len(self.peers)

    # ------------------------------------------------------------------
    # async scheduler (ISSUE 10 tentpole)
    # ------------------------------------------------------------------

    def async_enabled(self) -> bool:
        """Whether this epoch runs asynchronous group collectives.
        `auto` resolves to on for multi-peer sessions (a cluster of one
        has nothing to overlap). Cluster-agreed — the mode decides the
        fused rendezvous names, so it rides the knob consensus."""
        if self.async_mode == "on":
            return True
        if self.async_mode == "auto":
            return self.size >= 2
        return False

    def zero_enabled(self) -> bool:
        """Whether this epoch runs the ZeRO-1 sharded weight update
        (ISSUE 11). `auto` resolves to on for multi-peer sessions (a
        cluster of one has nothing to shard). Cluster-agreed — the mode
        decides the step's rendezvous dataflow, so it rides the knob
        consensus like KF_CONFIG_ASYNC.

        The memory plane (ISSUE 17) is CONSULTED here but deliberately
        cannot flip the resolution: `engine_knobs()` carries the mode
        string, not the resolved boolean, so two peers resolving
        `auto` differently from their own live RSS would sail through
        the consensus check and deadlock on mismatched rendezvous
        dataflow. The consult is therefore advisory — when `auto`
        resolves OFF (single peer) while this worker's measured
        headroom sits at/below the pressure line, it logs that sharding
        would have relieved the replicated optimizer state — and the
        BEHAVIOURAL consumer of measured headroom is the rank-0-local
        elastic grow gate (elastic/schedule.py), where a single
        decision maker is safe."""
        if self.zero_mode == "on":
            return True
        if self.zero_mode == "auto":
            on = self.size >= 2
            if not on and not getattr(self, "_zero_mem_advised", False):
                self._zero_mem_advised = True  # one advisory per session
                try:
                    from kungfu_tpu.telemetry import log
                    from kungfu_tpu.telemetry import memory as tmem

                    sig = tmem.get_plane().signals()
                    if sig.get("memory/pressure"):
                        log.warn(
                            "zero=auto resolved off (single peer) under "
                            "measured memory pressure (headroom %.0f%%): "
                            "replicated optimizer state is a candidate — "
                            "grow the cluster or set KF_CONFIG_ZERO=on "
                            "fleet-wide",
                            100.0 * float(sig.get("memory/headroom_frac", 0)),
                        )
                # kfcheck: disable=KF400 — advisory log only; a failed
                # headroom read must never block auto resolution
                except Exception:  # noqa: BLE001
                    pass
            return on
        return False

    def scheduler(self) -> "CollectiveScheduler":
        """The session's async collective scheduler, created on first
        use. Lives exactly as long as the session epoch: Peer._update_to
        calls :meth:`close` (drain) before replacing the session."""
        with self._scheduler_lock:
            if self._scheduler is None:
                from kungfu_tpu.collective.scheduler import (
                    CollectiveScheduler,
                    SchedulerClosed,
                )

                if self._epoch_closed:
                    # a resize already ended this epoch: a fresh
                    # scheduler here would walk against a fenced
                    # transport token — the caller must re-fetch the
                    # CURRENT session
                    raise SchedulerClosed(
                        "session epoch closed — fetch the current "
                        "session's scheduler"
                    )
                self._scheduler = CollectiveScheduler(self)
                if self._protowatch is not None:
                    from kungfu_tpu.devtools import protowatch

                    protowatch.attach_scheduler(self._scheduler)
            return self._scheduler

    def close(self, timeout: Optional[float] = None) -> None:
        """End-of-epoch teardown: drain or cancel the async scheduler's
        in-flight buckets so nothing from this epoch keeps walking (or
        writing caller buffers) once the next session exists."""
        with self._scheduler_lock:
            sched = self._scheduler
            self._scheduler = None
            self._epoch_closed = True
        if sched is not None:
            sched.close(timeout=self.timeout if timeout is None else timeout)

    def _collected(self, kind: str, nbytes: int):
        """Telemetry wrapper for one public collective: a named span
        (feeding /trace) plus a latency-histogram observation when
        metrics are on. Returns a context manager."""
        return _CollectiveScope(self, kind, nbytes)

    # ------------------------------------------------------------------
    # public collectives
    # ------------------------------------------------------------------

    def all_reduce(self, w: Workspace) -> None:
        with self._collected("all_reduce", w.recv.nbytes):
            with stall_detect(f"all_reduce({w.name})"):
                self._allreduce_ws(w)

    def reduce_scatter(
        self, w: Workspace, cancel: Optional[threading.Event] = None
    ) -> Tuple[int, int]:
        """First-class reduce-scatter half of the segmented ring walk
        (ISSUE 11): after it, ``w.recv`` holds the fully reduced OWNED
        segment — whose (begin, end) element bounds are returned — and
        partially reduced garbage elsewhere. The layout is
        :meth:`owned_bounds`: contiguous ``segment_bounds`` slices under
        the CURRENT ring plan (equal, or throughput-weighted after a
        measured re-plan — ISSUE 14), identical on every peer without
        negotiation. Always raw f32-exact ((k-1)/k·N bytes per peer);
        k == 1 (and empty payloads) degrade to ``forward()`` with the
        whole array owned. Runs the ring regardless of payload size —
        an explicit RS is a deliberate choice, not a heuristic."""
        with self._collected("reduce_scatter", w.recv.nbytes):
            with stall_detect(f"reduce_scatter({w.name})"):
                self._run_segmented(w, cancel=cancel, phase="rs")
        return self.owned_bounds(w.recv.size)

    def all_gather_shards(
        self,
        full: np.ndarray,
        name: str,
        cancel: Optional[threading.Event] = None,
        allow_wire: bool = True,
        ef: Optional[np.ndarray] = None,
    ) -> None:
        """Standalone segment all-gather (ISSUE 11): the caller placed
        this rank's shard into ``full``'s owned segment
        (``topo.owned_segment_bounds``); the walk relays every segment
        around the ring until ``full`` is complete and identical on all
        peers. The inverse of :meth:`reduce_scatter` — rs + this ==
        all_reduce, bit for bit.

        With the wire codec active (and ``allow_wire``) eligible f32
        payloads cross the transport in the wire dtype — (k-1)/k·N/2
        bytes per peer — with each segment quantized exactly once by its
        owner and decoded once per peer at walk end, so every peer
        (owner included) lands on bit-identical values; see
        docs/collectives.md for the error model.

        ``ef`` (quantized modes only): a caller-owned f32 error-feedback
        residual sized to THIS RANK's owned segment — the send quantizes
        shard+residual and the new residual is written back in place.
        Callers whose shards outlive the walk name (ZeRO's round-stamped
        gathers) pass their per-shard buffer here instead of relying on
        the session's name-keyed store."""
        ws = Workspace(send=full, recv=full, op=ReduceOp.SUM, name=name)
        wire = self._wire_codec_for(ws) if allow_wire else None
        with self._collected("all_gather", full.nbytes):
            with stall_detect(f"all_gather({name})"):
                self._run_segmented(ws, cancel=cancel, wire=wire, phase="ag",
                                    ef_owned=ef)

    def monitored_all_reduce(self, w: Workspace) -> None:
        """AllReduce + throughput accounting for the ACTIVE strategy
        (parity: KungfuMonitoredAllReduce, ops/cpu/collective.cpp:149-196 +
        runMonitoredStrategies, session/monitoring.go:15-35).

        Runs the active candidate's wire format like all_reduce — this
        is the ONLY site feeding adaptive.current, so it MUST measure
        what the candidate actually does or codec candidates would
        accumulate raw-walk stats and interference votes could never
        observe compression. Probe-style traffic keeps exact semantics
        through the codec's own gates: non-f32 lanes and payloads under
        WIRE_MIN_BYTES always bypass (audited), and the gradient-
        variance/noise-scale monitors are on-device psums that never
        touch the host plane at all."""
        nbytes = w.recv.size * w.recv.itemsize
        t0 = time.perf_counter()
        with self._collected("monitored_all_reduce", nbytes):
            with stall_detect(f"monitored_all_reduce({w.name})"):
                self._allreduce_ws(w)
        self.adaptive.current.update(nbytes, time.perf_counter() - t0)

    def check_interference(self, vote_tag: str = "") -> bool:
        """Majority vote on local interference suspicion; on a cluster-wide
        majority every peer advances to the next candidate strategy in the
        same deterministic order. Returns True if the strategy switched.
        Parity: CheckInterference + MonitoredAllReduce consensus switch
        (session/adaptiveStrategies.go:61-121).

        Call this at a step boundary. With the async scheduler active
        the switch lands at a bucket boundary by construction: walks are
        launched one at a time from the scheduler thread and re-read the
        active candidate per workspace, and the flush() barrier that
        ends every round means no bucket of the PREVIOUS round is still
        in flight when the vote's allreduce runs."""
        if self._tree_override or len(self._candidates) < 2:
            return False
        suspect = self.adaptive.current.suspect_interference()
        votes_in = np.array([1 if suspect else 0], np.int32)
        votes_out = np.zeros(1, np.int32)
        self.all_reduce(
            Workspace(votes_in, votes_out, ReduceOp.SUM,
                      f"kungfu::interference:{self.adaptive.switch_count}{vote_tag}")
        )
        if int(votes_out[0]) * 2 <= self.size:
            return False
        old_strategy, old_wire = self._candidates[self.adaptive.active]
        idx = self.adaptive.advance()
        self.global_strategies = self._candidate(idx)
        new_strategy, new_wire = self._candidates[idx]
        # safety: all peers must now run the same graphs AND wire format
        # (a codec split would desync every message size in the walk)
        if not self.bytes_consensus(
            st.digest(self.global_strategies) + new_wire.encode(),
            f":switch:{self.adaptive.switch_count}",
        ):
            raise RuntimeError("strategy switch diverged across peers")
        self._publish_wire_mode()
        from kungfu_tpu.telemetry import audit as _audit

        _audit.record_event(
            "strategy_switch",
            peer=str(self.self_id),
            trigger="interference_vote",
            old_strategy=old_strategy.name,
            new_strategy=new_strategy.name,
            old_wire=old_wire,
            new_wire=new_wire,
            switch_count=self.adaptive.switch_count,
        )
        # decision ledger (ISSUE 15): open the causal record the moment
        # the switch lands — the paired step windows around this point
        # close it with a realized gain and verdict
        from kungfu_tpu.telemetry import decisions as _decisions

        _decisions.open_decision(
            "strategy_switch",
            peer=str(self.self_id),
            epoch=self.cluster_version,
            trigger="interference_vote",
            signals={"votes": int(votes_out[0]), "size": self.size},
            old=f"{old_strategy.name}/{old_wire}",
            new=f"{new_strategy.name}/{new_wire}",
        )
        return True

    def check_precision(
        self,
        proposal: Optional[str] = None,
        trigger: str = "noise_scale",
        signals: Optional[dict] = None,
        vote_tag: str = "",
    ) -> Optional[str]:
        """Majority vote on the wire PRECISION of the active candidate
        (ISSUE 20): every peer calls in lockstep at a step boundary with
        its locally preferred mode (``proposal``; None votes to keep the
        current one), ballots are one-hot over :data:`WIRE_MODES`, and a
        strict cluster majority for a different mode flips the active
        candidate's wire member on EVERY peer — same graphs, new codec.
        Returns the new mode, or None when nothing changed.

        The flip is digest-checked like a strategy switch (a codec split
        would desync every message size in the walk), flushes the
        error-feedback residual store (residuals measure the OLD codec's
        rounding), and opens a ``precision_switch`` decision-ledger
        record so a throughput- or accuracy-hostile downshift closes
        ``regressed`` and the precision policy votes itself back."""
        if proposal is not None and proposal not in WIRE_MODES:
            raise ValueError(
                f"check_precision: unknown wire mode {proposal!r}; "
                f"expected one of {', '.join(WIRE_MODES)}"
            )
        old_mode = self._active_wire_mode()
        want = proposal if proposal is not None else old_mode
        votes_in = np.zeros(len(WIRE_MODES), np.int32)
        votes_in[WIRE_MODES.index(want)] = 1
        votes_out = np.zeros(len(WIRE_MODES), np.int32)
        self.all_reduce(
            Workspace(votes_in, votes_out, ReduceOp.SUM,
                      f"kungfu::precision:{self._precision_flips}{vote_tag}")
        )
        winner = None
        for i, mode in enumerate(WIRE_MODES):
            if mode != old_mode and int(votes_out[i]) * 2 > self.size:
                winner = mode
                break
        if winner is None:
            return None
        self._precision_flips += 1
        if self._tree_override:
            self.wire_mode = winner
        else:
            strategy = self._candidates[self.adaptive.active][0]
            self._candidates[self.adaptive.active] = (strategy, winner)
        # safety: every peer must now frame messages in the same codec
        if not self.bytes_consensus(
            winner.encode(),
            f":precision:{self._precision_flips}",
        ):
            raise RuntimeError("precision switch diverged across peers")
        self._flush_residuals(f"precision vote {old_mode!r} -> {winner!r}")
        self._publish_wire_mode()
        from kungfu_tpu.telemetry import audit as _audit

        _audit.record_event(
            "precision_switch",
            peer=str(self.self_id),
            trigger=trigger,
            old_wire=old_mode,
            new_wire=winner,
            flip_count=self._precision_flips,
        )
        from kungfu_tpu.telemetry import decisions as _decisions

        _decisions.open_decision(
            "precision_switch",
            peer=str(self.self_id),
            epoch=self.cluster_version,
            trigger=trigger,
            signals=dict(signals or {},
                         votes=int(votes_out[WIRE_MODES.index(winner)]),
                         size=self.size),
            old=old_mode,
            new=winner,
        )
        return winner

    def active_strategy(self) -> Optional[Strategy]:
        """The running candidate strategy, or None when an explicit
        set_tree forest overrides the candidates. This is the Strategy-
        typed accessor; the operator-facing codec-qualified name lives
        in :meth:`active_candidate_name` (ISSUE 10 satellite — the two
        contracts used to be conflated at the api layer)."""
        if self._tree_override:
            return None
        return self._candidates[self.adaptive.active][0]

    def active_candidate_name(self) -> str:
        """Display name of the running adaptive candidate: the strategy,
        suffixed with "/<codec>" when a wire codec is active (an
        interference vote may have toggled compression rather than the
        graphs); "SET_TREE" under a set_tree override."""
        s = self.active_strategy()
        if s is None:
            return "SET_TREE"
        wire = self._active_wire_mode()
        return s.name if wire == "off" else f"{s.name}/{wire}"

    def set_tree(self, fathers: Sequence[int]) -> None:
        """Install a runtime forest (e.g. an MST over probed latencies) as
        the active global strategy (parity: SetTree / SetGlobalStrategy,
        adaptation.cpp:5-33). Disables vote-driven switching — an explicit
        tree wins until the next session epoch.

        The installed forest must be a single tree rooted at rank 0:
        gather/reduce/broadcast walk global_strategies[0] assuming its root
        is rank 0, so a forest rooted elsewhere (or with several roots)
        would silently produce wrong data. Per-component forests are still
        available via subset_all_reduce/all_reduce_with."""
        if len(fathers) != self.size:
            raise ValueError(f"forest size {len(fathers)} != cluster {self.size}")
        roots = [r for r, f in enumerate(fathers) if int(f) == r]
        if roots != [0]:
            raise ValueError(
                f"set_tree forest must be one tree rooted at rank 0, got roots {roots}"
            )
        self.global_strategies = st.from_forest_array(list(fathers))
        self._tree_override = True

    def calc_stats(self) -> dict:
        """Per-strategy throughput summary (parity: CalcStats/LogStats)."""
        return self.adaptive.summary()

    # ------------------------------------------------------------------
    # measured-topology re-planning (ISSUE 14)
    # ------------------------------------------------------------------

    def ring_plan(self) -> Optional[rp.RingPlan]:
        """The adopted measured-topology plan, or None for the naive
        rank-order ring with equal segments. Under a two-level plan
        this is its FLAT projection (``HierPlan.as_ring_plan``) — the
        single layout every flat consumer (ZeRO shard bounds, ring
        gauges, the segmented RS/AG legs) keeps reading unchanged."""
        return self._ring_plan

    def hier_plan(self) -> Optional[rp.HierPlan]:
        """The adopted two-level plan (ISSUE 19), or None when the
        session runs a flat ring."""
        return self._hier_plan

    def demoted_peers(self) -> Tuple[int, ...]:
        """Ranks currently voted into the demoted (backup) role."""
        return self._demoted

    def _static_hosts(self) -> List[List[int]]:
        """The static host partition as rank groups — the clustering
        fallback when the measured matrix is not bimodal enough to
        derive host boundaries."""
        _, master_of = self.peers.partition_by_host()
        groups: Dict[int, List[int]] = {}
        for r in range(self.size):
            groups.setdefault(master_of[r], []).append(r)
        return [sorted(g) for _, g in sorted(groups.items())]

    def owned_bounds(self, count: int) -> Tuple[int, int]:
        """(begin, end) bounds of the segment THIS rank owns fully
        reduced after a reduce-scatter of ``count`` elements, under the
        CURRENT ring plan — the single layout source the walk engine,
        the ZeRO-1 shard views and the api helpers all read, so a plan
        change re-shards every consumer through one function."""
        plan = self._ring_plan
        if plan is None:
            return topo.owned_segment_bounds(count, self.size, self.rank)
        return topo.owned_segment_bounds(
            count, self.size, self.rank,
            order=plan.order, weights=plan.weights,
        )

    def add_replan_listener(self, listener) -> None:
        """Register an object with ``pre_replan() -> token`` /
        ``post_replan(token)`` hooks, invoked around every plan adoption
        (the ZeRO-1 session registers itself: pre exports exact state
        under the OLD shard layout, post re-shards under the new)."""
        self._replan_listeners.append(listener)

    def _replan_name(self, kind: str) -> str:
        """Round-stamped rendezvous name for the lockstep re-plan
        rounds (KF700 discipline: version + per-epoch sequence — every
        member runs these rounds in lockstep, so the stamp agrees
        cluster-wide and repeats can never cross-consume lanes)."""
        return f"kungfu::replan:{kind}:v{self.cluster_version}:{self._replan_seq}"

    def measured_matrix(self) -> "np.ndarray":
        """Exchange every peer's outgoing link-table row and return the
        merged k×k bandwidth matrix (bytes/sec; 0 = no estimate),
        identical bytes on every peer BY CONSTRUCTION: one gather to
        rank 0 + one broadcast of the concatenation (``all_gather``),
        so the plan derivation downstream is a pure function of shared
        input — the version-skew a scraped /cluster/links snapshot
        would reintroduce cannot exist here. Collective: call in
        lockstep on every peer."""
        k = self.size
        row = np.zeros(k, np.float32)
        if self._links is not None:
            for j, pid in enumerate(self.peers):
                if j == self.rank:
                    continue
                bw = self._links.bandwidth(pid)
                if bw is not None and bw > 0:
                    row[j] = np.float32(bw)
        out = np.zeros(k * k, np.float32)
        self.all_gather(Workspace(
            send=row, recv=out, op=ReduceOp.SUM,
            name=self._replan_name("mx"),
        ))
        return out.reshape(k, k).astype(np.float64)

    def measured_compute_frac(self) -> float:
        """All-gather each peer's measured window CPU fraction (the
        resource plane's compute floor, ISSUE 16) and return the
        cluster MAX — identical bytes on every peer by construction,
        like :meth:`measured_matrix`, so ``derive_plan``'s Amdahl clamp
        stays a pure function of shared input. 0.0 when nobody has a
        measurement (no clamp: missing data must never fabricate
        pessimism). Collective: call in lockstep on every peer."""
        k = self.size
        mine = 0.0
        try:
            from kungfu_tpu.telemetry import resource as _tres

            mine = max(0.0, min(1.0, _tres.get_plane().compute_frac()))
        # kfcheck: disable=KF400 — an unmeasurable local floor must
        # degrade to 0.0 (no clamp), never kill the re-plan round; every
        # peer still runs the same all_gather below so the protocol
        # stays lockstep
        except Exception:  # noqa: BLE001
            pass
        send = np.array([np.float32(mine)], np.float32)
        out = np.zeros(k, np.float32)
        self.all_gather(Workspace(
            send=send, recv=out, op=ReduceOp.SUM,
            name=self._replan_name("cf"),
        ))
        return round(float(out.max()), 6)

    def check_replan(
        self, want: bool = True, min_gain: float = 1.05, tag: str = ""
    ) -> Optional[rp.RingPlan]:
        """One lockstep re-plan round (ISSUE 14): call on EVERY peer at
        the same step boundary (the :class:`~kungfu_tpu.policy
        .ReplanPolicy` gates on the step counter). Mirrors the
        interference vote's shape:

        1. majority vote over each peer's local ``want`` (its signal
           window: a persistent ``links/slowest_edge`` or
           ``step/critical_edge``);
        2. on a majority, exchange the measured link rows
           (:meth:`measured_matrix`) — every peer now holds identical
           matrix bytes;
        3. derive the plan (``plan.replan.derive_plan`` — pure function
           of the matrix, so every peer derives the identical plan) and
           adopt it via :meth:`adopt_replan` when the predicted gain
           clears ``min_gain``.

        Returns the adopted plan, or None (no majority / no measurable
        win / mode off). ``KF_CONFIG_REPLAN`` is consensus-checked at
        session start, so either every peer runs these rounds or none
        does — a half-configured fleet fails fast at epoch start, not
        here."""
        if (
            self.replan_mode == "off"
            or self.size < 2
            or self._tree_override
        ):
            return None
        votes_in = np.array([1 if want else 0], np.int32)
        votes_out = np.zeros(1, np.int32)
        self._fixed_allreduce(Workspace(
            votes_in, votes_out, ReduceOp.SUM,
            self._replan_name("vote") + tag,
        ))
        if int(votes_out[0]) * 2 <= self.size:
            self._replan_seq += 1
            return None
        matrix = self.measured_matrix()
        # the measured compute floor (ISSUE 16): a ring re-order only
        # shrinks the network share of the step, so the predicted gain
        # is clamped by the busiest peer's CPU fraction — gathered like
        # the matrix so every peer clamps by the identical scalar
        compute_frac = self.measured_compute_frac()
        if self.replan_mode == "hier":
            # two-level mode (ISSUE 19): derive the hierarchy from the
            # shared matrix; on a single host group (nothing to nest)
            # fall back to the flat measured ring — same pure-function
            # contract, every peer takes the same branch
            hier = rp.derive_hier_plan(
                matrix, hosts=self._static_hosts(), mode=self.replan_mode,
                current=self._hier_plan, compute_frac=compute_frac,
                demoted=self._demoted,
            )
            if hier is not None:
                if not self._hier_worthwhile(hier, min_gain):
                    self._replan_seq += 1
                    return None
                self.adopt_replan(hier)
                return self._ring_plan
            if self._hier_plan is not None:
                # current hierarchy still the best derivation: keep it
                # (a flat fallback here would silently tear it down)
                self._replan_seq += 1
                return None
            plan = rp.derive_plan(
                matrix, mode="auto", current=self._ring_plan,
                compute_frac=compute_frac,
            )
        else:
            plan = rp.derive_plan(
                matrix, mode=self.replan_mode, current=self._ring_plan,
                compute_frac=compute_frac,
            )
        if plan is None or not self._replan_worthwhile(plan, min_gain):
            # nothing derivable, or the predicted win doesn't clear the
            # bar — seq still advances (every peer took the same branch:
            # the decision is a pure function of the shared matrix)
            self._replan_seq += 1
            return None
        self.adopt_replan(plan)
        return plan

    def _hier_worthwhile(self, plan: rp.HierPlan, min_gain: float) -> bool:
        """Churn gate for two-level derivations, pure like
        `_replan_worthwhile`: the FIRST hierarchy (or any change to the
        demoted set) is structural and always adopted — demotions are
        voted deliberately and their win is graded by the ledger, not
        predicted — while a re-derivation that merely reshuffles groups
        or heads must clear ``min_gain``."""
        cur = self._hier_plan
        if cur is None or plan.demoted != cur.demoted:
            return True
        return plan.gain >= min_gain

    def check_demote(
        self,
        demote: Optional[int] = None,
        promote: Optional[int] = None,
        tag: str = "",
    ) -> Optional[rp.RingPlan]:
        """One lockstep demote/promote round (ISSUE 19): call on EVERY
        peer at the same step boundary, like :meth:`check_replan`. Each
        peer proposes at most one rank to demote into the backup role
        and one to promote back; a one-hot per-candidate SUM on the
        knob-independent star walk counts the proposals, candidates
        carried by a strict majority flip, and the changed demoted set
        re-derives the two-level plan from freshly exchanged matrix
        rows, adopted through the ordinary :meth:`adopt_replan` digest +
        listener bracket (the ledger opens a `peer_demoted` /
        `peer_promoted` record per flipped rank there).

        Returns the adopted flat projection, or None when no candidate
        carried, the set didn't change, or no hierarchy is derivable
        (demotion only acts under an active two-level mode — a flat
        ring routes around stragglers instead). A vote that would
        demote the last contributing member of a host is rejected by
        the derivation (no head candidate), never half-applied."""
        if (
            self.replan_mode != "hier"
            or self.size < 2
            or self._tree_override
        ):
            return None
        k = self.size
        ballot = np.zeros(2 * k, np.int32)
        if demote is not None and 0 <= int(demote) < k:
            ballot[int(demote)] = 1
        if promote is not None and 0 <= int(promote) < k:
            ballot[k + int(promote)] = 1
        counts = np.zeros(2 * k, np.int32)
        self._fixed_allreduce(Workspace(
            ballot, counts, ReduceOp.SUM,
            self._replan_name("demote") + tag,
        ))
        demotes = {r for r in range(k) if int(counts[r]) * 2 > k}
        promotes = {r for r in range(k) if int(counts[k + r]) * 2 > k}
        new_demoted = tuple(sorted(
            (set(self._demoted) | demotes) - promotes
        ))
        if new_demoted == self._demoted:
            self._replan_seq += 1
            return None
        matrix = self.measured_matrix()
        compute_frac = self.measured_compute_frac()
        hier = rp.derive_hier_plan(
            matrix, hosts=self._static_hosts(), mode=self.replan_mode,
            current=self._hier_plan, compute_frac=compute_frac,
            demoted=new_demoted,
        )
        if hier is None:
            # not derivable with the new set (single host group, or a
            # host would lose its last head) — same branch on every
            # peer: the inputs are all shared
            self._replan_seq += 1
            return None
        self.adopt_replan(hier)
        return self._ring_plan

    def _replan_worthwhile(self, plan: rp.RingPlan, min_gain: float) -> bool:
        """Churn gate, a pure function of (current plan, derived plan):
        a REORDER must clear ``min_gain`` (estimates drift every round —
        re-pairing the ring on noise costs a ZeRO re-shard each time,
        live-drive finding); an order-preserving weight refinement must
        move some segment weight by ≥10% relative."""
        cur = self._ring_plan
        if cur is None or plan.order != cur.order:
            return plan.gain >= min_gain
        if plan.weights is None or cur.weights is None:
            return True  # weights appearing/disappearing is material
        return any(
            abs(n - o) > 0.1 * max(o, 1e-12)
            for n, o in zip(plan.weights, cur.weights)
        )

    def adopt_replan(self, plan) -> None:
        """Install ``plan`` (a :class:`RingPlan`, a :class:`HierPlan`,
        or None = back to the naive ring) as the active topology,
        cluster-safely; call in lockstep on every peer at a step
        boundary (no walk in flight).

        The plan digest is asserted on the knob-INDEPENDENT star walk
        first (KF700/701 discipline): a peer whose matrix-fed derivation
        diverged gets a named RuntimeError here — never a rendezvous
        hang inside a later walk whose segment bounds silently differ.
        Registered listeners bracket the swap (``pre_replan`` runs under
        the OLD plan — the ZeRO-1 session exports exact state there —
        and ``post_replan`` re-shards under the new). A HierPlan
        installs BOTH itself (driving the two-level walk) and its flat
        projection (``as_ring_plan``), so every flat consumer —
        owned_bounds, the ring gauges, the ZeRO RS/AG legs — re-shards
        through the same one listener bracket, flat→hier flips
        included."""
        seq = self._replan_seq
        self._replan_seq += 1
        if not self._bytes_agree(
            rp.plan_digest(plan),
            f":replan:adopt:v{self.cluster_version}:{seq}",
            self._fixed_allreduce,
        ):
            raise RuntimeError(
                "measured-topology re-plan diverged across peers: the "
                "ring plan must be a pure function of the exchanged "
                "link matrix, but this peer derived "
                f"{plan.describe() if plan is not None else 'naive'} "
                f"(digest {rp.plan_digest(plan).hex()}) and at least one "
                "peer derived something else — refusing to install "
                "mismatched segment bounds (walks would deadlock or "
                "corrupt); this is a determinism bug, not a transient"
            )
        if isinstance(plan, rp.HierPlan):
            hier: Optional[rp.HierPlan] = plan
            flat: Optional[rp.RingPlan] = plan.as_ring_plan()
        else:
            hier = None
            flat = plan
        tokens = [
            (listener, listener.pre_replan())
            for listener in self._replan_listeners
        ]
        old = self._ring_plan
        old_demoted = self._demoted
        self._ring_plan = flat
        self._hier_plan = hier
        self._demoted = hier.demoted if hier is not None else ()
        for listener, token in tokens:
            listener.post_replan(token)
        # error-feedback residuals index the OLD plan's segment bounds;
        # under the new ownership they would correct the wrong slices
        self._flush_residuals("replan adopted: segment ownership moved")
        self._publish_ring_metrics()
        if self._replans_ctr is not None:
            self._replans_ctr.inc()
        from kungfu_tpu.telemetry import audit as _audit

        _audit.record_event(
            "topology_replanned",
            peer=str(self.self_id),
            trigger="replan_vote",
            old_order=list(old.order) if old is not None else list(range(self.size)),
            new_order=(
                list(flat.order) if flat is not None
                else list(range(self.size))
            ),
            weighted=bool(flat is not None and flat.weights is not None),
            hier=hier is not None,
            demoted=list(self._demoted),
            predicted_gain=flat.gain if flat is not None else 1.0,
        )
        # decision ledger (ISSUE 15): the re-plan predicted a throughput
        # ratio — this record is what finally measures the realized one.
        # Demote/promote flips get their OWN named records (ISSUE 19) so
        # `info decisions` can grade each straggler demotion separately.
        from kungfu_tpu.telemetry import decisions as _decisions

        _decisions.open_decision(
            "topology_replanned",
            peer=str(self.self_id),
            epoch=self.cluster_version,
            trigger="replan_vote",
            predicted_gain=flat.gain if flat is not None else 1.0,
            old_order=",".join(
                str(r) for r in (old.order if old is not None
                                 else range(self.size))
            ),
            new_order=",".join(
                str(r) for r in (flat.order if flat is not None
                                 else range(self.size))
            ),
            weighted=bool(flat is not None and flat.weights is not None),
            hier=hier is not None,
        )
        for r in sorted(set(self._demoted) - set(old_demoted)):
            _decisions.open_decision(
                "peer_demoted",
                peer=str(self.self_id),
                epoch=self.cluster_version,
                trigger="straggler_patience",
                predicted_gain=flat.gain if flat is not None else 1.0,
                demoted_rank=str(r),
            )
        for r in sorted(set(old_demoted) - set(self._demoted)):
            _decisions.open_decision(
                "peer_promoted",
                peer=str(self.self_id),
                epoch=self.cluster_version,
                trigger="straggler_recovered",
                predicted_gain=1.0,
                promoted_rank=str(r),
            )

    def _publish_ring_metrics(self) -> None:
        """Refresh the active-ring gauges (position + successor edge)
        from the current plan; children are rebuilt so a re-plan never
        leaves the OLD successor edge frozen in the exposition."""
        if self._ring_pos_g is None:
            return
        order = (
            self._ring_plan.order if self._ring_plan is not None
            else tuple(range(self.size))
        )
        pos = order.index(self.rank)
        succ = self.peers[order[(pos + 1) % self.size]] if self.size > 1 else None
        self._ring_pos_g.set(pos)
        self._ring_next_g.clear_children()
        if succ is not None:
            self._ring_next_g.labels(str(succ)).set(1)
        if self._ring_role_g is not None:
            self._ring_role_g.clear_children()
            hier = self._hier_plan
            if hier is None:
                self._ring_role_g.labels("flat", "member").set(0)
            else:
                gi = hier.group_of(self.rank)
                if self.rank in hier.demoted:
                    level, role = "intra", "demoted"
                elif self.rank == hier.heads[gi]:
                    level, role = "inter", "head"
                else:
                    level, role = "intra", "member"
                self._ring_role_g.labels(level, role).set(gi)
        self._publish_wire_mode()

    def _publish_wire_mode(self) -> None:
        """Refresh the active-precision gauge; children are rebuilt so a
        precision flip never leaves the OLD mode frozen at 1."""
        if self._wire_mode_g is None:
            return
        self._wire_mode_g.clear_children()
        self._wire_mode_g.labels(self._active_wire_mode()).set(1)

    def cross_all_reduce(self, w: Workspace) -> None:
        """AllReduce across host masters only (hierarchical path). While
        RING_SEGMENTED is the ACTIVE strategy, masters run the segmented
        walk over the master ring (the subset/cross variant); non-masters
        forward. Gated on _segmented_active — not the static configured
        strategy — so set_tree overrides and adaptive switches govern the
        cross path exactly like the global one (votes advance in lockstep
        on every peer, so the gate stays cluster-consistent).

        The wire codec applies here like the global allreduce — the
        cross-host hop crosses the DCN, exactly where halving wire
        bytes pays most; the intra-host reduce/broadcast phases around
        it stay raw (loopback/shm, nothing to save)."""
        wire = self._wire_codec_for(w)
        with stall_detect(f"cross_all_reduce({w.name})"):
            if (
                self._segmented_active()
                and len(self._masters) >= 2
                and w.recv.nbytes >= self.SEGMENT_MIN_BYTES
            ):
                self._run_segmented(w, ranks=self._masters, wire=wire)
            else:
                self._run_strategies(w, self.cross_strategies, wire=wire)

    def local_reduce(self, w: Workspace) -> None:
        self._run_graphs(w, [self.local_strategies[0].reduce_graph])

    def local_broadcast(self, w: Workspace) -> None:
        self._run_graphs(w, [self.local_strategies[0].bcast_graph])

    def _root_star_graphs(self, root: int) -> Tuple[Graph, Graph]:
        """(bcast, reduce) star graphs rooted at `root`, cached on the
        session — reduce/broadcast/broadcast_bytes used to regenerate
        them on every call (a Graph build is O(size) allocations, paid
        per elastic state-sync message). Benign to race: both writers
        compute identical graphs."""
        pair = self._root_graphs.get(root)
        if pair is None:
            bcast = topo.gen_star_bcast_graph(self.size, root)
            pair = (bcast, topo.gen_default_reduce_graph(bcast))
            self._root_graphs[root] = pair
        return pair

    def reduce(self, w: Workspace, root: int = 0) -> None:
        """Reduce to `root` (parity: runGraphs with a reduce graph; the
        reference's Reduce takes arbitrary roots). Root 0 walks the
        configured strategy; other roots use a root-specific star."""
        if root == 0:
            self._run_graphs(w, [self.global_strategies[0].reduce_graph])
        else:
            self._check_root(root)
            self._run_graphs(w, [self._root_star_graphs(root)[1]])

    def broadcast(self, w: Workspace, root: int = 0) -> None:
        with self._collected("broadcast", w.recv.nbytes):
            if root == 0:
                self._run_graphs(w, [self.global_strategies[0].bcast_graph])
            else:
                self._check_root(root)
                self._run_graphs(w, [self._root_star_graphs(root)[0]])

    def _check_root(self, root: int) -> None:
        if not 0 <= root < self.size:
            raise ValueError(f"root {root} outside cluster of {self.size}")

    def subset_all_reduce(self, fathers: Sequence[int], w: Workspace) -> None:
        sl = st.from_forest_array(list(fathers))
        self._run_strategies(w, sl)

    def all_reduce_with(self, fathers: Sequence[int], w: Workspace) -> None:
        """AllReduce on a runtime-supplied tree (parity: AllReduceWith)."""
        if fathers:
            sl = st.from_forest_array(list(fathers))
        else:
            sl = self.global_strategies
        self._run_strategies(w, sl)

    def barrier(self, tag: str = "") -> None:
        """Parity: session.go:98-113 (an allreduce of size bytes)."""
        k = len(self.peers)
        w = Workspace(
            send=np.zeros(k, np.uint8),
            recv=np.zeros(k, np.uint8),
            op=ReduceOp.SUM,
            name=f"kungfu::barrier{tag}",
        )
        self.all_reduce(w)

    def bytes_consensus(self, bs: bytes, name: str) -> bool:
        """True iff every peer supplied identical bytes (parity:
        session.go:126-157, which runs 4 allreduce rounds). 2 rounds
        here: a MIN-allreduce of the packed (len, -len) int64 workspace
        yields the cluster's (min-len, -max-len) in one walk, and a
        MIN-allreduce of the two-lane (payload, 255-payload) bytes yields
        (elementwise-min, 255-elementwise-max) in another — consensus iff
        min == max in both. Every elastic resize and strategy switch pays
        this path, so halving the rounds halves its serialized latency.

        Runs int64/uint8 lanes through the regular engine — the wire
        codec is f32-only, so consensus payloads are never quantized
        (docs/collectives.md: consensus MUST stay exact)."""
        return self._bytes_agree(bs, name, self.all_reduce)

    def _bytes_agree(
        self, bs: bytes, name: str, run: Callable[[Workspace], None]
    ) -> bool:
        """The 2-round consensus algebra, parameterized over the
        allreduce runner so the knob-consensus check can use graphs that
        do not depend on the very knobs being checked."""
        n = len(bs)
        lens = np.array([n, -n], np.int64)
        out_len = np.zeros(2, np.int64)
        run(Workspace(lens, out_len, ReduceOp.MIN, f":consensus:len:{name}"))
        if out_len[0] != -out_len[1]:
            return False
        if n == 0:
            return True
        x = np.frombuffer(bs, np.uint8)
        lanes = np.empty(2 * n, np.uint8)
        lanes[:n] = x
        np.subtract(255, x, out=lanes[n:])
        out = np.zeros(2 * n, np.uint8)
        run(Workspace(lanes, out, ReduceOp.MIN, f":consensus:data:{name}"))
        return bool(np.array_equal(out[:n], 255 - out[n:]))

    # ------------------------------------------------------------------
    # engine-knob consensus (fail fast instead of deadlocking)
    # ------------------------------------------------------------------

    def engine_knobs(self) -> List[Tuple[str, str]]:
        """The cluster-agreed engine knobs, as resolved BY THIS SESSION.

        Every entry decides rendezvous names, message sizes or peer
        pairings, so peers that resolved different values would wait on
        each other's names (or mis-frame messages) forever. Local-only
        tuning (KF_CONFIG_GROUP_WINDOW — pure intra-host concurrency —
        and KF_CONFIG_ASYNC_QUEUE, the scheduler's local in-flight
        depth) is deliberately excluded: it may legitimately differ per
        host."""
        return [
            ("KF_CONFIG_ALGO", knobs.get("KF_CONFIG_ALGO")),
            ("KF_CONFIG_CHUNK_BYTES", str(CHUNK_BYTES)),
            ("KF_CONFIG_SEGMENT_MIN_BYTES", str(self.SEGMENT_MIN_BYTES)),
            ("KF_CONFIG_GROUP_BUCKET_BYTES", str(self.GROUP_BUCKET_BYTES)),
            ("KF_CONFIG_GROUP_FUSE_MIN", str(self.FUSE_MIN_TENSORS)),
            ("KF_CONFIG_WIRE", self.wire_mode),
            ("KF_CONFIG_WIRE_MIN_BYTES", str(self.WIRE_MIN_BYTES)),
            ("KF_WIRE_BLOCK", str(self.WIRE_BLOCK)),
            ("KF_CONFIG_ASYNC", self.async_mode),
            ("KF_CONFIG_ZERO", self.zero_mode),
            ("KF_CONFIG_REPLAN", self.replan_mode),
            ("KF_REPLAN_DEMOTE_PATIENCE", str(self.demote_patience)),
        ]

    def _fixed_allreduce(self, w: Workspace) -> None:
        """Allreduce over a rank-0 star, unchunked and uncompressed — a
        walk whose rendezvous names and message sizes depend on NOTHING
        the knobs control, so it completes even across knob-divergent
        peers (tiny payloads; latency is 2 serialized hops).

        Marked as a DELIBERATE graph walk: the knob-consensus and
        re-plan rounds choose the star by design, so they must not
        trip the `segmented_fallback` audit meant for payloads that
        FELL BACK from the segmented engine (review finding: every
        segmented session fired the event on its startup consensus
        walk, before any user collective could)."""
        self._in_fixed_walk = True
        try:
            bcast, red = self._root_star_graphs(0)
            self._run_graphs(w, [red, bcast])
        finally:
            self._in_fixed_walk = False

    def check_knob_consensus(self) -> None:
        """Fail fast on engine-knob divergence (satellite of ISSUE 5).

        Without this, peers that resolved different KF_CONFIG_ALGO /
        CHUNK_BYTES / GROUP_BUCKET_BYTES / WIRE / ASYNC values wait on
        each other's rendezvous names forever — the first collective of
        the epoch just hangs. One consensus over the resolved knob tuple
        at session start turns that into an immediate, named error. Runs
        on the knob-independent star walk, so the check itself cannot
        deadlock on the very disagreement it detects; on mismatch a
        per-knob round pins down WHICH knob diverged."""
        if self.size < 2:
            return
        resolved = self.engine_knobs()
        blob = ";".join(f"{k}={v}" for k, v in resolved).encode()
        if self._bytes_agree(blob, ":knobs", self._fixed_allreduce):
            return
        bad = [
            k for k, v in resolved
            if not self._bytes_agree(
                v.encode(), f":knob:{k}", self._fixed_allreduce
            )
        ]
        mine = dict(resolved)
        names = ", ".join(bad) if bad else "engine knob tuple"
        raise RuntimeError(
            f"engine knob mismatch across peers: {names} — these KF_CONFIG_* "
            f"values decide rendezvous names and message sizes, so they MUST "
            f"be set identically fleet-wide (collectives would deadlock); "
            f"this peer ({self.self_id}) resolved "
            + ", ".join(f"{k}={mine[k]!r}" for k in (bad or mine))
        )

    def broadcast_bytes(self, bs: bytes, name: str, root: int = 0) -> bytes:
        """Broadcast variable-length bytes from `root` (two graph walks:
        length, then payload). Used to bootstrap the device plane — the
        TPU analog of broadcasting the NCCL unique id over the CPU
        collective (gpu_collective.cpp:190-212) — and for elastic state
        re-sync, where the root must be a SURVIVING peer (not necessarily
        rank 0 of the new cluster)."""
        # a fixed star keeps the walk root-correct regardless of the active
        # strategy (set_tree/adaptive switches may re-root global_strategies)
        graph = self._root_star_graphs(root)[0]
        n_send = np.array([len(bs) if self.rank == root else 0], np.int64)
        n_recv = np.zeros(1, np.int64)
        self._run_graphs(
            Workspace(n_send, n_recv, ReduceOp.SUM, f"{name}:len"), [graph]
        )
        n = int(n_recv[0])
        if n == 0:
            return b""
        if self.rank == root:
            send = np.frombuffer(bs, np.uint8)
        else:
            send = np.zeros(n, np.uint8)
        recv = np.zeros(n, np.uint8)
        self._run_graphs(
            Workspace(send, recv, ReduceOp.SUM, f"{name}:data"), [graph]
        )
        return recv.tobytes()

    def gather(self, w: Workspace, root: int = 0) -> None:
        """`root` receives everyone's send buffer into recv (rank-major);
        parity: runGather (session.go:195-221), arbitrary roots like the
        reference's Gather. Handles unequal per-peer counts: the wire
        framing carries each message's true length, so the root lays
        contributions out by their actual sizes (the reference relies on
        the same message framing)."""
        self._check_root(root)
        if self.rank != root:
            with self._collected("gather", w.send.nbytes):
                self.client.send(
                    self.peers[root], w.name, _buf(w.send), ConnType.COLLECTIVE
                )
                self._count_wire(w.send.nbytes, "STAR")
            return
        scope = self._collected("gather", w.recv.nbytes)
        scope.__enter__()
        cancel = threading.Event()
        parts: List[Optional[np.ndarray]] = [None] * len(self.peers)
        releases: List = [None] * len(self.peers)

        def recv_part(r: int, peer: PeerID) -> None:
            msg = self.endpoint.recv(peer, w.name, self.timeout)
            if cancel.is_set():
                if msg.release is not None:
                    msg.release()
                return
            parts[r] = np.frombuffer(msg.data, w.send.dtype)
            releases[r] = msg.release

        jobs = []
        for r, peer in enumerate(self.peers):
            if r == self.rank:
                parts[r] = w.send.reshape(-1)
            else:
                jobs.append(lambda r=r, p=peer: recv_part(r, p))
        try:
            _par(jobs, self.timeout, cancel)
            off = 0
            for part in parts:
                assert part is not None
                n = part.size
                if off + n > w.recv.size:
                    raise ValueError(
                        f"gather overflow: recv buffer {w.recv.size} < {off + n}"
                    )
                np.copyto(w.recv[off:off + n], part)
                off += n
            if off != w.recv.size:
                # a short contribution would silently shift later ranks' data
                raise ValueError(
                    f"gather underflow: contributions fill {off} of {w.recv.size}"
                )
        finally:
            parts.clear()
            for rel in releases:
                if rel is not None:
                    rel()
            scope.__exit__(None, None, None)

    def all_gather(self, w: Workspace) -> None:
        """Gather to root then broadcast the concatenation (parity:
        AllGatherTransform, session.cpp:201-220)."""
        self.gather(w)
        bw = Workspace(send=w.recv, recv=w.recv, op=w.op, name=w.name + ":bcast")
        self.broadcast(bw)
