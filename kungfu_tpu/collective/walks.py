"""Walk engines of the host collective plane, factored out of
host_session.py (ISSUE 10 prerequisite refactor).

Two walk families execute every allreduce:

- the bandwidth-optimal **segmented ring** (`_run_segmented`, ISSUE 4):
  (k-1)-step reduce-scatter + (k-1)-step all-gather, exactly
  2·(k-1)/k·N bytes per peer;
- chunk-striped **graph walks** (`_run_strategies` → `_run_graphs`,
  parity: runGraphs, session.go:231-299) over (reduce, bcast) pairs.

Both live on the :class:`WalkEngine` mixin of
:class:`~kungfu_tpu.collective.host_session.HostSession`, sharing the
receive protocol (`_recv_collective`), the wire-byte accounting and the
critical-path profiler feeds, so the fused pipeline (pipeline.py) and
the async scheduler (scheduler.py) drive the exact same engine.
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional, Sequence

import numpy as np

from kungfu_tpu import knobs
from kungfu_tpu.base.dtype import DType
from kungfu_tpu.base.ops import (
    QWire,
    copy_segment,
    decode_accumulate_any,
    decode_wire_any,
    encode_wire_any,
    reduce_inplace,
    reduce_segment,
    transform_n,
)
from kungfu_tpu.base.ops import wire_nbytes as _wire_payload_nbytes
from kungfu_tpu.base.strategy import Strategy
from kungfu_tpu.base.workspace import Workspace, even_partition
from kungfu_tpu.collective import strategies as st
from kungfu_tpu.collective.codec import DeferredDecode
from kungfu_tpu.collective.profiler import WalkProfile, get_walk_profiler
from kungfu_tpu.telemetry import steptrace
from kungfu_tpu.plan import topology as topo
from kungfu_tpu.plan.graph import Graph
from kungfu_tpu.plan.peer import PeerID
from kungfu_tpu.transport.message import ConnType, Flags
from kungfu_tpu.telemetry import tracing as trace
from kungfu_tpu.utils.handoff import parallel_run as _par
from kungfu_tpu.utils.pool import get_buffer_pool, get_pool

# Chunking (parity: session.go chunkSize, but self-tuned): the optimal
# trades chunk-walk overhead (fewer, bigger chunks) against striping/
# pipelining (more, smaller chunks) and depends on host core count —
# concurrent chunk walks only pay when cores exist to run them; on a
# 1-core host every extra in-flight chunk is pure context-switch cost.
# KF_CONFIG_CHUNK_BYTES overrides the heuristic.
CHUNK_BYTES = int(knobs.get("KF_CONFIG_CHUNK_BYTES"))
_CHUNK_MIN = 1 << 20
_CHUNK_MAX = 32 << 20
DEFAULT_TIMEOUT = 120.0

# A/B algorithm override (benchmarks, operators): forces the engine onto
# one family regardless of the configured/AUTO strategy. Like every other
# engine knob it MUST agree cluster-wide (peers that resolved different
# algorithms would wait on each other's rendezvous names forever).
_ALGO_STRATEGY = {
    "": None,
    "auto": Strategy.AUTO,
    "tree": Strategy.BINARY_TREE,
    "segmented": Strategy.RING_SEGMENTED,
}


def algo_override() -> Optional[Strategy]:
    """Parse KF_CONFIG_ALGO (read per session epoch, not import time).
    The registry's strict choice parser raises on a typo — fail fast,
    not silently diverge the cluster."""
    return _ALGO_STRATEGY[knobs.get("KF_CONFIG_ALGO")]


def choose_chunk_bytes(total: int) -> int:
    """Chunk size for a `total`-byte collective: honour the env override,
    else ~8 chunks per collective, clamped to [1 MiB, 32 MiB].

    MUST depend only on cluster-agreed inputs (the workspace size): chunk
    workspaces are named '<name>[i/k]', so peers that computed different
    k would wait forever on each other's chunk names. That rules out
    os.cpu_count() here (heterogeneous hosts); measured on the 1-core
    box, 8 in-flight walks of >=1 MiB is within noise of the per-core
    optimum anyway."""
    if CHUNK_BYTES > 0:
        return CHUNK_BYTES
    c = total // 8
    return max(_CHUNK_MIN, min(_CHUNK_MAX, c))


def _buf(arr: np.ndarray):
    """Zero-copy byte view of a contiguous array (tobytes() fallback)."""
    try:
        return arr.data.cast("B")
    except (ValueError, TypeError, AttributeError):
        return arr.tobytes()


class WalkEngine:
    """Walk-engine mixin for HostSession: owns engine dispatch
    (`_allreduce_ws`), the segmented ring walk, the chunked graph walks
    and the shared receive/accounting/profiling plumbing. Relies on
    session state (peers, client, endpoint, timeout, candidates,
    adaptive, metrics handles) owned by the facade's constructor."""

    # Segmentation pays only when the per-step segment amortizes the
    # 2*(k-1) serialized message latencies; below this the rank-0 binary
    # tree fallback graphs win. MUST be cluster-agreed (it decides which
    # rendezvous names a peer waits on) — like CHUNK_BYTES, the default
    # is a constant and the env override must be set fleet-wide.
    SEGMENT_MIN_BYTES = int(knobs.get("KF_CONFIG_SEGMENT_MIN_BYTES"))

    # adopted two-level plan (ISSUE 19): set in lockstep by
    # adopt_replan, None = flat ring. Class default so the mixin is
    # safe before the facade constructor runs.
    _hier_plan = None
    # intra-leg wire-label override (see _run_hier): the two-level
    # walk's intra star legs run through _run_graphs by design, not as
    # a fallback — they must neither fire the segmented_fallback audit
    # nor pollute the RING_SEGMENTED/BINARY_TREE series.
    _wire_label_override: Optional[str] = None

    def _segmented_active(self) -> bool:
        return (
            not self._tree_override
            and self.size >= 2
            and self._candidates[self.adaptive.active][0]
            == Strategy.RING_SEGMENTED
        )

    def _allreduce_ws(
        self,
        w: Workspace,
        cancel: Optional[threading.Event] = None,
        defer_decode: bool = False,
    ) -> Optional[DeferredDecode]:
        """Engine dispatch for one allreduce workspace: the segmented
        ring walk when RING_SEGMENTED is active and the payload is worth
        segmenting, else chunked graph walks. `cancel` (group/window
        scope) propagates so an abandoned walk observes the caller's
        timeout before mutating recv buffers.

        With `defer_decode=True` a compressed segmented walk skips its
        walk-end decode and returns the wire buffer as a
        DeferredDecode (w.recv is then NOT fully written!); every
        other path returns None and w.recv holds the result."""
        wire = self._wire_codec_for(w)
        if self._segmented_active() and w.recv.nbytes >= self.SEGMENT_MIN_BYTES:
            if self._hier_plan is not None:
                self._run_hier(w, cancel=cancel, wire=wire)
                return None
            return self._run_segmented(
                w, cancel=cancel, wire=wire, defer_decode=defer_decode
            )
        self._run_strategies(w, self.global_strategies, cancel, wire=wire)
        return None

    # ------------------------------------------------------------------
    # accounting / profiling plumbing
    # ------------------------------------------------------------------

    def _count_wire(
        self, nbytes: int, strategy_label: str, codec: str = "off",
        raw_bytes: int = 0,
    ) -> None:
        if self._wire_ctr is not None and nbytes:
            self._wire_ctr.labels(self._wire_kind, strategy_label, codec).inc(nbytes)
        if (
            self._wire_saved_ctr is not None
            and codec != "off"
            and raw_bytes > nbytes
        ):
            self._wire_saved_ctr.labels(self._wire_kind, codec).inc(
                raw_bytes - nbytes
            )

    def _record_walk(
        self,
        strategy_label: str,
        k: int,
        payload_bytes: int,
        wall: float,
        prof: WalkProfile,
        dsts=None,
        sink=None,
    ) -> None:
        """Feed one finished allreduce walk to the process profiler,
        scored against the slowest link the walk used (all estimated
        links when `dsts` is None — graph walks fan out over many).
        `sink` (a captured steptrace sink, ISSUE 13) additionally gets
        the same attribution with the walk's dominant edge — the ring's
        successor when the walk names one, else the slowest estimated
        link — so the step timeline can name the blocking edge."""
        # (shared by the flat segmented walk, the graph walks and the
        # two-level walk's inter leg)
        link_dst = link_bw = None
        if self._links is not None:
            link_dst, link_bw = self._links.min_bandwidth(dsts)
        get_walk_profiler().record(
            self._wire_kind, strategy_label, k, payload_bytes,
            wall, prof.wait, prof.send, link_bw,
        )
        if sink is not None:
            edge = str(dsts[0]) if dsts else link_dst
            steptrace.note_walk(
                sink, strategy_label, wall, prof.wait, prof.send, edge
            )

    def _walk_label(self) -> str:
        """Strategy label for graph-walk wire accounting. Labels the
        graphs that actually EXECUTED: when RING_SEGMENTED is active but
        a payload fell below SEGMENT_MIN_BYTES (or a non-allreduce graph
        consumer — reduce/broadcast/gather — walked the strategy table's
        fallback pair), the walk ran the binary-tree fallback graphs and
        must not pollute the RING_SEGMENTED series (it is the one the
        optimality assertion reads). The first such fallback per session
        epoch is audited (`segmented_fallback`) so the by-design
        tree-under-segmented path is visible, not silent (ISSUE 14
        satellite; PR 4's counter-purity rule)."""
        if self._wire_label_override is not None:
            return self._wire_label_override
        if self._tree_override:
            return "SET_TREE"
        active = self._candidates[self.adaptive.active][0]
        if active == Strategy.RING_SEGMENTED:
            if not self._segmented_fallback_noted and not self._in_fixed_walk:
                self._segmented_fallback_noted = True
                from kungfu_tpu.telemetry import audit as _audit

                _audit.record_event(
                    "segmented_fallback",
                    peer=str(self.self_id),
                    collective=self._wire_kind,
                    wire_label=Strategy.BINARY_TREE.name,
                    threshold_bytes=self.SEGMENT_MIN_BYTES,
                )
            return Strategy.BINARY_TREE.name
        return active.name

    def _recv_collective(
        self, peer: PeerID, name: str, nbytes: int, dtype, count: int,
        timeout: float,
    ):
        """Receive (peer, name) into a pooled scratch buffer — delivered
        straight off the socket when we're parked first (sink path), else
        from the buffered Message (possibly a zero-copy shm borrow).
        Returns (ndarray view, scratch-or-None to return to the pool,
        release-or-None to call once the view has been consumed). Shared
        by the graph walk and the segmented walk so the borrow/release/
        leak-on-timeout contract lives in ONE place. On error the scratch
        is deliberately NOT returned to the pool: a timed-out sink may
        still be mid-fill by the transport thread."""
        bufpool = get_buffer_pool()
        scratch = bufpool.get(nbytes)
        msg, filled = self.endpoint.recv_into(
            peer, name, memoryview(scratch), timeout
        )
        if filled:
            return np.frombuffer(scratch, dtype, count), scratch, None
        bufpool.put(scratch)  # unused: sender raced us or size mismatch
        return np.frombuffer(msg.data, dtype, count), None, msg.release

    # ------------------------------------------------------------------
    # segmented ring walk
    # ------------------------------------------------------------------

    def _run_segmented(
        self,
        w: Workspace,
        ranks: Optional[Sequence[int]] = None,
        cancel: Optional[threading.Event] = None,
        wire=None,
        defer_decode: bool = False,
        phase: str = "all",
        ef_owned: Optional[np.ndarray] = None,
    ) -> Optional[DeferredDecode]:
        """Bandwidth-optimal segmented walk: a (k-1)-step reduce-scatter
        over contiguous segments followed by a (k-1)-step all-gather
        around a ring (arXiv:1810.11112 §3; the TPU-pod MLPerf stack
        leans on the same segmented summation, arXiv:1909.09756). Each
        step sends ONE ~N/k segment to the ring successor and reduces
        (or, in the gather phase, copies) the segment arriving from the
        predecessor in place — zero-copy views into the recv buffer, no
        full-payload relays, ~2*(k-1)/k*N bytes moved per peer total.

        With `wire` set (the codec, ISSUE 5) each segment crosses the
        transport as bf16/f16 — half the bytes, 2*(k-1)/k*N/2 per peer:

        * reduce-scatter: the sender encodes its f32 partial into a
          pooled wire scratch; the receiver decode-accumulates into the
          f32 buffer in one fused pass, so every transmitted value is
          quantized exactly once and no rounding compounds in 16-bit
          storage across the (k-1) steps;
        * all-gather: segments STAY in wire dtype in a walk-local wire
          buffer — each already-reduced segment is quantized once by its
          owner, relayed untouched, and decoded exactly once per peer at
          walk end (the owner decodes its own encoding too, so every
          peer lands on bit-identical results).

        Contracts shared with the graph walk: receives prefer the
        zero-copy sink/shm-borrow path (`recv_into`) and release borrows
        after the in-place reduce; one deadline bounds the WHOLE walk (not
        per step); a timed-out scratch buffer is never returned to the
        pool (the transport thread may still be mid-fill); empty segments
        (payload < k elements) are skipped identically on both ends of
        every edge, so no peer waits on a message that never departs.

        `ranks` restricts the ring to a subset (hierarchical cross-host
        mode); non-members just forward send into recv. With
        `defer_decode` (compressed walks only) the walk-end decode is
        skipped and the wire buffer returned — see DeferredDecode.

        `phase` selects which half of the walk runs (ISSUE 11):

        * ``"all"`` — the full allreduce (default, behavior unchanged);
        * ``"rs"``  — stop after the reduce-scatter: ``w.recv`` holds the
          fully reduced OWNED segment (``topo.owned_segment_bounds``) and
          partial garbage elsewhere. Always raw — the reduce leg of the
          sharded update keeps f32 exactness (the codec's win goes to the
          weight all-gather), so ``wire`` is ignored;
        * ``"ag"``  — the standalone all-gather: the caller already
          placed this rank's segment into ``w.recv`` (use an INPLACE
          workspace; ``forward()`` degenerates to a no-op) and the walk
          relays every segment around the ring, wire-encoded when `wire`
          is set (each segment quantized once by its owner, decoded once
          per peer at walk end — every peer, owner included, lands on
          bit-identical values).

        `wire` accepts a :class:`~kungfu_tpu.base.dtype.DType` (bf16/
        f16) or a :class:`~kungfu_tpu.base.ops.QWire` (block-scaled
        int8/int4). The quantized codec additionally carries
        error-feedback residuals: full walks use the session store
        (keyed by workspace name); the standalone ``"ag"`` phase takes
        the caller's per-shard residual via `ef_owned` (sized to the
        OWNED segment — ZeRO's weight leg). Quantized walks never defer
        the walk-end decode (member bounds don't align with the
        block-scaled layout), so `defer_decode` is ignored for them."""
        if phase not in ("all", "rs", "ag"):
            raise ValueError(f"unknown segmented phase: {phase!r}")
        if phase == "rs":
            wire = None  # the reduce leg stays exact f32 (see docstring)
        if w.is_empty:
            w.forward()
            return None
        # measured-topology plan (ISSUE 14): the GLOBAL ring follows the
        # adopted plan's order and segment weights; subset rings
        # (hierarchical cross-host mode) stay naive — the plan indexes
        # the full rank space. Read once per walk: adoption happens in
        # lockstep at step boundaries, so no walk straddles a flip.
        plan = self._ring_plan if ranks is None else None
        if plan is not None:
            members = list(plan.order)
            weights = plan.weights
        else:
            members = list(range(self.size)) if ranks is None else list(ranks)
            weights = None
        k = len(members)
        if self.rank not in members or k == 1:
            w.forward()
            return None
        # capture the step-plane sink on THIS thread before any work:
        # the attribution calls at walk end run here too, but capturing
        # once keeps the contract identical to the graph walk's (whose
        # chunk jobs hop to pool threads)
        steptrace_sink = steptrace.current_sink()
        sched = topo.gen_segmented_schedule(members, members.index(self.rank))
        bounds = topo.segment_bounds(w.recv.size, k, weights)
        w.forward()  # seed the accumulator with own contribution
        acc = w.recv
        send_peer = self.peers[sched.send_peer]
        recv_peer = self.peers[sched.recv_peer]
        itemsize = acc.itemsize
        codec_label = wire.name.lower() if wire is not None else "off"

        def seg_wire_nbytes(count: int) -> int:
            """Bytes segment `count` elements occupy on the wire."""
            if wire is None:
                return count * itemsize
            return _wire_payload_nbytes(count, wire)

        bufpool = get_buffer_pool()
        deadline = time.monotonic() + self.timeout
        wire_bytes = 0
        raw_bytes = 0
        # critical-path attribution for this walk (profiler, ISSUE 6):
        # wait-on-recv and send-blocked seconds of THIS thread; the
        # reduce/codec compute is the residual against walk wall time
        prof = WalkProfile()
        emit_steps = self._span_sampler.sample()
        # all-gather wire buffer: segments stay encoded here from the
        # owner's single quantization until the walk-end decode. Leaked
        # (not pool-returned) on any error — the transport may still be
        # mid-fill into a timed-out sink slice. 16-bit codecs index it
        # by element (2 bytes each); the block-scaled quantizer's
        # variable-length segments get per-segment byte offsets (scales
        # + packed payload, blocks relative to each segment start — the
        # segment's single owner encodes every one of its scale blocks).
        wirebuf: Optional[bytearray] = None
        wirearr: Optional[np.ndarray] = None
        qoff: Optional[List[int]] = None
        if isinstance(wire, QWire):
            qoff = [0]
            for b, e in bounds:
                qoff.append(qoff[-1] + seg_wire_nbytes(e - b))
            wirebuf = bufpool.get(qoff[-1])
            wirearr = np.frombuffer(wirebuf, np.uint8, qoff[-1])
        elif wire is not None:
            wirebuf = bufpool.get(acc.size * 2)
            wirearr = np.frombuffer(wirebuf, np.uint16, acc.size)

        def ag_slice(seg: int) -> np.ndarray:
            """The wire buffer slice holding segment `seg`'s encoding."""
            b, e = bounds[seg]
            if qoff is not None:
                return wirearr[qoff[seg]:qoff[seg + 1]]
            return wirearr[b:e]

        # error feedback (quantized codec only): the un-transmitted
        # remainder of each quantized send, added back into the next
        # one. Full walks carry a session-store residual keyed by the
        # workspace name (flushed on mode changes and re-plans, dead on
        # resize); the standalone all-gather takes the caller's
        # per-shard buffer (`ef_owned`, ZeRO's weight leg). RS sends and
        # the AG seed touch DISJOINT slices (a peer never RS-sends the
        # segment it ends up owning), so each element's residual is
        # written at most once per walk — pool-thread encodes included.
        ef_full: Optional[np.ndarray] = None
        if isinstance(wire, QWire) and phase == "all":
            ef_full = self._ef_residual(w.name, acc.size)

        def encode_seg(payload: np.ndarray, sb: int, se: int,
                       ef: Optional[np.ndarray]) -> None:
            """Quantize acc[sb:se] into `payload`, folding the carried
            residual in and banking the new remainder (EF). Exact for
            the 16-bit codecs' callers too (ef is None there)."""
            if ef is None:
                encode_wire_any(payload, acc[sb:se], wire)
                return
            corrected = acc[sb:se] + ef
            encode_wire_any(payload, corrected, wire)
            decoded = np.empty(se - sb, np.float32)
            decode_wire_any(decoded, payload, wire)
            np.subtract(corrected, decoded, out=ef)

        def do_send(name: str, sb: int, se: int, buf) -> None:
            """Deadline-bounded send: a frozen successor (full shm ring
            -> socket fallback -> full TCP buffer) would otherwise block
            sendall forever and the walk-wide deadline — checked only in
            do_recv — would never fire. Dispatch + event-wait costs tens
            of µs per step, noise against the segment memcpy. A timed-out
            send thread is abandoned exactly like the graph walk's _par
            send threads; the buffer stays valid because the caller
            raises out of the walk without touching acc again."""
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TimeoutError(f"segmented walk timed out: {name}")
            done = threading.Event()
            errs: List[BaseException] = []

            def run() -> None:
                try:
                    # zero-copy: segments are disjoint and steps
                    # sequential per workspace, so this view cannot be
                    # mutated mid-sendall
                    self.client.send(
                        send_peer, name, _buf(buf), ConnType.COLLECTIVE
                    )
                except BaseException as e:  # noqa: BLE001 - re-raised below
                    errs.append(e)
                finally:
                    done.set()

            _t_send = time.perf_counter()
            get_pool().submit(run)
            ok = done.wait(remaining)
            prof.send += time.perf_counter() - _t_send
            if not ok:
                raise TimeoutError(f"segmented send timed out: {name}")
            if errs:
                raise errs[0]

        def start_send_wire(name: str, sb: int, se: int, buf, ef=None):
            """Async wire-mode send: encode (when `buf` is an f32 view)
            and transport copy run on the pool thread so they OVERLAP
            the blocking predecessor recv — the codec's encode would
            otherwise sit on the ring's serialized critical path, which
            a time-sliced multi-worker host punishes step after step.
            Safe because a step's send and recv segments are disjoint by
            schedule construction, so the thread reads acc[sb:se] (or a
            wirearr slice) and writes the disjoint residual slice `ef`
            while the main thread fills a different segment. Returns
            (done, errs) for finish_send; the encode scratch is
            pool-returned by the thread itself (never while anything can
            still read it)."""
            done = threading.Event()
            errs: List[BaseException] = []

            def run() -> None:
                try:
                    if buf.dtype != np.float32:
                        payload = buf  # all-gather: already wire-encoded
                        scratch = None
                    else:
                        nb = seg_wire_nbytes(se - sb)
                        scratch = bufpool.get(nb)
                        if qoff is not None:
                            payload = np.frombuffer(scratch, np.uint8, nb)
                        else:
                            payload = np.frombuffer(scratch, np.uint16, se - sb)
                        encode_seg(payload, sb, se, ef)
                    self.client.send(
                        send_peer, name, _buf(payload), ConnType.COLLECTIVE
                    )
                    if scratch is not None:
                        bufpool.put(scratch)
                except BaseException as e:  # noqa: BLE001 - re-raised below
                    errs.append(e)
                finally:
                    done.set()

            get_pool().submit(run)
            return done, errs

        def finish_send(pending, name: str) -> None:
            done, errs = pending
            remaining = deadline - time.monotonic()
            _t_send = time.perf_counter()
            ok = remaining > 0 and done.wait(remaining)
            prof.send += time.perf_counter() - _t_send
            if not ok:
                raise TimeoutError(f"segmented send timed out: {name}")
            if errs:
                raise errs[0]

        def recv_rs(name: str, rb: int, re_: int) -> None:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TimeoutError(f"segmented walk timed out: {name}")
            nb = seg_wire_nbytes(re_ - rb)
            if qoff is not None:
                recv_dtype, recv_count = np.dtype(np.uint8), nb
            elif wire is not None:
                recv_dtype, recv_count = np.dtype(np.uint16), re_ - rb
            else:
                recv_dtype, recv_count = acc.dtype, re_ - rb
            _t_recv = time.perf_counter()
            incoming, scratch, release = self._recv_collective(
                recv_peer, name, nb, recv_dtype, recv_count, remaining,
            )
            prof.wait += time.perf_counter() - _t_recv
            try:
                if cancel is not None and cancel.is_set():
                    # caller-scope timeout fired while we were blocked:
                    # the recv buffer may already be reused — a late
                    # arrival must not be reduced into it
                    raise TimeoutError(f"collective cancelled: {name}")
                if wire is not None:
                    # fused decode + f32 accumulate: one pass, one
                    # quantization deep (the sender's encode)
                    decode_accumulate_any(acc, rb, re_, incoming, wire, w.op)
                else:
                    reduce_segment(acc, rb, re_, incoming, w.op)
            finally:
                del incoming
                if release is not None:
                    release()
            if scratch is not None:
                bufpool.put(scratch)

        def recv_ag(name: str, seg: int, rb: int, re_: int) -> None:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TimeoutError(f"segmented walk timed out: {name}")
            if wire is None:
                _t_recv = time.perf_counter()
                incoming, scratch, release = self._recv_collective(
                    recv_peer, name, (re_ - rb) * itemsize, acc.dtype,
                    re_ - rb, remaining,
                )
                prof.wait += time.perf_counter() - _t_recv
                try:
                    if cancel is not None and cancel.is_set():
                        raise TimeoutError(f"collective cancelled: {name}")
                    copy_segment(acc, rb, re_, incoming)
                finally:
                    del incoming
                    if release is not None:
                        release()
                if scratch is not None:
                    bufpool.put(scratch)
                return
            # wire mode: deliver straight into the wire buffer slice —
            # no scratch, no decode (the segment is relayed as-is and
            # decoded once at walk end)
            if qoff is not None:
                byte_lo, byte_hi = qoff[seg], qoff[seg + 1]
            else:
                byte_lo, byte_hi = rb * 2, re_ * 2
            _t_recv = time.perf_counter()
            msg, filled = self.endpoint.recv_into(
                recv_peer, name, memoryview(wirebuf)[byte_lo:byte_hi],
                remaining,
            )
            prof.wait += time.perf_counter() - _t_recv
            if cancel is not None and cancel.is_set():
                if msg is not None and msg.release is not None:
                    msg.release()
                raise TimeoutError(f"collective cancelled: {name}")
            if not filled:
                try:
                    if qoff is not None:
                        np.copyto(
                            wirearr[byte_lo:byte_hi],
                            np.frombuffer(msg.data, np.uint8,
                                          byte_hi - byte_lo),
                        )
                    else:
                        np.copyto(
                            wirearr[rb:re_],
                            np.frombuffer(msg.data, np.uint16, re_ - rb),
                        )
                finally:
                    if msg.release is not None:
                        msg.release()

        def step(phase: str, s: int, send_seg: int, recv_seg: int) -> None:
            nonlocal wire_bytes, raw_bytes
            sb, se = bounds[send_seg]
            rb, re_ = bounds[recv_seg]
            name = f"{w.name}:{phase}{s}"
            if cancel is not None and cancel.is_set():
                raise TimeoutError(f"collective cancelled: {name}")
            # empty segments (payload < k elements) are skipped on BOTH
            # ends: sender and receiver compute identical bounds.
            # RAW mode: send-then-recv is deliberately SEQUENTIAL — the
            # send returns once the payload is in the shm ring / kernel
            # buffer, so the wire is already busy while we block on the
            # predecessor, and a _par pair per step measured 15% slower
            # on the 2-core bench box (thread dispatch + GIL beat the
            # overlap). WIRE mode: the encode pass makes the send phase
            # heavy enough to flip that trade — encode+send run async on
            # the pool thread and overlap the predecessor wait, awaited
            # at step end (disjoint segments make this safe).
            if se > sb:
                wire_bytes += seg_wire_nbytes(se - sb)
                raw_bytes += (se - sb) * itemsize
            if wire is not None:
                pending = None
                if se > sb:
                    if phase == "rs":
                        ef = ef_full[sb:se] if ef_full is not None else None
                        pending = start_send_wire(name, sb, se, acc[sb:se], ef)
                    else:
                        pending = start_send_wire(name, sb, se,
                                                  ag_slice(send_seg))
                if re_ > rb:
                    if phase == "rs":
                        recv_rs(name, rb, re_)
                    else:
                        recv_ag(name, recv_seg, rb, re_)
                if pending is not None:
                    finish_send(pending, name)
                return
            if se > sb:
                do_send(name, sb, se, acc[sb:se])
            if re_ > rb:
                if phase == "rs":
                    recv_rs(name, rb, re_)
                else:
                    recv_ag(name, recv_seg, rb, re_)

        def timed_step(span_name: str, phase: str, s: int, snd: int, rcv: int) -> None:
            """One ring step, with a per-step span (subject to
            KF_TELEMETRY_SPAN_SAMPLE) annotated with how long the step
            was blocked waiting on its predecessor vs its successor."""
            if not emit_steps:
                step(phase, s, snd, rcv)
                return
            w0, s0 = prof.wait, prof.send
            with trace.span(span_name, step=s, k=k) as sp:
                step(phase, s, snd, rcv)
                sp.args["wait_us"] = round((prof.wait - w0) * 1e6)
                sp.args["send_us"] = round((prof.send - s0) * 1e6)

        _t0 = time.perf_counter()
        if phase != "ag":
            for s, (snd, rcv) in enumerate(sched.rs_steps):
                timed_step("host.rs.step", "rs", s, snd, rcv)
        if phase == "rs":
            self._count_wire(
                wire_bytes, Strategy.RING_SEGMENTED.name, "off", raw_bytes
            )
            wall = time.perf_counter() - _t0
            trace.record(f"host.rs[{w.recv.nbytes >> 20}MiB]", wall)
            # half walks move (k-1)/k·N = the optimal 2(k-1)/k volume of
            # HALF the payload: score against the halved payload so the
            # profiler's efficiency ratio stays meaningful
            self._record_walk(
                Strategy.RING_SEGMENTED.name, k, w.recv.nbytes // 2, wall,
                prof, dsts=[send_peer], sink=steptrace_sink,
            )
            return None
        if wire is not None:
            # seed the all-gather: quantize the owned (fully reduced)
            # segment ONCE; every peer — self included — will decode
            # this same encoding, so results stay bit-identical ringwide
            ob, oe = bounds[sched.owned_segment]
            if oe > ob:
                ef = None
                if isinstance(wire, QWire):
                    if ef_owned is not None and ef_owned.size != oe - ob:
                        raise ValueError(
                            f"ef residual of {ef_owned.size} elements for "
                            f"owned segment [{ob}:{oe}) — caller sharded "
                            "differently"
                        )
                    ef = ef_owned
                    if ef is None and ef_full is not None:
                        ef = ef_full[ob:oe]
                encode_seg(ag_slice(sched.owned_segment), ob, oe, ef)
        for s, (snd, rcv) in enumerate(sched.ag_steps):
            timed_step("host.ag.step", "ag", s, snd, rcv)
        if cancel is not None and cancel.is_set():
            # KF703: a sibling in the group scope timed out while our
            # steps completed — acc may belong to a caller that already
            # raised, so observe the abort before the walk-end decode
            # writes it (wirebuf deliberately leaks, pool policy)
            raise TimeoutError(f"collective cancelled: {w.name}")
        deferred: Optional[DeferredDecode] = None
        if wire is not None:
            if defer_decode and qoff is None:
                deferred = DeferredDecode(wire, wirebuf, wirearr)
            elif qoff is not None:
                # block-scaled: segments decode individually (each one's
                # scale blocks are relative to its own start)
                with trace.span("host.wire.decode", bytes=int(qoff[-1])):
                    for i, (b, e) in enumerate(bounds):
                        if e > b:
                            decode_wire_any(acc[b:e], ag_slice(i), wire)
                bufpool.put(wirebuf)
            else:
                with trace.span("host.wire.decode", bytes=int(acc.size * 2)):
                    decode_wire_any(acc, wirearr, wire)
                bufpool.put(wirebuf)
        self._count_wire(
            wire_bytes, Strategy.RING_SEGMENTED.name, codec_label, raw_bytes
        )
        wall = time.perf_counter() - _t0
        trace.record(f"host.segmented[{w.recv.nbytes >> 20}MiB]", wall)
        # the ring's only outgoing edge is the successor: score this walk
        # against that link's measured bandwidth (half walks against the
        # correspondingly halved payload, see the rs return above)
        self._record_walk(
            Strategy.RING_SEGMENTED.name, k,
            w.recv.nbytes if phase == "all" else w.recv.nbytes // 2,
            wall, prof, dsts=[send_peer], sink=steptrace_sink,
        )
        return deferred

    # ------------------------------------------------------------------
    # two-level (hierarchical) walk — ISSUE 19
    # ------------------------------------------------------------------

    def _run_hier(
        self,
        w: Workspace,
        cancel: Optional[threading.Event] = None,
        wire: Optional[DType] = None,
    ) -> None:
        """Two-level allreduce over the adopted :class:`HierPlan`
        (arXiv:1909.09756's 2D shape): (1) intra-host star reduce of
        every contributing member onto its host head — the fast
        shm/loopback links, always exact f32; (2) segmented ring
        allreduce over the heads only (`_run_segmented`'s subset
        variant) — the DCN leg, wire-codec-eligible; (3) intra-host
        star broadcast of the result back to every member, demoted
        peers included.

        Demoted ranks (:attr:`HierPlan.demoted`) contribute NOTHING —
        they skip phases 1–2 and receive the result in phase 3, so a
        persistent straggler stops serializing the ring (the source
        paper's adaptive peer selection). On exact payloads the result
        is bit-identical to the flat segmented walk over the active
        set; with the codec, phase 2's once-per-owner quantization
        keeps heads bit-identical and phase 3 relays those exact f32
        bytes.

        Messages reuse the flat walk's naming discipline: intra legs
        rendezvous on ``w.name`` (directions disambiguate reduce vs
        broadcast, like the graph walks' (reduce, bcast) pairs), the
        inter ring on ``w.name:x:{rs,ag}{step}`` — disjoint from any
        flat walk name, so a peer that missed the lockstep adoption
        fails on a named rendezvous, never reduces into the wrong
        buffer."""
        plan = self._hier_plan
        if plan is None or plan.size != self.size:
            # stale plan (resize raced the flip) — the flat walk is
            # always correct
            self._run_segmented(w, cancel=cancel, wire=wire)
            return
        if w.is_empty:
            w.forward()
            return
        dem = set(plan.demoted)
        n = self.size
        heads = list(plan.heads)
        # phase 1: intra star reduce, members → head (exact f32)
        reduce_g = Graph(n)
        for head, grp in zip(plan.heads, plan.groups):
            members = [r for r in grp if r != head and r not in dem]
            if members:
                reduce_g.add_edge(head, head)
                for r in members:
                    reduce_g.add_edge(r, head)
        prev_label = self._wire_label_override
        self._wire_label_override = "HIER_INTRA"
        try:
            self._run_graphs(w, [reduce_g], cancel, None)
        finally:
            self._wire_label_override = prev_label
        # phase 2: segmented ring over the heads, INPLACE over the
        # group-reduced recv (non-heads forward(), a no-op inplace)
        wx = Workspace(send=w.recv, recv=w.recv, op=w.op,
                       name=f"{w.name}:x")
        self._run_segmented(wx, ranks=heads, cancel=cancel, wire=wire)
        # phase 3: intra star broadcast, head → every member (demoted
        # included), inplace so the head's forward() keeps its result
        bcast_g = Graph(n)
        for head, grp in zip(plan.heads, plan.groups):
            for r in grp:
                if r != head:
                    bcast_g.add_edge(head, r)
        wb = Workspace(send=w.recv, recv=w.recv, op=w.op, name=w.name)
        self._wire_label_override = "HIER_INTRA"
        try:
            self._run_graphs(wb, [bcast_g], cancel, None)
        finally:
            self._wire_label_override = prev_label

    # ------------------------------------------------------------------
    # chunked graph walks
    # ------------------------------------------------------------------

    def _run_strategies(
        self,
        w: Workspace,
        strategies: List[st.StrategyPair],
        cancel: Optional[threading.Event] = None,
        wire: Optional[DType] = None,
    ) -> None:
        """`wire` is decided ONCE on the whole workspace (in
        _allreduce_ws) and inherited by every chunk — a per-chunk
        decision would let a residual chunk fall below WIRE_MIN_BYTES
        and mix wire formats inside one collective (still cluster-
        consistent, but pointlessly branchy on the hot path)."""
        total = w.recv.size * w.recv.itemsize
        k = max(1, -(-total // choose_chunk_bytes(total)))
        chunks = w.split(even_partition, k) if k > 1 else [w]
        if cancel is None:
            cancel = threading.Event()
        # capture the step-plane sink HERE (the submitting walk thread):
        # chunk jobs execute on pool threads, where the thread-local
        # sink of the scheduler's walker would be invisible
        sink = steptrace.current_sink()
        if k == 1:
            pair = strategies[0]
            self._run_graphs(
                chunks[0], [pair.reduce_graph, pair.bcast_graph], cancel,
                wire, profile=True, sink=sink,
            )
            return
        jobs = []
        for i, chunk in enumerate(chunks):
            pair = st.choose(strategies, i)
            jobs.append(
                lambda c=chunk, p=pair: self._run_graphs(
                    c, [p.reduce_graph, p.bcast_graph], cancel, wire,
                    profile=True, sink=sink,
                )
            )
        _par(jobs, self.timeout, cancel)

    def _run_graphs(
        self,
        w: Workspace,
        graphs: List[Graph],
        cancel: Optional[threading.Event] = None,
        wire: Optional[DType] = None,
        profile: bool = False,
        sink=None,
    ) -> None:
        """The hot walk; parity: runGraphs (session.go:231-299).

        `profile=True` (the allreduce paths, via _run_strategies) feeds
        this walk's wait/send/compute attribution to the process
        WalkProfiler; direct reduce/broadcast/gather walks skip it (the
        2(k-1)/k*N allreduce bound doesn't describe them).

        `cancel` is shared across every thread touching this workspace: once
        any part of the collective times out, late-arriving receives must not
        write into (possibly reused) caller buffers.

        With `wire` set, every send encodes the f32 buffer into a pooled
        bf16/f16 scratch and every receive decode-accumulates (reduce
        phase) or decodes (bcast phase) back into f32 — accumulation
        never happens in 16-bit storage. Relays re-encode values that
        are already wire-quantized, which is exact (encode of an
        exactly-representable value is the identity), so the quantized
        result every peer converges on is bit-identical."""
        if w.is_empty:
            return
        if all(g.is_isolated(self.rank) for g in graphs):
            w.forward()
            return
        if cancel is None:
            cancel = threading.Event()
        _t_walk = time.perf_counter()
        prof = WalkProfile() if profile else None

        state = {"recv_count": 0}
        lock = threading.Lock()

        def effective() -> np.ndarray:
            if state["recv_count"] > 0 or w.is_inplace:
                return w.recv
            return w.send

        wire_label = self._walk_label()
        codec_label = wire.name.lower() if wire is not None else "off"

        def send_to(peer: PeerID, flags: Flags = Flags.NONE) -> None:
            # zero-copy: the walk's phases are sequential per chunk, so the
            # buffer cannot be mutated while sendall drains it
            self.client.send(
                peer, w.name, _buf(effective()), ConnType.COLLECTIVE, flags
            )
            self._count_wire(wire_nbytes, wire_label, codec_label, nbytes)

        def send_all(peers: List[PeerID], flags: Flags = Flags.NONE) -> None:
            """Fan-out send of the current effective() buffer. Wire mode
            encodes ONCE into a shared scratch for the whole fan-out —
            every edge carries identical bytes, so per-peer encodes (a
            full payload pass each) would be pure waste at STAR/CLIQUE
            fan-outs. The scratch returns to the pool only on success:
            after a timeout an abandoned send thread may still be
            draining it."""
            if not peers:
                return
            if wire is None:
                _t_send = time.perf_counter()
                _par([lambda p=p: send_to(p, flags) for p in peers],
                     self.timeout, cancel)
                if prof is not None:
                    prof.send += time.perf_counter() - _t_send
                return
            scratch = bufpool.get(wire_nbytes)
            enc = np.frombuffer(scratch, wire_np_dtype, wire_count)
            # the fan-out encode is codec COMPUTE (the residual bucket),
            # so only the transport fan-out below is timed as send.
            # Quantized payloads re-encode idempotently (pow2 scales):
            # a relay that decoded q-bytes re-produces those exact
            # bytes, so graph fan-outs need no error feedback to stay
            # bit-identical.
            encode_wire_any(enc, effective(), wire)

            def send_enc(peer: PeerID) -> None:
                self.client.send(
                    peer, w.name, _buf(enc), ConnType.COLLECTIVE, flags
                )
                self._count_wire(wire_nbytes, wire_label, codec_label, nbytes)

            _t_send = time.perf_counter()
            _par([lambda p=p: send_enc(p) for p in peers], self.timeout, cancel)
            if prof is not None:
                prof.send += time.perf_counter() - _t_send
            bufpool.put(scratch)

        bufpool = get_buffer_pool()
        nbytes = w.recv.size * w.recv.itemsize
        wire_nbytes = (
            _wire_payload_nbytes(w.recv.size, wire) if wire is not None
            else nbytes
        )
        if isinstance(wire, QWire):
            # block-scaled payload: scales + packed bytes, u8-framed
            wire_np_dtype, wire_count = np.dtype(np.uint8), wire_nbytes
        elif wire is not None:
            wire_np_dtype, wire_count = np.dtype(np.uint16), w.recv.size
        else:
            wire_np_dtype, wire_count = w.send.dtype, w.recv.size

        def recv_payload(peer: PeerID):
            """See _recv_collective (shared with the segmented walk)."""
            return self._recv_collective(
                peer, w.name, wire_nbytes, wire_np_dtype, wire_count,
                self.timeout
            )

        def recv_onto(peer: PeerID) -> None:
            incoming, scratch, release = recv_payload(peer)
            try:
                with lock:
                    if cancel.is_set():
                        # abort the whole walk: a late arrival must neither
                        # write the workspace nor let the send phase relay
                        # stale data
                        raise TimeoutError(f"collective cancelled: {w.name}")
                    if wire is not None:
                        if state["recv_count"] == 0 and not w.is_inplace:
                            # first arrival: recv = decode(incoming), then
                            # fold own send in f32 (ops are commutative)
                            decode_wire_any(w.recv, incoming, wire)
                            reduce_inplace(w.recv, w.send, w.op)
                        else:
                            decode_accumulate_any(
                                w.recv, 0, w.recv.size, incoming, wire, w.op
                            )
                    elif state["recv_count"] == 0 and not w.is_inplace:
                        # first arrival: recv = send (op) incoming
                        from kungfu_tpu.base.ops import transform2

                        transform2(w.recv, w.send, incoming, w.op)
                    else:
                        reduce_inplace(w.recv, incoming, w.op)
                    state["recv_count"] += 1
            finally:
                del incoming
                if release is not None:
                    release()
            if scratch is not None:
                bufpool.put(scratch)

        def recv_all_onto(peers: List[PeerID]) -> None:
            """Accumulate phase: receive every prev, then reduce them all
            in ONE n-ary pass (kf_transform_n). Pairwise-on-arrival
            overlaps receive with reduce, which pays when cores are free;
            the n-ary pass minimizes memory traffic, which wins outright
            on busy/low-core hosts — and the receives themselves still
            overlap each other."""
            got: List = [None] * len(peers)

            def grab(i: int, p: PeerID) -> None:
                res = recv_payload(p)
                if cancel.is_set():
                    # the walk already timed out and its finally block may
                    # have run: release the borrow here or nobody will
                    if res[2] is not None:
                        res[2]()
                    return
                got[i] = res

            try:
                _t_recv = time.perf_counter()
                _par(
                    [lambda i=i, p=p: grab(i, p) for i, p in enumerate(peers)],
                    self.timeout,
                    cancel,
                )
                if prof is not None:
                    prof.wait += time.perf_counter() - _t_recv
                with lock:
                    if cancel.is_set():
                        raise TimeoutError(f"collective cancelled: {w.name}")
                    if wire is not None:
                        # decode-accumulate each arrival into f32 (the
                        # fused kernel; no n-ary variant exists for mixed
                        # wire/f32 sources and the tree fan-in is small)
                        if not w.is_inplace:
                            w.forward()
                        for incoming, _, _ in got:
                            decode_accumulate_any(
                                w.recv, 0, w.recv.size, incoming, wire, w.op
                            )
                    elif w.is_inplace:
                        for incoming, _, _ in got:
                            reduce_inplace(w.recv, incoming, w.op)
                    else:
                        transform_n(
                            w.recv,
                            [w.send] + [inc for inc, _, _ in got],
                            w.op,
                        )
                    state["recv_count"] += len(peers)
            finally:
                for item in got:
                    if item is not None and item[2] is not None:
                        item[2]()
            for item in got:
                if item is not None and item[1] is not None:
                    bufpool.put(item[1])

        def recv_into(peer: PeerID) -> None:
            incoming, scratch, release = recv_payload(peer)
            try:
                with lock:
                    if cancel.is_set():
                        raise TimeoutError(f"collective cancelled: {w.name}")
                    if wire is not None:
                        decode_wire_any(w.recv, incoming, wire)
                    else:
                        np.copyto(w.recv, incoming)
                    state["recv_count"] += 1
            finally:
                del incoming
                if release is not None:
                    release()
            if scratch is not None:
                bufpool.put(scratch)

        for g in graphs:
            prevs = [self.peers[r] for r in g.prevs(self.rank)]
            nexts = [self.peers[r] for r in g.nexts(self.rank)]
            if g.is_self_loop(self.rank):
                # accumulate: receive from all prevs, n-ary reduce, send on
                if prevs and state["recv_count"] == 0:
                    recv_all_onto(prevs)
                elif prevs:
                    # pairwise path: the pool threads fold their reduce
                    # into this timed block (profiler caveat, see
                    # WalkProfiler) — receives dominate it
                    _t_recv = time.perf_counter()
                    _par([lambda p=p: recv_onto(p) for p in prevs], self.timeout, cancel)
                    if prof is not None:
                        prof.wait += time.perf_counter() - _t_recv
                send_all(nexts)
            else:
                # pass-through node: take value from single prev (or forward
                # own), relay to nexts
                if not prevs and state["recv_count"] == 0:
                    w.forward()
                else:
                    _t_recv = time.perf_counter()
                    for p in prevs:
                        recv_into(p)
                    if prof is not None:
                        prof.wait += time.perf_counter() - _t_recv
                send_all(nexts, Flags.WAIT_RECV_BUF)
        if cancel.is_set():
            # KF703: the group scope aborted while this walk's own edges
            # completed — w.recv may already be reused by the caller that
            # raised, so the root's codec roundtrip below must not touch it
            raise TimeoutError(f"collective cancelled: {w.name}")
        if wire is not None and not graphs[-1].prevs(self.rank):
            # the bcast root never receives a wire message, so it would
            # keep its full-precision f32 result while every other peer
            # decodes the quantized broadcast: roundtrip the root's recv
            # through the codec so all peers land on bit-identical values
            scratch = bufpool.get(wire_nbytes)
            enc = np.frombuffer(scratch, wire_np_dtype, wire_count)
            encode_wire_any(enc, w.recv, wire)
            decode_wire_any(w.recv, enc, wire)
            bufpool.put(scratch)
        wall = time.perf_counter() - _t_walk
        trace.record(f"host.walk[{w.recv.nbytes >> 20}MiB]", wall)
        if prof is not None:
            # graph walks fan out over many edges: score against the
            # slowest estimated link overall (dsts=None)
            self._record_walk(
                wire_label, self.size, w.recv.nbytes, wall, prof, sink=sink
            )
