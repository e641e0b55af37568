"""User-facing process API.

Capability parity: srcs/python/kungfu/python/__init__.py:17-168 —
current_rank/cluster_size/local metadata, barrier, resize/propose,
all_reduce helpers — backed by the in-process Peer singleton instead of
ctypes into libkungfu.
"""

from __future__ import annotations

import atexit
import threading
from typing import Optional, Sequence

import numpy as np

from kungfu_tpu.base.ops import ReduceOp
from kungfu_tpu.base.strategy import Strategy
from kungfu_tpu.base.workspace import Workspace
from kungfu_tpu.peer import finalize_default_peer, get_default_peer
from kungfu_tpu.transport.message import ConnType as _ConnType

atexit.register(finalize_default_peer)


def current_rank() -> int:
    return get_default_peer().rank


def cluster_size() -> int:
    return get_default_peer().size


def current_local_rank() -> int:
    return get_default_peer().current_session().local_rank


def current_local_size() -> int:
    return get_default_peer().current_session().local_size


def host_count() -> int:
    return get_default_peer().current_session().host_count


def current_cluster_version() -> int:
    return get_default_peer().cluster_version


def uid() -> int:
    """(version, rank) packed; parity: python/__init__.py uid. Rank gets the
    low 32 bits so the version never collides with it (a 16-bit version
    field would silently wrap after 65k resizes)."""
    p = get_default_peer()
    return (p.cluster_version << 32) | p.rank


def detached() -> bool:
    return get_default_peer().detached


def run_barrier() -> None:
    get_default_peer().current_session().barrier()


def all_reduce_array(
    x: np.ndarray, op: ReduceOp = ReduceOp.SUM, name: str = "user"
) -> np.ndarray:
    """Host-plane allreduce of a numpy array (control data, NOT gradients —
    those belong on the ICI plane via kungfu_tpu.ops)."""
    flat = np.ascontiguousarray(x).reshape(-1)
    # empty, not zeros: every element of recv is written by the graph walk
    # (forward / transform2 / copyto), and zeroing 100 MB gradient sets per
    # call is measurable
    out = np.empty_like(flat)
    w = Workspace(send=flat, recv=out, op=op, name=f"kungfu::user::{name}")
    get_default_peer().current_session().all_reduce(w)
    return out.reshape(x.shape)


def group_all_reduce_arrays(
    xs, op: ReduceOp = ReduceOp.SUM, name: str = "group", outs=None
):
    """Host-plane allreduce of a list of arrays (one fused/windowed group
    op — the way the reference reduces a whole gradient set). Pass
    `outs` (same shapes/dtypes as `xs`) to reuse result buffers across
    steps — the reference's TF op outputs are graph-allocated once, and
    fresh 100 MB of np.empty per step costs real page-fault time."""
    flats = [np.ascontiguousarray(x).reshape(-1) for x in xs]
    flat_outs = _group_outs(xs, flats, outs)
    ws = [
        Workspace(send=f, recv=o, op=op, name=f"kungfu::user::{name}:{i}")
        for i, (f, o) in enumerate(zip(flats, flat_outs))
    ]
    get_default_peer().current_session().group_all_reduce(ws)
    return [o.reshape(x.shape) for o, x in zip(flat_outs, xs)]


class AsyncGroupResult:
    """Handle for one round of asynchronous group allreduce
    (:func:`group_all_reduce_async`): ``wait()`` blocks until every
    submitted tensor has been reduced and returns the results (the
    ``outs`` buffers, reshaped). With the scheduler disabled
    (``KF_CONFIG_ASYNC=off``) the group already ran synchronously
    INSIDE the submitting call — results are complete before the handle
    exists, ``wait()`` just returns them and ``timeout`` is moot — so
    the submit-per-tensor + ``flush_async()`` pattern works identically
    under either knob value (one code path, A/B by knob)."""

    def __init__(self, sess, flat_outs, xs, round_index=None):
        self._sess = sess
        self._flat_outs = flat_outs
        self._xs = xs
        self._round = round_index  # scheduler round; None = sync fallback
        self._done = round_index is None

    def wait(self, timeout=None):
        if not self._done:
            # round-aware: several handles of the same round each call
            # wait() (the documented per-tensor pattern) — only the
            # first actually flushes; the rest see the round already
            # advanced and return immediately
            self._sess.scheduler().flush_round(self._round, timeout=timeout)
            self._done = True
        return [o.reshape(x.shape) for o, x in zip(self._flat_outs, self._xs)]


def group_all_reduce_async(
    xs, op: ReduceOp = ReduceOp.SUM, name: str = "group", outs=None
) -> AsyncGroupResult:
    """Asynchronous host-plane group allreduce (ISSUE 10): each array is
    SUBMITTED to the session's background collective scheduler as soon
    as this call sees it — buckets launch and walk while the caller
    keeps computing (the backprop-overlap path) — and the returned
    handle's ``wait()`` blocks only for the tail. Call once per tensor
    as gradients become ready (1-element lists), or with the whole set.

    Tensor identity: ``(name, index)`` must be stable across steps —
    the first step's submission order is negotiated cluster-wide as the
    launch order (consensus-checked), and every later step must submit
    the same set (in any order). Results are bit-identical to
    :func:`group_all_reduce_arrays` on the same inputs. Pass ``outs``
    to reuse result buffers across steps like the sync API."""
    flats = [np.ascontiguousarray(x).reshape(-1) for x in xs]
    flat_outs = _group_outs(xs, flats, outs)
    sess = get_default_peer().current_session()
    if not sess.async_enabled():
        # synchronous fallback, executed EAGERLY: callers following the
        # submit + flush_async() pattern never touch the handle, so a
        # deferred group would silently not run. Name notes: unlike the
        # scheduler path (stable names, scheduler-stamped rounds), each
        # call needs its OWN wire names — a fast peer's step k+1 sends
        # must never be consumed by a slower peer still receiving step
        # k. Peers call in identical program order, so the process-
        # local sequence agrees.
        with _async_seq_lock:
            seq = _async_seq[0]
            _async_seq[0] += 1
        ws = [
            Workspace(send=f, recv=o, op=op,
                      name=f"kungfu::user::async:{name}:{i}@{seq}")
            for i, (f, o) in enumerate(zip(flats, flat_outs))
        ]
        sess.group_all_reduce(ws)
        return AsyncGroupResult(sess, flat_outs, xs)
    sched = sess.scheduler()
    ws = [
        Workspace(send=f, recv=o, op=op, name=f"kungfu::user::async:{name}:{i}")
        for i, (f, o) in enumerate(zip(flats, flat_outs))
    ]
    for w in ws:
        sched.submit(w)
    return AsyncGroupResult(sess, flat_outs, xs, round_index=sched.round_index())


def flush_async(timeout=None) -> None:
    """End the current async round: block until every workspace
    submitted to the session's scheduler has completed (no-op when the
    scheduler is off, unused this epoch, or the round is empty — a
    defensive flush never freezes an empty registration). The per-round
    barrier of the submission API — call once per training step."""
    sess = get_default_peer().current_session()
    if sess.async_enabled():
        sess.scheduler().flush(timeout=timeout)


_async_seq = [0]
_async_seq_lock = threading.Lock()


def _group_outs(xs, flats, outs):
    """Shared outs validation of the group allreduce APIs: C-contiguous,
    size- and dtype-matched — mismatches reach the native reduce as raw
    pointers, so they must fail here, not corrupt memory there."""
    if outs is None:
        return [np.empty_like(f) for f in flats]
    if len(outs) != len(xs):
        raise ValueError(f"outs mismatch: {len(outs)} != {len(xs)}")
    for i, (o, f) in enumerate(zip(outs, flats)):
        # reshape(-1) of a non-contiguous array is a COPY — the
        # collective would fill the copy and the caller's buffer
        # would silently keep last step's data
        if not o.flags["C_CONTIGUOUS"]:
            raise ValueError("outs arrays must be C-contiguous")
        if o.size != f.size:
            raise ValueError(f"outs[{i}] size {o.size} != input size {f.size}")
        if o.dtype != f.dtype:
            raise ValueError(
                f"outs[{i}] dtype {o.dtype} != input dtype {f.dtype}"
            )
    return [o.reshape(-1) for o in outs]


def reduce_scatter(
    x: np.ndarray, op: ReduceOp = ReduceOp.SUM, name: str = "user"
) -> np.ndarray:
    """First-class reduce-scatter (ISSUE 11): reduce `x` across the
    cluster and return only this rank's owned 1/k shard — the RS half of
    the segmented ring walk, (k-1)/k·N bytes per peer, f32-exact. The
    shard layout is the session's ``owned_bounds`` (contiguous
    ``segment_bounds`` slices of the FLATTENED array under the current
    ring plan — equal, or measured-topology re-planned, ISSUE 14),
    identical on every peer without negotiation; ranks beyond the
    element count get an empty shard (the n<k edge the segmented walk
    already handles). ``all_gather(reduce_scatter(x))`` ==
    ``all_reduce_array(x)`` bit for bit."""
    flat = np.ascontiguousarray(x).reshape(-1)
    out = np.empty_like(flat)
    w = Workspace(send=flat, recv=out, op=op, name=f"kungfu::user::rs:{name}")
    b, e = get_default_peer().current_session().reduce_scatter(w)
    return out[b:e].copy()


def all_gather(shard: np.ndarray, name: str = "user") -> np.ndarray:
    """Standalone segment all-gather (ISSUE 11): every rank contributes
    its owned shard (the ``reduce_scatter`` layout) and receives the
    reassembled full array, identical on all peers. The shard must be
    exactly this rank's ``owned_segment_bounds`` slice — a mismatched
    size fails fast here, not as a wire-framing corruption. Rides the
    wire codec like allreduce (bf16 on the wire for eligible f32
    payloads, each segment quantized once by its owner; see
    docs/collectives.md for the error model)."""
    sess = get_default_peer().current_session()
    flat = np.ascontiguousarray(shard).reshape(-1)
    # one int64 lane agrees the total element count (shard sizes differ
    # across ranks under the segment partition, so it is not derivable
    # locally); exact, never compressed
    total = int(all_reduce_array(
        np.array([flat.size], np.int64), ReduceOp.SUM, f"agsz:{name}"
    )[0])
    # plan-aware: the owned-segment layout follows the session's current
    # ring plan (naive, or measured-topology re-planned — ISSUE 14)
    b, e = sess.owned_bounds(total)
    if flat.size != e - b:
        raise ValueError(
            f"all_gather shard has {flat.size} elements but rank "
            f"{sess.rank} owns [{b}:{e}) of {total} — shards must follow "
            "the reduce_scatter layout (owned_segment_bounds)"
        )
    full = np.empty(total, flat.dtype)
    full[b:e] = flat
    sess.all_gather_shards(full, f"kungfu::user::ag:{name}")
    return full


def sharded_update_session(
    params, lr: float, momentum: float = 0.0, name: str = "zero",
    restore_state: "Optional[bytes]" = None,
):
    """Build a :class:`~kungfu_tpu.collective.zero.ShardedUpdateSession`
    — the ZeRO-1 sharded SGD update over the current session (ISSUE 11):
    reduce-scatter gradients, update (and hold optimizer state for) only
    this rank's 1/k shard, all-gather the updated weights (bf16 on the
    wire when the codec wins). See the module docstring for the
    synchronous and scheduler-overlapped driving patterns and the
    resize/re-shard contract (`export_state`/`restore_state`)."""
    from kungfu_tpu.collective.zero import ShardedSGD, ShardedUpdateSession

    return ShardedUpdateSession(
        params, ShardedSGD(lr, momentum=momentum), name=name,
        session=get_default_peer().current_session(),
        restore_state=restore_state,
    )


def broadcast_array(x: np.ndarray, root: int = 0, name: str = "user") -> np.ndarray:
    """Host-plane broadcast from `root` (arbitrary roots, parity: the
    reference's Broadcast op)."""
    flat = np.ascontiguousarray(x).reshape(-1)
    # no root-side copy needed: the bcast root has no prevs, so the graph
    # walk's forward() performs the send->recv copy itself
    out = np.empty_like(flat)
    w = Workspace(send=flat, recv=out, op=ReduceOp.SUM,
                  name=f"kungfu::user::bcast:{name}")
    get_default_peer().current_session().broadcast(w, root=root)
    return out.reshape(x.shape)


def gather_arrays(x: np.ndarray, root: int = 0, name: str = "user"):
    """Host-plane gather of equal-shaped contributions to `root`; returns
    the (size, *x.shape) stack at the root, None elsewhere (parity:
    Gather, arbitrary roots)."""
    sess = get_default_peer().current_session()
    flat = np.ascontiguousarray(x).reshape(-1)
    recv = (
        np.empty(flat.size * sess.size, flat.dtype)
        if sess.rank == root
        else np.empty(0, flat.dtype)
    )
    w = Workspace(send=flat, recv=recv, op=ReduceOp.SUM,
                  name=f"kungfu::user::gather:{name}")
    sess.gather(w, root=root)
    if sess.rank != root:
        return None
    return recv.reshape((sess.size,) + x.shape)


def all_reduce_int_max(x: int) -> int:
    out = all_reduce_array(np.array([x], np.int64), ReduceOp.MAX, "int-max")
    return int(out[0])


def consensus(data: bytes, name: str = "user") -> bool:
    return get_default_peer().current_session().bytes_consensus(data, name)


def resize(new_size: Optional[int] = None):
    """Resize the cluster; returns (changed, detached).

    With new_size=None, pulls the desired cluster from the config server
    (parity: resize_cluster_from_url); otherwise grows/shrinks to new_size.
    """
    p = get_default_peer()
    if new_size is None:
        return p.resize_cluster_from_url()
    return p.resize_cluster(new_size)


def propose_new_size(new_size: int) -> None:
    get_default_peer().propose_new_size(new_size)


def last_resize_phases() -> dict:
    """Per-phase ms breakdown of the most recent resize seen by this peer.

    A peer that lived through a delta resize: `wait_config_ms`,
    `consensus_ms`, `notify_ms` (rank 0), `update_ms`, each the duration
    of its `resize.*` span. A worker that a reload started, once its
    `ElasticState` has seen the first step end: the whole pause as
    `elastic.state.pause_parts` makes it from the marks that came with
    the worker and its own ring (`agree_ms` with the old workers'
    `wait_config_ms`, `consensus_ms`, `notify_ms` inside it, `kill_ms`,
    `spawn_ms`, `import_ms`, `startup_ms`, `device_plane_ms`,
    `restore_ms`, `broadcast_ms`, `compile_ms` with `compile_hits` and
    `compile_misses`, `first_step_ms`, `pause_ms`, `unaccounted_ms`).
    {} before any resize: a first incarnation knows of none."""
    return dict(get_default_peer().last_resize_phases)


def trace_summary(prefix: str = "") -> dict:
    """Total ms per hot-path span recorded in this process (transport
    send/recv, collective walks, fuse pack/unpack, elastic state sync) —
    parity: the reference compiles TRACE_SCOPE into its GPU hot paths
    (trace.hpp under srcs/cpp/include/kungfu/utils, gpu_collective.cpp)."""
    from kungfu_tpu.telemetry import tracing as trace

    return trace.summary_ms(prefix)


def telemetry_dump(prefix: str = "") -> dict:
    """Snapshot of the whole telemetry subsystem: Prometheus metrics
    text, Chrome-trace JSON, resize audit records and a per-span ms
    summary (see kungfu_tpu.telemetry.dump)."""
    from kungfu_tpu import telemetry

    return telemetry.dump(prefix)


def resize_audit() -> list:
    """The elastic resize audit records of this process, as dicts
    (old/new cluster, trigger, per-phase durations, progress)."""
    from kungfu_tpu.telemetry import audit

    return [r.to_json() for r in audit.records(kind="resize")]


def metrics_text() -> str:
    """Prometheus text exposition of the process metrics registry — the
    same body the per-worker /metrics endpoint serves."""
    from kungfu_tpu.telemetry import metrics

    return metrics.render()


def change_cluster(progress: int, before_notify=None):
    return get_default_peer().change_cluster(progress, before_notify)


def monitored_all_reduce_array(
    x: np.ndarray, op: ReduceOp = ReduceOp.SUM, name: str = "user"
) -> np.ndarray:
    """Host-plane allreduce with throughput accounting feeding the adaptive
    controller (parity: MonitoredAllReduce op)."""
    flat = np.ascontiguousarray(x).reshape(-1)
    # empty, not zeros: every element of recv is written by the graph walk
    # (forward / transform2 / copyto), and zeroing 100 MB gradient sets per
    # call is measurable
    out = np.empty_like(flat)
    w = Workspace(send=flat, recv=out, op=op, name=f"kungfu::monitored::{name}")
    get_default_peer().current_session().monitored_all_reduce(w)
    return out.reshape(x.shape)


def check_interference() -> bool:
    """Vote on interference; True if the cluster switched strategy (parity:
    check_interference, session/adaptiveStrategies.go:61-121)."""
    return get_default_peer().current_session().check_interference()


def check_replan(want: bool = True, min_gain: float = 1.05) -> bool:
    """One lockstep measured-topology re-plan round (ISSUE 14): vote,
    exchange link rows, derive, digest-assert + adopt. Call on EVERY
    peer at the same step boundary (the collective contract — see
    ``policy.ReplanPolicy``, which drives this on an interval); a no-op
    unless ``KF_CONFIG_REPLAN`` is on. True if a plan was adopted."""
    sess = get_default_peer().current_session()
    return sess.check_replan(want=want, min_gain=min_gain) is not None


def active_strategy() -> "Optional[Strategy]":
    """The running adaptive candidate's Strategy (the enum), or None
    under a set_tree override. ISSUE 10 satellite: this used to return
    the codec-qualified display string while its callers expected the
    Strategy — the string contract now lives in its own accessor,
    :func:`active_candidate`."""
    return get_default_peer().current_session().active_strategy()


def active_candidate() -> str:
    """Display name of the running adaptive candidate: the strategy,
    suffixed with "/<codec>" when a wire codec is active (candidates are
    (strategy, codec) pairs — an interference vote may have toggled
    compression rather than the graphs); "SET_TREE" under a set_tree
    override."""
    return get_default_peer().current_session().active_candidate_name()


def calc_stats() -> dict:
    """Per-strategy throughput stats (parity: calc_stats/log_stats ops)."""
    return get_default_peer().current_session().calc_stats()


def get_peer_latencies(samples: int = 3) -> np.ndarray:
    """RTT seconds to every peer (self = 0); parity: GetPeerLatencies op."""
    from kungfu_tpu.monitor.latency import probe_peer_latencies

    p = get_default_peer()
    sess = p.current_session()
    return probe_peer_latencies(p.client, list(sess.peers), sess.rank, samples)


def minimum_spanning_tree(weights) -> list:
    """Father array of the MST of a dense cost matrix (parity:
    MinimumSpanningTree op backed by the native Prim kernel)."""
    from kungfu_tpu.plan.mst import minimum_spanning_tree as _mst

    return _mst(weights)


_latency_probe_seq: dict = {}  # cluster version -> probes this epoch


def optimized_tree(samples: int = 3) -> list:
    """Probe latencies, allgather rows into the full matrix, and return the
    MST father array — identical on every peer (deterministic MST over the
    consensus matrix), ready for set_tree."""
    from kungfu_tpu.monitor.latency import latency_matrix_from_rows

    peer = get_default_peer()
    sess = peer.current_session()
    n = sess.size
    row = get_peer_latencies(samples)
    recv = np.zeros(n * n, np.float64)
    # KF700: back-to-back probes must not share a rendezvous name. The
    # counter is PER CLUSTER VERSION, not process-lifetime: a joiner's
    # process starts at 0 while survivors have probed for epochs — only
    # within one epoch do peers call in identical program order, so only
    # the (version, calls-this-version) pair agrees cluster-wide
    v = peer.cluster_version
    seq = _latency_probe_seq.get(v, 0)
    _latency_probe_seq[v] = seq + 1
    w = Workspace(send=row, recv=recv, op=ReduceOp.SUM,
                  name=f"kungfu::latency:v{v}:{seq}")
    sess.all_gather(w)
    matrix = latency_matrix_from_rows(list(recv.reshape(n, n)))
    return minimum_spanning_tree(matrix)


def set_tree(fathers) -> None:
    """Install a collective tree for the current epoch (parity: SetTree
    op); a resize reverts to the configured strategy — re-probe with
    optimized_tree() after membership changes."""
    get_default_peer().set_tree(fathers)


def get_neighbour(step: int) -> int:
    """Deterministic partner schedule: at step t, pair with the peer whose
    rank differs in bit position (t mod log2-ceiling) — a hypercube-style
    schedule giving each peer a distinct partner per step (capability
    parity: GetNeighbour op for PairAveraging peer selection). On
    non-power-of-two clusters an out-of-range hypercube partner falls back
    to the round-robin schedule, so the result is always a VALID peer and
    never self (the reference's GetNeighbour has the same guarantee)."""
    sess = get_default_peer().current_session()
    n, r = sess.size, sess.rank
    if n == 1:
        return 0
    bits = max(1, (n - 1).bit_length())
    partner = r ^ (1 << (step % bits))
    if partner < n:
        return partner
    # fallback: (r+1+k) % n with k <= n-2 can never wrap onto r
    return (r + 1 + step % (n - 1)) % n


def round_robin_peer(step: int) -> int:
    """Round-robin over the other peers (parity: RoundRobin op)."""
    sess = get_default_peer().current_session()
    n, r = sess.size, sess.rank
    if n == 1:
        return 0
    return (r + 1 + step % (n - 1)) % n


def egress_rates() -> "np.ndarray":
    """Per-peer egress rates (bytes/sec), rank-aligned (parity:
    EgressRates op, ops/cpu/monitoring.cpp:5-22 + sess.GetEgressRates).
    All zeros unless monitoring is on (KF_CONFIG_ENABLE_MONITORING
    truthy or KF_TELEMETRY=metrics)."""
    from kungfu_tpu.monitor.net import get_monitor

    sess = get_default_peer().current_session()
    return np.asarray(get_monitor().egress_rates(list(sess.peers)), np.float64)


_queue_ids: dict = {}
_queue_lock = threading.Lock()


def new_queue(src: int, dst: int) -> int:
    """Allocate the next queue id for the (src, dst) peer pair.

    Parity: NewQueue (ops/cpu/queue.cpp:7-44 + libkungfu-comm/queue.go):
    both endpoints call new_queue in the same program order, so each side's
    local counter yields matching ids without any wire traffic. Counters
    are scoped to the cluster epoch — after an elastic resize the rank
    space changes, so every peer restarts the pair counters from 0 (stale
    cross-epoch messages are already fenced by the transport token).
    """
    version = get_default_peer().cluster_version
    with _queue_lock:
        for k in [k for k in _queue_ids if k[0] != version]:
            del _queue_ids[k]  # only one epoch is ever live
        qid = _queue_ids.get((version, src, dst), 0)
        _queue_ids[(version, src, dst)] = qid + 1
        return qid


def queue_put(dst: int, qid: int, data) -> None:
    """Append to queue `qid` toward peer `dst` (parity: QueuePut,
    queue.cpp:47-83). `data` is bytes or a numpy array (sent raw;
    per-connection FIFO order is the queue order). Wire names carry the
    cluster version: a message left undrained in a mailbox across an
    elastic resize can never be popped by the next epoch's queue 0."""
    p = get_default_peer()
    sess = p.current_session()
    payload = data.tobytes() if isinstance(data, np.ndarray) else bytes(data)
    p.client.send(
        sess.peers[dst],
        f"kungfu::queue:v{p.cluster_version}:{sess.rank}:{dst}:{qid}",
        payload,
        _ConnType.QUEUE,
    )


def queue_get(src: int, qid: int, timeout: float = 30.0) -> bytes:
    """Blocking pop from queue `qid` fed by peer `src` (parity: QueueGet)."""
    p = get_default_peer()
    sess = p.current_session()
    return p.queue.get(
        sess.peers[src],
        f"kungfu::queue:v{p.cluster_version}:{src}:{sess.rank}:{qid}",
        timeout,
    )


def save(name: str, data: bytes, version: Optional[int] = None) -> None:
    """Publish a blob to this peer's store (parity: SaveVariable). With a
    version, the blob is an immutable entry in the versioned store (GC
    window 3) — the consistency contract PairAveraging readers rely on."""
    p = get_default_peer()
    if version is None:
        p.p2p.save(name, data)
    else:
        p.p2p.save_version(version, name, data)


def request(
    rank: int, name: str, version: "Optional[int | str]" = None
) -> Optional[bytes]:
    """Fetch a blob from peer `rank`'s store (parity: RequestVariable).
    version: None = flat store; an int or "latest" = versioned store."""
    p = get_default_peer()
    sess = p.current_session()
    return p.p2p.request(sess.peers[rank], name, version=version)
