"""Group-allreduce fusion pipeline, factored out of host_session.py
(ISSUE 10 prerequisite refactor).

Owns the bucket side of `group_all_reduce`: deterministic same-
(dtype, op) bucketing (`_make_buckets`), the pack / walk / unpack
stages, and the 3-stage software pipeline that overlaps them. The
stages are exactly what the async scheduler (scheduler.py) drives
per-bucket as gradients become ready — one implementation, two
drivers (step-end batch here, readiness-ordered there).

The stage queues are :class:`~kungfu_tpu.utils.handoff.HandoffQueue`
(ISSUE 10 satellite): bounded, abort-aware, shared with the scheduler.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from kungfu_tpu import knobs
from kungfu_tpu.base.workspace import Workspace
from kungfu_tpu.collective.strategies import effective_cpu_count
from kungfu_tpu.telemetry import tracing as trace
from kungfu_tpu.utils.handoff import HandoffQueue, parallel_run as _par
from kungfu_tpu.utils.pool import get_buffer_pool
from kungfu_tpu.utils.stall import stall_detect


class GroupFusion:
    """Group-collective mixin for HostSession: windowed singles plus
    fused buckets through the pack/walk/unpack pipeline. Relies on
    session state (timeout, codec decision, walk engine) owned by the
    facade's constructor."""

    # concurrent workspaces per batch in group ops: concurrency only pays
    # when cores exist to run the walks (on a 1-core host it just adds
    # context switches), so the default scales with the cgroup-aware
    # core count — os.cpu_count() reports the HOST's cores inside a
    # CPU-quota'd container, the phantom-parallelism trap auto_select
    # already avoids; KF_CONFIG_GROUP_WINDOW overrides
    GROUP_WINDOW = int(
        knobs.get("KF_CONFIG_GROUP_WINDOW")
        or max(1, min(8, effective_cpu_count()))
    )

    # Gradient bucketing: fuse same-(dtype, op) workspaces into ONE
    # contiguous walk. A 160-tensor gradient set otherwise pays the fixed
    # per-walk cost (rendezvous conditions, pool dispatch, ~6 framed
    # messages) 160 times — on a host-plane reduce that overhead rivals
    # the byte-copy time itself. Two extra memcpy passes (pack + unpack)
    # buy a ~160x cut in message count. The reference runs one collective
    # per tensor and leans on cheap goroutines instead; bucketing is the
    # standard DDP/Horovod answer and is strictly better here.
    FUSE_MIN_TENSORS = int(knobs.get("KF_CONFIG_GROUP_FUSE_MIN"))

    # Fused-bucket size cap: fused groups split into buckets that pack /
    # walk / unpack as a 3-stage pipeline, so the cap trades per-walk
    # fixed cost (bigger buckets) against pack/unpack overlap (smaller
    # buckets start their walk sooner and unpack while the next bucket is
    # on the wire). Measured on the 2-core bench box: 8 MiB buckets pay
    # 12 walks' fixed cost for resnet50 and run 2x SLOWER than one big
    # bucket; 64 MiB is within noise of a single bucket while still
    # pipelining multi-hundred-MB sets (bert ~700 MB -> 11 buckets).
    # Part of the fused workspace name, so it MUST be cluster-agreed
    # like CHUNK_BYTES (which also rules out core-count scaling here).
    GROUP_BUCKET_BYTES = int(knobs.get("KF_CONFIG_GROUP_BUCKET_BYTES"))

    def group_all_reduce(self, ws: Sequence[Workspace]) -> None:
        """Allreduce of many workspaces as one windowed group op (parity:
        the reference reduces a whole gradient set per session.run —
        srcs/python/kungfu/tensorflow/v1/benchmarks). Fused buckets run
        through the 3-stage pipeline while the singles windows walk
        concurrently — neither waits for the other to finish."""
        if not ws:
            return
        with self._collected(
            "group_all_reduce", sum(w.recv.nbytes for w in ws)
        ), stall_detect(f"group_all_reduce[{len(ws)}]"):
            singles: List[Workspace] = []
            groups: Dict[tuple, List[Workspace]] = {}
            for w in ws:
                if w.is_empty:
                    continue
                groups.setdefault((w.send.dtype.str, int(w.op)), []).append(w)
            buckets: List[List[Workspace]] = []
            for members in groups.values():
                if len(members) < self.FUSE_MIN_TENSORS:
                    singles.extend(members)
                else:
                    buckets.extend(self._make_buckets(members))
            jobs: List[Callable[[], None]] = []
            # the group deadline scales with the number of walks it
            # covers — the serial predecessor allowed one self.timeout
            # PER fused walk / singles window, and a large healthy group
            # on a slow link must not trip a single flat budget
            windows = -(-len(singles) // self.GROUP_WINDOW)
            group_timeout = self.timeout * max(1, len(buckets) + windows)
            # shared cancel: a group-level timeout must also abort the
            # pipeline stages, or a lingering unpacker would keep writing
            # caller recv buffers after this call already raised (the
            # late-write hazard _par's contract exists to prevent)
            cancel = threading.Event()
            if buckets:
                jobs.append(
                    lambda: self._fused_pipeline(buckets, group_timeout, cancel)
                )
            if singles:
                jobs.append(lambda: self._singles_windows(singles, cancel))
            _par(jobs, group_timeout, cancel)

    def _make_buckets(
        self, members: List[Workspace]
    ) -> List[List[Workspace]]:
        """Greedy, order-preserving packing of same-(dtype, op)
        workspaces into <= GROUP_BUCKET_BYTES buckets. Derived only from
        the caller's tensor order and the byte cap, so every peer computes
        the same layout (the fused name encodes it); an oversized single
        tensor gets a bucket of its own."""
        buckets: List[List[Workspace]] = []
        cur: List[Workspace] = []
        cur_bytes = 0
        for w in members:
            if cur and cur_bytes + w.send.nbytes > self.GROUP_BUCKET_BYTES:
                buckets.append(cur)
                cur, cur_bytes = [], 0
            cur.append(w)
            cur_bytes += w.send.nbytes
        if cur:
            buckets.append(cur)
        return buckets

    def _singles_windows(
        self,
        singles: List[Workspace],
        cancel: Optional[threading.Event] = None,
    ) -> None:
        for i in range(0, len(singles), self.GROUP_WINDOW):
            if cancel is not None and cancel.is_set():
                # the group already raised (timeout, or a pipeline-stage
                # error that set the shared cancel): stop launching
                # windows, but return QUIETLY — raising here would race
                # the real error to _par's errs[0] and misreport a
                # deterministic failure as 'cancelled'
                return
            batch = singles[i : i + self.GROUP_WINDOW]
            _par(
                [lambda w=w: self._allreduce_ws(w, cancel) for w in batch],
                self.timeout,
                cancel,
            )

    def _pack_bucket(self, bi: int, members: List[Workspace],
                     name_prefix: str = ""):
        """Pack one bucket into pooled contiguous buffers. Workspace
        order is the caller's tensor order, identical on every peer, so
        the fused name and layout agree cluster-wide. `name_prefix`
        namespaces the fused rendezvous (the async scheduler stamps its
        round counter here so back-to-back rounds cannot collide).

        When the wire codec will compress this bucket, members are
        packed straight into ONE buffer that doubles as the walk's f32
        accumulator (an inplace workspace): all wire staging already
        happens in pooled 2-byte scratches inside the walk, so the
        second full-size f32 buffer (and its memcpy) of the raw path
        buys nothing. Inplace fused workspaces are valid on every walk
        path, so a mid-flight adaptive codec toggle stays correct."""
        dtype = members[0].send.dtype
        op = members[0].op
        total = sum(w.send.size for w in members)
        nbytes = total * dtype.itemsize
        pool = get_buffer_pool()
        single = (
            self._active_wire_mode() != "off"
            and dtype == np.float32
            and nbytes >= self.WIRE_MIN_BYTES
        )
        send_b = pool.get(nbytes)
        recv_b = None if single else pool.get(nbytes)
        with trace.span("host.fuse.pack"):
            send = np.frombuffer(send_b, dtype, total)
            recv = send if single else np.frombuffer(recv_b, dtype, total)
            off = 0
            for w in members:
                send[off : off + w.send.size] = w.send
                off += w.send.size
        fused = Workspace(
            send=send,
            recv=recv,
            op=op,
            name=f"{members[0].name}::fused:{name_prefix}"
                 f"b{bi}:{len(members)}x{total}",
        )
        return (fused, send_b, recv_b, members)

    def _unpack_bucket(self, item, abort: Optional[threading.Event] = None) -> None:
        fused, send_b, recv_b, members, deferred = item
        if abort is not None and abort.is_set():
            # KF703: the group/scheduler scope aborted while this bucket
            # was in flight — the member recv buffers may already be
            # reused by the caller that raised, so drop the bucket (its
            # pooled staging goes to GC, the pool's policy for buffers a
            # worker may still touch)
            return
        pool = get_buffer_pool()
        try:
            with trace.span("host.fuse.unpack"):
                off = 0
                if deferred is not None:
                    # fused decode+unpack: the compressed walk handed us
                    # its wire buffer instead of decoding into the fused
                    # recv first — one full f32 pass saved per bucket
                    for w in members:
                        deferred.decode_into(w.recv, off, off + w.recv.size)
                        off += w.recv.size
                else:
                    for w in members:
                        np.copyto(w.recv, fused.recv[off : off + w.recv.size])
                        off += w.recv.size
        finally:
            if deferred is not None:
                deferred.close()
            pool.put(send_b)
            if recv_b is not None:
                pool.put(recv_b)

    def _fused_pipeline(
        self,
        buckets: List[List[Workspace]],
        timeout: float,
        cancel: Optional[threading.Event] = None,
    ) -> None:
        """3-stage software pipeline over fused buckets: pack bucket i+1
        and unpack bucket i-1 while bucket i is on the wire. The serial
        predecessor (all packs, then all walks, then all unpacks per
        bucket) left the wire idle during every memcpy phase. Depth-1
        handoff queues bound live pooled buffers at 5 buckets (one per
        stage + one per queue) — x2 buffers x GROUP_BUCKET_BYTES, well
        under the serial path's single whole-group buffer pair for big
        sets. The queues are abort-aware HandoffQueues sharing one abort
        event, so any stage's failure (or a dropped sentinel after one)
        unblocks the other two and the REAL error propagates out of
        _par; aborted in-flight buffers are dropped to GC (the pool's
        documented policy for buffers a worker may still touch)."""
        # the caller's cancel event doubles as the abort flag: _par sets
        # it on timeout, so every stage (unpacker included) stops before
        # touching caller buffers again
        abort = cancel if cancel is not None else threading.Event()
        packed = HandoffQueue(maxsize=1, abort=abort)
        unpackq = HandoffQueue(maxsize=1, abort=abort)

        def packer():
            try:
                for bi, members in enumerate(buckets):
                    if abort.is_set():
                        return
                    if not packed.put(self._pack_bucket(bi, members)):
                        return
            except BaseException:
                abort.set()
                raise
            finally:
                packed.put(None)

        def walker():
            try:
                while True:
                    item = packed.get()
                    if item is None:
                        return
                    if abort.is_set():
                        continue  # drain to the sentinel
                    with trace.span("host.fuse.walk"):
                        # defer the codec's walk-end decode to the
                        # unpacker, which fuses it with the member
                        # scatter (an aborted in-flight wire buffer is
                        # dropped to GC like every other staging buffer)
                        deferred = self._allreduce_ws(
                            item[0], defer_decode=True
                        )
                    if not unpackq.put(item + (deferred,)):
                        return
            except BaseException:
                abort.set()
                raise
            finally:
                unpackq.put(None)

        def unpacker():
            try:
                while True:
                    item = unpackq.get()
                    if item is None:
                        return
                    if abort.is_set():
                        continue  # aborted: must not touch caller buffers
                    self._unpack_bucket(item, abort)
            except BaseException:
                abort.set()
                raise

        _par([packer, walker, unpacker], timeout, abort)
