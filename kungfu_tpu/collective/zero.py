"""ZeRO-1 sharded weight update on the ring (ISSUE 11 tentpole).

Replaces allreduce-then-replicated-update with the sharded dataflow of
"Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training" (PAPERS.md, arXiv:2004.13336), mapped onto the machinery PRs
4–8 built:

1. **reduce-scatter** (the RS half of the segmented ring walk,
   ``HostSession.reduce_scatter``) leaves each rank holding the fully
   reduced 1/k gradient segment it already owns per
   ``plan.topology.owned_segment_bounds`` — (k-1)/k·N bytes per peer,
   f32-exact;
2. the rank runs the **optimizer update on only that shard** and holds
   optimizer state (momentum) plus the f32 **master weights** for only
   that shard — state and update FLOPs drop k-fold;
3. an **all-gather of updated weights**
   (``HostSession.all_gather_shards``, bf16 on the wire where the codec
   wins — EQuARX's motivation, arXiv:2506.17615) broadcasts the result:
   (k-1)/k·N raw, (k-1)/k·N/2 compressed.

Total per step: (k-1)/k·N + (k-1)/k·N/2 wire bytes with bf16 weights vs
2·(k-1)/k·N for the replicated allreduce path.

**Master weights.** Each rank keeps an f32 master copy of its OWNED
shard; the update always applies to the master and the (possibly
bf16-quantized) all-gather result is only the cluster-identical forward
mirror. Without this, a compressed weight all-gather would trap weights
on the bf16 grid and silently drop updates smaller than one ULP; with
it, the quantization error per step is bounded by one wire step of the
weight and does not accumulate. With the codec off, mirror shard ==
master bit for bit.

**Bit-identity contract** (tests/test_zero.py): for plain SGD with the
codec off, the sharded step is bit-identical to the replicated path —
the RS half produces exactly the partial sums the full segmented
allreduce produces, the update applies the same elementwise float ops,
and the AG relays exact f32 segments.

**Scheduler integration.** With ``KF_CONFIG_ASYNC`` on, gradients are
submitted per tensor as they become ready and this object acts as the
scheduler's *sharded-unit handler*: the scheduler drives
``pack → reduce_and_update → gather → scatter`` per bucket across its
pipeline stages, so bucket 0's weight all-gather walks while bucket 1's
shard is still updating, and the tail all-gathers overlap the NEXT
step's forward (``flush()`` returns once every shard updated;
``wait_params()`` — `CollectiveScheduler.wait_gather` — blocks only for
gathers still in flight, call it before the next forward consumes the
params).

**Elastic resize.** Shard ownership is a function of k, so optimizer
state must re-shard when the cluster resizes: call
:meth:`ShardedUpdateSession.export_state` BEFORE the resize (a one-shot
exact state all-gather — every peer leaves with the identical full
blob), then rebuild the session on the new epoch with
``restore_state=blob``; the in-flight scheduler work drains through the
existing ``Peer._update_to`` → ``HostSession.close()`` path. Joining
peers receive the blob via the usual elastic state sync
(``broadcast_bytes``).
"""

from __future__ import annotations

import threading
import time
import weakref
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from kungfu_tpu.base.ops import ReduceOp
from kungfu_tpu.base.serialize import pack_leaves, unpack_leaves
from kungfu_tpu.base.workspace import Workspace
from kungfu_tpu.plan import topology as topo
from kungfu_tpu.telemetry import config as tconfig
from kungfu_tpu.telemetry import metrics as tmetrics
from kungfu_tpu.telemetry import tracing as trace


def bucket_layout(sizes: Sequence[int], cap_bytes: int,
                  itemsize: int = 4) -> List[List[int]]:
    """Greedy order-preserving packing of param indices into buckets of
    <= `cap_bytes` — THE bucket layout of the sharded update, shared by
    ShardedUpdateSession and the torch frontend's replicated state
    import/export so a KF_CONFIG_ZERO flip across a resize can still
    parse the other mode's state blob (the layout is a pure function of
    the param sizes and the cluster-agreed cap)."""
    out: List[List[int]] = []
    cur: List[int] = []
    cur_bytes = 0
    for i, n in enumerate(sizes):
        nbytes = int(n) * itemsize
        if cur and cur_bytes + nbytes > cap_bytes:
            out.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes
    if cur:
        out.append(cur)
    return out


class ShardedSGD:
    """SGD (optional momentum) over a contiguous f32 shard. The same
    elementwise formula as the replicated reference path — ``g *= 1/k;
    buf = momentum·buf + g; p -= lr·buf`` — so sharded and replicated
    updates are bit-identical where the inputs are (tests assert this).
    State (the momentum buffer) exists for the SHARD only: the k-fold
    state cut of ZeRO-1."""

    def __init__(self, lr: float, momentum: float = 0.0):
        self.lr = float(lr)
        self.momentum = float(momentum)

    def state_names(self) -> Tuple[str, ...]:
        """Deterministic state-leaf order (export/restore layout)."""
        return ("momentum",) if self.momentum else ()

    def init(self, n: int) -> Dict[str, np.ndarray]:
        return {name: np.zeros(n, np.float32) for name in self.state_names()}

    def apply(
        self,
        params: np.ndarray,
        grads: np.ndarray,
        state: Dict[str, np.ndarray],
        scale: float,
    ) -> None:
        """In-place update of the param shard; `grads` is staging and is
        consumed (mutated). `scale` is the gradient-averaging factor."""
        np.multiply(grads, np.float32(scale), out=grads)
        if self.momentum:
            buf = state["momentum"]
            np.multiply(buf, np.float32(self.momentum), out=buf)
            np.add(buf, grads, out=buf)
            grads = buf
        # temp of shard size; the rounding (f32 multiply then f32
        # subtract) matches the replicated reference formula exactly
        np.subtract(params, np.float32(self.lr) * grads, out=params)


class _ZeroItem:
    """One in-flight sharded bucket as it moves through the scheduler
    stages (or the synchronous step loop): the walk-naming identity plus
    the round's POOLED gradient staging buffer. Gradients stage in a
    pooled buffer — not a persistent one — because the launcher packs
    round r+1 while the walker may still be reduce-scattering round r's
    buffer for the same bucket (the queue-depth overlap the scheduler
    exists to create); pooled buffers give each round its own, exactly
    like the fused allreduce pipeline. Returned to the pool after the
    shard update consumes it; dropped to GC on abort (the pool's
    documented policy for buffers a worker may still touch)."""

    __slots__ = ("zindex", "rnd", "tag", "gbuf", "garr")

    def __init__(self, zindex: int, rnd: int, tag: str, gbuf, garr):
        self.zindex = zindex
        self.rnd = rnd
        self.tag = tag  # "r" scheduler rounds / "s" sync rounds
        self.gbuf = gbuf
        self.garr = garr


class _Bucket:
    """One fused sharded-update bucket: contiguous members in param
    order, a persistent full-size weight mirror W (the all-gather
    buffer, cluster-identical after every step), grad staging G, and the
    SHARD-ONLY master weights + optimizer state."""

    __slots__ = (
        "index", "names", "params", "sizes", "offsets", "total",
        "W", "ob", "oe", "master", "state", "settled", "wres",
    )

    def __init__(self, index: int, names, params, opt: ShardedSGD,
                 bounds: Tuple[int, int]):
        self.index = index
        self.names = list(names)
        self.params = list(params)
        self.sizes = [p.size for p in self.params]
        self.offsets = list(np.cumsum([0] + self.sizes[:-1]))
        self.total = int(sum(self.sizes))
        self.W = np.empty(self.total, np.float32)
        off = 0
        for p in self.params:
            self.W[off:off + p.size] = p
            off += p.size
        # round-ordering gate for the weight mirror: round r's gather +
        # scatter read W while round r+1's update would write it — the
        # update waits for `settled` (set by scatter, cleared after each
        # update) so a slow all-gather can never interleave with the
        # next round's shard write on the same bucket
        self.settled = threading.Event()
        self.settled.set()
        # the owned-shard bounds under the session's CURRENT ring plan
        # (HostSession.owned_bounds — the single layout source); a
        # measured re-plan re-slices them through reshard_bounds
        self.ob, self.oe = bounds
        # f32 master of the owned shard: the update's source of truth.
        # The mirror W may be bf16-quantized by the weight all-gather;
        # the master integrates sub-ULP updates the mirror would lose.
        self.master = self.W[self.ob:self.oe].copy()
        self.state = opt.init(self.oe - self.ob)
        # error-feedback residual of the quantized weight all-gather
        # (ISSUE 20): the masters hold the exact weights, so the mirror's
        # per-step quantization error telescopes instead of compounding.
        # Per-shard (the gather names are round-stamped, so the session's
        # name-keyed store would never re-hit); reset to zero on every
        # re-shard — post_replan restores exact masters, so a zero
        # residual is the deterministic restart on every peer.
        self.wres = np.zeros(self.oe - self.ob, np.float32)

    def state_bytes(self) -> int:
        n = self.master.nbytes
        for arr in self.state.values():
            n += arr.nbytes
        return n

    def reshard_bounds(self, opt: ShardedSGD, bounds: Tuple[int, int]) -> None:
        """Re-slice this bucket's shard to new owned bounds (a measured
        re-plan moved the segment layout). The caller restores master/
        state contents from an exported full-state blob immediately
        after — the freshly sized arrays here are pure allocation."""
        self.ob, self.oe = bounds
        self.master = np.empty(self.oe - self.ob, np.float32)
        self.state = opt.init(self.oe - self.ob)
        self.wres = np.zeros(self.oe - self.ob, np.float32)


class ShardedUpdateSession:
    """Owner of the shard ↔ full-param mapping for one model's ZeRO-1
    update (module docstring has the dataflow). `params` are 1-D
    contiguous f32 numpy views of the model weights — scatter writes the
    gathered results back into them in place (the torch frontend passes
    zero-copy tensor views). Buckets follow the param order under the
    cluster-agreed ``KF_CONFIG_GROUP_BUCKET_BYTES`` cap, so every peer
    derives the identical layout without negotiation.

    Drive it one of two ways:

    * synchronous (``KF_CONFIG_ASYNC`` off): :meth:`step` per training
      step — pack, reduce-scatter, shard update, weight all-gather,
      scatter, inline;
    * through the async scheduler: :meth:`submit_grad` per tensor as
      gradients become ready (this object is the scheduler's sharded
      handler), :meth:`flush` at step end (returns once every shard
      updated — weight all-gathers keep walking), :meth:`wait_params`
      before the next forward consumes the params.
    """

    def __init__(
        self,
        params: Sequence[np.ndarray],
        opt: ShardedSGD,
        name: str = "zero",
        session=None,
        restore_state: Optional[bytes] = None,
    ):
        if session is None:
            from kungfu_tpu.peer import get_default_peer

            session = get_default_peer().current_session()
        self.sess = session
        self.opt = opt
        self.name = name
        self._prefix = f"kungfu::zero:{name}"
        k = session.size
        self._scale = 1.0 / k
        views: List[np.ndarray] = []
        for i, p in enumerate(params):
            a = np.asarray(p)
            if a.dtype != np.float32:
                raise ValueError(
                    f"sharded update params must be float32, got "
                    f"{a.dtype} at index {i}"
                )
            if not a.flags["C_CONTIGUOUS"]:
                raise ValueError(
                    f"sharded update params must be C-contiguous "
                    f"(param {i}) — scatter writes them back in place"
                )
            views.append(a.reshape(-1))
        if not views:
            raise ValueError("sharded update needs at least one param")
        self._views = views
        self._member_names = [f"{self._prefix}:{i}" for i in range(len(views))]
        self._buckets: List[_Bucket] = []
        self._member_bucket: Dict[str, Tuple[int, int]] = {}
        for idxs in bucket_layout([v.size for v in views],
                                  session.GROUP_BUCKET_BYTES):
            self._add_bucket([self._member_names[i] for i in idxs],
                             [views[i] for i in idxs])
        # measured-topology re-planning (ISSUE 14): a plan adoption
        # moves the owned-segment layout, so this session must re-shard
        # its masters/state exactly — pre_replan exports the full state
        # under the OLD layout, post_replan re-slices under the new
        if hasattr(session, "add_replan_listener"):
            session.add_replan_listener(self)
        # quantized-codec residual lifecycle (ISSUE 20): any session
        # flush (wire-mode flip, precision vote, re-plan) must reach the
        # per-shard weight residuals too — stale residuals measure the
        # old codec/layout and would corrupt the next gather
        if hasattr(session, "add_ef_flush_listener"):
            session.add_ef_flush_listener(self._reset_weight_residuals)
        self._sync_round = 0
        self._export_seq = 0
        self._lock = threading.Lock()
        if restore_state is not None:
            self._restore(restore_state)
        if tconfig.metrics_enabled():
            self._state_gauge = tmetrics.gauge(
                "kungfu_sharded_update_state_bytes",
                "Optimizer-held bytes of the ZeRO-1 sharded update on "
                "this peer (shard master weights + shard optimizer "
                "state) — ~1/k of the replicated path's full-size state",
            )
            self._update_ctr = tmetrics.counter(
                "kungfu_sharded_update_seconds_total",
                "Seconds spent in the shard-local optimizer update "
                "(the k-fold-reduced update FLOPs of ZeRO-1)",
            )
            self._state_gauge.set(self.state_bytes())
        else:
            self._state_gauge = None
            self._update_ctr = None
        # memory plane (ISSUE 17): shard masters + optimizer state +
        # the full-size reduce mirrors are long-lived buffer owners.
        # Weakref so the registry never pins a session across an
        # elastic resize — the entry self-drops when the session dies.
        try:
            from kungfu_tpu.telemetry import memory as _tmem

            def _acct(ref=weakref.ref(self)) -> Optional[int]:
                zs = ref()
                if zs is None:
                    return None
                return zs.state_bytes() + sum(
                    b.W.nbytes for b in zs._buckets
                )

            _tmem.register_accountant(
                f"zero:{name}", "zero_state", _acct
            )
        # kfcheck: disable=KF400 — byte accounting is best-effort;
        # it must never kill the update path
        except Exception:  # noqa: BLE001
            pass

    def _add_bucket(self, names, params) -> None:
        total = int(sum(p.size for p in params))
        b = _Bucket(len(self._buckets), names, params, self.opt,
                    self._owned_bounds(total))
        for j, n in enumerate(names):
            self._member_bucket[n] = (b.index, j)
        self._buckets.append(b)

    def _owned_bounds(self, total: int) -> Tuple[int, int]:
        """The session's plan-aware owned bounds (falls back to the
        naive layout for bare/mock sessions without the accessor)."""
        if hasattr(self.sess, "owned_bounds"):
            return self.sess.owned_bounds(total)
        return topo.owned_segment_bounds(total, self.sess.size, self.sess.rank)

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------

    def state_bytes(self) -> int:
        """Bytes of optimizer-held state on THIS peer (shard masters +
        shard optimizer state) — the number the
        `kungfu_sharded_update_state_bytes` gauge exports. The
        replicated equivalent is full-size state on every peer."""
        return sum(b.state_bytes() for b in self._buckets)

    def total_elems(self) -> int:
        return sum(b.total for b in self._buckets)

    def bucket_count(self) -> int:
        return len(self._buckets)

    def _check_epoch(self) -> None:
        if getattr(self.sess, "_epoch_closed", False):
            raise RuntimeError(
                "sharded update session's epoch ended (elastic resize): "
                "export_state() BEFORE the resize and rebuild "
                "ShardedUpdateSession(restore_state=...) on the new "
                "session"
            )

    def _grad_views(self, grads: Sequence[np.ndarray]) -> List[np.ndarray]:
        if len(grads) != len(self._views):
            raise ValueError(
                f"expected {len(self._views)} gradients, got {len(grads)}"
            )
        out = []
        for i, (g, p) in enumerate(zip(grads, self._views)):
            a = np.asarray(g)
            if a.dtype != np.float32 or a.size != p.size:
                raise ValueError(
                    f"grad {i} mismatch: {a.dtype}/{a.size} vs param "
                    f"float32/{p.size}"
                )
            out.append(np.ascontiguousarray(a).reshape(-1))
        return out

    # ------------------------------------------------------------------
    # synchronous step path (KF_CONFIG_ASYNC off)
    # ------------------------------------------------------------------

    def step(self, grads: Sequence[np.ndarray]) -> None:
        """One synchronous ZeRO-1 step over the full gradient set (param
        order): per bucket pack → reduce-scatter → shard update → weight
        all-gather → scatter back into the params. Wire names carry a
        process-local round counter (peers call in identical program
        order, so it agrees) — a fast peer's next step can never be
        consumed by a slower peer still in this one."""
        self._check_epoch()
        views = self._grad_views(grads)
        with self._lock:
            rnd = self._sync_round
            self._sync_round += 1
        for b in self._buckets:
            item = self._pack_views(b, views, rnd, "s")
            self.reduce_and_update(item)
            self.gather(item)
            self.scatter(item)

    def _pack_into(self, b: _Bucket, rnd: int, tag: str,
                   source) -> _ZeroItem:
        """Shared staging pack of one bucket's gradients into a pooled
        buffer (one implementation behind BOTH the sync step and the
        scheduler's launcher stage — the sync-vs-async bit-identity
        contract depends on identical staging). `source(name, j)`
        returns member j's gradient array."""
        from kungfu_tpu.utils.pool import get_buffer_pool

        gbuf = get_buffer_pool().get(b.total * 4)
        garr = np.frombuffer(gbuf, np.float32, b.total)
        for j, n in enumerate(b.names):
            off = b.offsets[j]
            garr[off:off + b.sizes[j]] = source(n, j)
        return _ZeroItem(b.index, rnd, tag, gbuf, garr)

    def _pack_views(self, b: _Bucket, views, rnd: int, tag: str) -> _ZeroItem:
        return self._pack_into(
            b, rnd, tag,
            lambda n, j: views[int(n.rsplit(":", 1)[1])],
        )

    # ------------------------------------------------------------------
    # async path (the scheduler drives the handler protocol below)
    # ------------------------------------------------------------------

    def submit_grad(self, i: int, grad: np.ndarray) -> None:
        """Hand gradient `i` (param order) to the async scheduler as it
        becomes ready. The workspace's recv is NOT written — the
        gradient is consumed by the shard update; the deliverable is the
        updated params, scattered back by the scheduler's unpack stage.
        `priority=i` pins the negotiated registration order to param
        order on every peer regardless of arrival order."""
        self._check_epoch()
        g = np.ascontiguousarray(np.asarray(grad)).reshape(-1)
        if i < 0 or i >= len(self._views):
            raise IndexError(f"param index {i} outside 0..{len(self._views) - 1}")
        if g.dtype != np.float32 or g.size != self._views[i].size:
            raise ValueError(
                f"grad {i} mismatch: {g.dtype}/{g.size} vs param "
                f"float32/{self._views[i].size}"
            )
        self.sess.scheduler().submit(
            Workspace(send=g, recv=g, op=ReduceOp.SUM,
                      name=self._member_names[i]),
            priority=i,
            handler=self,
        )

    def flush(self, timeout: Optional[float] = None) -> None:
        """End the gradient round: returns once every bucket's shard has
        been reduced and updated (gradient buffers are consumable
        again). Weight all-gathers may still be walking — they overlap
        the caller's next-step compute; see :meth:`wait_params`."""
        self.sess.scheduler().flush(timeout=timeout)

    def wait_params(self, timeout: Optional[float] = None) -> None:
        """Block until every in-flight weight all-gather has landed and
        been scattered into the params. Call before the next forward
        consumes the params (the start-of-step barrier of the
        overlapped loop)."""
        self.sess.scheduler().wait_gather(timeout=timeout)

    # ------------------------------------------------------------------
    # scheduler sharded-handler protocol
    # ------------------------------------------------------------------

    def plan_units(self, zero_keys) -> List[list]:
        """Map the scheduler's registered sharded keys onto this
        session's bucket layout: one launch unit per bucket, members in
        bucket (== param) order. Pure function of the consensus-checked
        registry and this object's deterministic layout, so every peer
        derives the identical plan. A registered set that doesn't match
        the declared params is a configuration error — fail fast."""
        by_name = {k[0]: k for k in zero_keys}
        if len(by_name) != len(zero_keys):
            raise ValueError("duplicate sharded tensor names registered")
        expected = set(self._member_names)
        got = set(by_name)
        if expected != got:
            missing = sorted(expected - got)[:4]
            rogue = sorted(got - expected)[:4]
            raise ValueError(
                "registered sharded tensors do not match the "
                f"ShardedUpdateSession params (missing {missing}, "
                f"unexpected {rogue}) — submit every param's gradient "
                "exactly once per round through submit_grad"
            )
        for k in zero_keys:
            bi, j = self._member_bucket[k[0]]
            if k[1] != self._buckets[bi].sizes[j]:
                raise ValueError(
                    f"sharded tensor {k[0]!r} registered with size "
                    f"{k[1]} but the param has {self._buckets[bi].sizes[j]}"
                )
        return [[by_name[n] for n in b.names] for b in self._buckets]

    def pack(self, zindex: int, members: List[Workspace], rnd: int) -> _ZeroItem:
        """Launcher stage: pack the round's submitted gradient
        workspaces (unit-key order == bucket member order) into a POOLED
        staging buffer — the walker may still be reduce-scattering the
        previous round's buffer for this bucket."""
        b = self._buckets[zindex]
        by_name = {}
        for w in members:
            bi, _ = self._member_bucket[w.name]
            if bi != zindex:
                raise ValueError(
                    f"tensor {w.name!r} landed in bucket {zindex}, "
                    f"belongs to {bi}"
                )
            by_name[w.name] = w.send
        with trace.span("zero.pack", bucket=zindex):
            return self._pack_into(b, rnd, "r", lambda n, j: by_name[n])

    def reduce_and_update(self, item: _ZeroItem,
                          cancel: Optional[threading.Event] = None) -> _ZeroItem:
        """Walker stage: reduce-scatter the bucket's gradients (raw f32,
        (k-1)/k·N bytes), then run the optimizer on the owned shard —
        update FLOPs and state touched are 1/k of the replicated path.
        The update applies to the f32 master; the mirror shard is
        refreshed from it for the all-gather. Waits for the PREVIOUS
        round's gather+scatter of this bucket to land before touching
        the mirror (the `settled` gate)."""
        from kungfu_tpu.utils.pool import get_buffer_pool

        b = self._buckets[item.zindex]
        ws = Workspace(
            send=item.garr, recv=item.garr, op=ReduceOp.SUM,
            name=f"{self._prefix}:zrs:{item.tag}{item.rnd}:b{item.zindex}",
        )
        ob, oe = self.sess.reduce_scatter(ws, cancel=cancel)
        if (ob, oe) != (b.ob, b.oe):
            raise RuntimeError(
                f"shard layout drift: walk owns [{ob}:{oe}), optimizer "
                f"holds [{b.ob}:{b.oe}) — owned_segment_bounds must be "
                "the single layout source"
            )
        # abort-aware settled wait: a hard-cancel (scheduler close past
        # its drain budget) must unblock this thread within one poll
        # interval, not leave it parked for the full walk timeout — the
        # close() join budget is seconds, and an old-epoch thread must
        # not outlive the epoch (the KF303 drain contract)
        deadline = time.monotonic() + self.sess.timeout
        while not b.settled.wait(0.2):
            if cancel is not None and cancel.is_set():
                raise TimeoutError(
                    f"sharded update cancelled: bucket {b.index}"
                )
            if time.monotonic() >= deadline:
                raise TimeoutError(
                    f"bucket {b.index}'s previous weight all-gather "
                    "never landed — cannot start the next shard update"
                )
        if cancel is not None and cancel.is_set():
            raise TimeoutError(
                f"sharded update cancelled: bucket {b.index}"
            )
        t0 = time.perf_counter()
        with trace.span("zero.update", bucket=item.zindex,
                        elems=int(b.oe - b.ob)):
            self.opt.apply(b.master, item.garr[b.ob:b.oe], b.state,
                           self._scale)
            np.copyto(b.W[b.ob:b.oe], b.master)
        b.settled.clear()
        if self._update_ctr is not None:
            self._update_ctr.inc(time.perf_counter() - t0)
        # the gradients are consumed: return the staging buffer
        get_buffer_pool().put(item.gbuf)
        item.gbuf = item.garr = None
        return item

    def gather(self, item: _ZeroItem,
               cancel: Optional[threading.Event] = None) -> _ZeroItem:
        """Gather stage: all-gather the bucket's updated weights around
        the ring — bf16 on the wire when the codec wins ((k-1)/k·N/2
        bytes), f32 otherwise. After it W is complete and identical on
        every peer, owner included."""
        b = self._buckets[item.zindex]
        self.sess.all_gather_shards(
            b.W,
            f"{self._prefix}:zag:{item.tag}{item.rnd}:b{item.zindex}",
            cancel=cancel,
            ef=b.wres,
        )
        return item

    def scatter(self, item: _ZeroItem,
                cancel: Optional[threading.Event] = None) -> None:
        """Unpack stage: scatter the gathered weights back into the
        caller's param views (in place — torch tensors see the update
        without a copy), then release the bucket's `settled` gate so the
        next round's update may write the mirror. A set `cancel`
        (scheduler hard-abort) skips the write — the epoch is ending and
        the params are restored by the elastic state sync, so a late
        scatter must not race the caller (KF703); the `settled` gate
        stays cleared, matching the driver's skip path."""
        if cancel is not None and cancel.is_set():
            return
        b = self._buckets[item.zindex]
        with trace.span("zero.scatter", bucket=item.zindex):
            for j, p in enumerate(b.params):
                off = b.offsets[j]
                np.copyto(p, b.W[off:off + b.sizes[j]])
        b.settled.set()

    # ------------------------------------------------------------------
    # elastic re-shard (resize support)
    # ------------------------------------------------------------------

    def export_state(self) -> bytes:
        """One-shot EXACT state all-gather: reconstruct the full master
        weights and full optimizer state from every peer's shards and
        serialize them. Every peer leaves with the identical blob — run
        it BEFORE a resize (on the old session), then rebuild with
        ``restore_state=blob`` on the new epoch; shard ownership is a
        function of k, so the new session re-slices its own shard.
        Never wire-compressed: re-sharded state must be bit-identical
        to what a fresh replicated run would hold. Call at a step
        boundary — after ``flush()`` + ``wait_params()`` — so no
        scheduler stage is concurrently touching the masters/state."""
        self._check_epoch()
        with self._lock:
            seq = self._export_seq
            self._export_seq += 1
        leaves: List[np.ndarray] = []
        for b in self._buckets:
            for li, name in enumerate(("master",) + self.opt.state_names()):
                full = np.zeros(b.total, np.float32)
                shard = b.master if name == "master" else b.state[name]
                full[b.ob:b.oe] = shard
                self.sess.all_gather_shards(
                    full,
                    f"{self._prefix}:state:{seq}:b{b.index}:{li}",
                    allow_wire=False,
                )
                leaves.append(full)
        return pack_leaves(leaves)

    def _reset_weight_residuals(self, reason: str) -> None:
        """Session ef-flush hook (ISSUE 20): zero every bucket's weight
        all-gather residual. Deterministic on every peer — the masters
        stay exact, so dropping the carried remainder costs at most one
        quantization step on the NEXT gather, never correctness."""
        for b in self._buckets:
            b.wres[:] = 0.0

    # ------------------------------------------------------------------
    # measured-topology re-plan hooks (ISSUE 14)
    # ------------------------------------------------------------------

    def pre_replan(self) -> bytes:
        """Replan-listener hook, called by ``HostSession.adopt_replan``
        BEFORE the plan swap (in lockstep on every peer, at a step
        boundary): quiesce in-flight weight all-gathers, then export the
        full exact state under the OLD shard layout. The returned blob
        feeds :meth:`post_replan`."""
        if self.sess._scheduler is not None:
            self.wait_params()
        return self.export_state()

    def post_replan(self, blob: bytes) -> None:
        """Replan-listener hook, called AFTER the plan swap: re-slice
        every bucket's shard to the session's NEW owned bounds and
        restore masters/state from the pre-swap export — bit-exact
        re-sharding, the same contract as an elastic resize
        (``export_state``/``restore_state``), just without changing k."""
        for b in self._buckets:
            b.reshard_bounds(self.opt, self._owned_bounds(b.total))
        self._restore(blob)
        if self._state_gauge is not None:
            self._state_gauge.set(self.state_bytes())

    def _restore(self, blob: bytes) -> None:
        per_bucket = 1 + len(self.opt.state_names())
        leaves = unpack_leaves(blob, per_bucket * len(self._buckets))
        it = iter(leaves)
        for b in self._buckets:
            for name in ("master",) + self.opt.state_names():
                full = np.asarray(next(it), np.float32).reshape(-1)
                if full.size != b.total:
                    raise ValueError(
                        f"restore_state bucket {b.index} leaf {name!r} "
                        f"has {full.size} elements, expected {b.total} — "
                        "param set or bucket knobs changed across the "
                        "resize"
                    )
                if name == "master":
                    # the exported masters ARE the true f32 weights:
                    # refresh the mirror and the caller's params from
                    # them (survivors' mirrors may hold bf16-rounded
                    # values; every peer restores the same blob, so the
                    # cluster stays consistent)
                    np.copyto(b.W, full)
                    b.master = full[b.ob:b.oe].copy()
                    for j, p in enumerate(b.params):
                        off = b.offsets[j]
                        # kfcheck: disable=KF703 — quiesced restore: runs
                        # at construction or inside a lockstep re-plan
                        # adoption (post_replan), both with no walk in
                        # flight, so no abort scope exists; the params
                        # are ours to (re)initialize before the next step
                        np.copyto(p, b.W[off:off + b.sizes[j]])
                else:
                    np.copyto(b.state[name], full[b.ob:b.oe])
