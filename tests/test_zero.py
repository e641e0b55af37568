"""ZeRO-1 sharded weight update on the ring (ISSUE 11 tentpole).

Covers: the shard layout algebra (owned_segment_bounds as the single
source shared by walk and optimizer, swept over sizes that don't divide
by k), segment-op boundary validation, the first-class reduce-scatter /
all-gather halves at np in {2,3,4} on exact payloads (including the n<k
empty-segment edge), bit-identity of the sharded update vs the
replicated path for plain SGD and momentum SGD (sync and
scheduler-overlapped, shuffled submission), the bf16 weight all-gather's
documented error bound + cross-peer bit-identity, KF_CONFIG_ZERO in the
engine-knob consensus (divergence raises a named error), elastic
re-shard across grow 2->4 and shrink 4->2 session epochs (re-sharded
state bit-identical to a fresh replicated run's shard), mid-flight
weight all-gather drain on close (old handles raise SchedulerClosed),
mixed sharded + allreduce rounds, the optax `zero_sharded` wrapper on
the 8-device mesh, and the torch `ZeroSGDOptimizer`.

Exactness note: like test_segmented/test_scheduler, bit-identity cases
reduce INTEGER-VALUED payloads so SUM is associativity-free; the
sharded path's reduce-scatter runs the identical ring association as
the replicated path's segmented allreduce, so for plain SGD the two are
bit-identical by construction — asserted with exact payloads to keep
the contract crisp.
"""

import threading
import time

import numpy as np
import pytest

from ports import kfrun_ports

from kungfu_tpu.base.ops import (
    ReduceOp,
    copy_segment,
    reduce_segment,
)
from kungfu_tpu.base.strategy import Strategy
from kungfu_tpu.base.workspace import Workspace, even_partition
from kungfu_tpu.collective.host_session import HostSession
from kungfu_tpu.collective.scheduler import SchedulerClosed
from kungfu_tpu.collective.zero import ShardedSGD, ShardedUpdateSession
from kungfu_tpu.peer import Peer
from kungfu_tpu.plan import topology as topo
from kungfu_tpu.plan.peer import PeerID, PeerList
from kungfu_tpu.runner.env import WorkerConfig


# ---------------------------------------------------------------------------
# shard layout algebra (satellite: boundary handling for n % k != 0)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 2, 3, 4, 5])
def test_owned_segment_bounds_property(k):
    """Property sweep over odd sizes 1..4k+3: the per-rank owned shards
    exactly partition [0, n) (no gaps, no overlap), each equals the
    even_partition segment the schedule designates, and the walk's
    per-step segment bounds agree with the optimizer's shard layout
    byte for byte — the single-source-of-truth contract."""
    for n in range(1, 4 * k + 4):
        bounds = even_partition(n, k)
        shards = [topo.owned_segment_bounds(n, k, i) for i in range(k)]
        # partition: sorted shards tile [0, n)
        assert sorted(e - b for b, e in shards) == sorted(
            e - b for b, e in bounds
        )
        covered = sorted(shards)
        pos = 0
        for b, e in covered:
            assert b == pos
            pos = e
        assert pos == n
        if k > 1:
            for i in range(k):
                sched = topo.gen_segmented_schedule(list(range(k)), i)
                assert shards[i] == bounds[sched.owned_segment]


def test_segment_ops_validate_and_agree():
    """reduce_segment/copy_segment must fail fast on a layout mismatch
    (the native kernels take raw pointers and would corrupt silently),
    and must agree with the even_partition shard layout on every odd
    size 1..4k+3."""
    k = 4
    for n in range(1, 4 * k + 4):
        acc = np.arange(n, dtype=np.float32)
        ref = acc.copy()
        for i in range(k):
            b, e = topo.owned_segment_bounds(n, k, i)
            inc = np.full(e - b, 2.0, np.float32)
            reduce_segment(acc, b, e, inc, ReduceOp.SUM)
            ref[b:e] += 2.0
        np.testing.assert_array_equal(acc, ref)
        dst = np.zeros(n, np.float32)
        for i in range(k):
            b, e = topo.owned_segment_bounds(n, k, i)
            copy_segment(dst, b, e, acc[b:e])
        np.testing.assert_array_equal(dst, acc)
    acc = np.zeros(10, np.float32)
    with pytest.raises(ValueError, match="partitioned the payload"):
        reduce_segment(acc, 0, 5, np.zeros(4, np.float32), ReduceOp.SUM)
    with pytest.raises(ValueError, match="outside buffer"):
        reduce_segment(acc, 8, 12, np.zeros(4, np.float32), ReduceOp.SUM)
    with pytest.raises(ValueError, match="partitioned the payload"):
        copy_segment(acc, 2, 4, np.zeros(3, np.float32))


# ---------------------------------------------------------------------------
# live-cluster harness (the test_segmented pattern)
# ---------------------------------------------------------------------------

def make_peer_cluster(n):
    from kungfu_tpu.cmd import _reserve_ports

    ports = _reserve_ports(n)
    ids = [PeerID("127.0.0.1", p) for p in ports]
    peers = PeerList(ids)
    out = []
    for me in ids:
        cfg = WorkerConfig(
            self_id=me,
            peers=peers,
            runners=PeerList(),
            parent=None,
            cluster_version=0,
            strategy=Strategy.STAR,
            config_server="",
            elastic_mode="",
            init_progress=0,
        )
        out.append(Peer(cfg))
    threads = [threading.Thread(target=p.start) for p in out]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
        assert not t.is_alive(), "peer start timed out"
    return out


@pytest.fixture(scope="module")
def clusters():
    built = {}

    def get(n):
        if n not in built:
            built[n] = make_peer_cluster(n)
        return built[n]

    yield get
    for ps in built.values():
        for p in ps:
            p.stop()


def _sessions(cluster, strategy=Strategy.RING_SEGMENTED, timeout=60.0,
              subset=None):
    """Fresh sessions on each peer's live transport; `subset` restricts
    to the first m peers (a smaller session epoch over the same
    transports — the in-process stand-in for an elastic resize)."""
    members = cluster if subset is None else cluster[:subset]
    peer_list = PeerList(list(p.self_id for p in members))
    return [
        HostSession(strategy, p.self_id, peer_list, p.client, p.collective,
                    timeout=timeout)
        for p in members
    ]


def _run_on_all(fns, join=120):
    errs = []

    def wrap(fn):
        try:
            fn()
        except BaseException as e:  # noqa: BLE001 - re-raised below
            errs.append(e)

    ts = [threading.Thread(target=wrap, args=(fn,)) for fn in fns]
    for t in ts:
        t.start()
    for t in ts:
        t.join(join)
        assert not t.is_alive(), "collective hung"
    if errs:
        raise errs[0]


def _close_all(sessions):
    for s in sessions:
        s.close(timeout=10)


def _replicated_sgd(p0, grad_rounds, k, lr, momentum=0.0):
    """The replicated reference: averaged gradient sum + the torch-SGD
    formula, full-size state — what every peer of the replicated path
    computes."""
    ref = [p.copy() for p in p0]
    bufs = [np.zeros(p.size, np.float32) for p in p0]
    for grads in grad_rounds:
        for i in range(len(ref)):
            g = grads[0][i].astype(np.float32).copy()
            for r in range(1, k):
                g = g + grads[r][i]
            g = g * np.float32(1.0 / k)
            if momentum:
                bufs[i] = np.float32(momentum) * bufs[i] + g
                g = bufs[i]
            ref[i] = ref[i] - np.float32(lr) * g
    return ref


# ---------------------------------------------------------------------------
# first-class reduce-scatter / all-gather halves
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("np_", [2, 3, 4])
def test_reduce_scatter_all_gather_exact(np_, clusters):
    """Exact payloads across sizes including the n<k empty-segment edge:
    every rank's shard equals the reference sum sliced at its owned
    bounds, and rs + ag reassembles the full allreduce result on every
    peer, bit for bit."""
    cluster = clusters(np_)
    rng = np.random.default_rng(7 + np_)
    sizes = [1, 2, np_ - 1, np_, np_ + 1, 1001, 4 * np_ + 3]
    inputs = {
        (si, r): rng.integers(-8, 9, s).astype(np.float32)
        for si, s in enumerate(sizes)
        for r in range(np_)
    }
    want = {
        si: sum(inputs[(si, r)] for r in range(np_))
        for si in range(len(sizes))
    }
    sessions = _sessions(cluster)
    shards = {}
    fulls = {}

    def run(r, sess):
        for si, s in enumerate(sizes):
            x = inputs[(si, r)]
            out = np.empty_like(x)
            b, e = sess.reduce_scatter(Workspace(
                send=x, recv=out, op=ReduceOp.SUM, name=f"zrs:{np_}:{si}",
            ))
            assert (b, e) == topo.owned_segment_bounds(s, np_, r)
            shards[(si, r)] = out[b:e].copy()
            full = np.empty_like(x)
            full[b:e] = out[b:e]
            sess.all_gather_shards(full, f"zag:{np_}:{si}")
            fulls[(si, r)] = full

    _run_on_all([lambda r=r, s=s: run(r, s) for r, s in enumerate(sessions)])
    for si, s in enumerate(sizes):
        for r in range(np_):
            b, e = topo.owned_segment_bounds(s, np_, r)
            np.testing.assert_array_equal(
                shards[(si, r)], want[si][b:e],
                err_msg=f"shard np={np_} size={s} rank={r}",
            )
            np.testing.assert_array_equal(
                fulls[(si, r)], want[si],
                err_msg=f"gathered np={np_} size={s} rank={r}",
            )


def test_all_gather_bf16_wire_bit_identical_across_peers(clusters, monkeypatch):
    """With the codec on, the weight all-gather carries bf16 on the wire
    and every peer — the segment owner included — lands on the SAME
    bf16-rounded values (one quantization per segment, decoded once per
    peer), within one wire step of the f32 input."""
    monkeypatch.setenv("KF_CONFIG_WIRE", "bf16")
    monkeypatch.setattr(HostSession, "WIRE_MIN_BYTES", 0)
    np_ = 2
    cluster = clusters(np_)
    sessions = _sessions(cluster)
    rng = np.random.default_rng(3)
    n = 1000
    truth = rng.standard_normal(n).astype(np.float32)
    outs = {}

    def run(r, sess):
        full = np.zeros(n, np.float32)
        b, e = topo.owned_segment_bounds(n, np_, r)
        full[b:e] = truth[b:e]
        sess.all_gather_shards(full, "bf16ag")
        outs[r] = full

    _run_on_all([lambda r=r, s=s: run(r, s) for r, s in enumerate(sessions)])
    np.testing.assert_array_equal(outs[0], outs[1])
    err = np.abs(outs[0] - truth)
    bound = np.abs(truth) * 2.0 ** -8 + 1e-30
    assert (err <= bound).all(), float((err / np.maximum(bound, 1e-30)).max())


# ---------------------------------------------------------------------------
# sharded update vs replicated: bit-identity
# ---------------------------------------------------------------------------

_SIZES = (5, 100, 333, 700, 20, 401)


@pytest.mark.parametrize("np_", [2, 3, 4])
def test_sharded_sync_bit_identical_plain_sgd(np_, clusters):
    """The acceptance criterion: plain SGD (no momentum), codec off —
    the sharded step lands every peer on params BIT-IDENTICAL to the
    replicated path on exact payloads, over several steps."""
    cluster = clusters(np_)
    sessions = _sessions(cluster)
    rng = np.random.default_rng(11 + np_)
    p0 = [rng.integers(-8, 9, s).astype(np.float32) for s in _SIZES]
    rounds = 3
    gr = {
        rnd: {r: [rng.integers(-8, 9, s).astype(np.float32) for s in _SIZES]
              for r in range(np_)}
        for rnd in range(rounds)
    }
    ref = _replicated_sgd(p0, [gr[rnd] for rnd in range(rounds)], np_, 0.1)
    res = {}

    def run(r, sess):
        params = [p.copy() for p in p0]
        zs = ShardedUpdateSession(params, ShardedSGD(0.1),
                                  name=f"sync{np_}", session=sess)
        for rnd in range(rounds):
            zs.step([g.copy() for g in gr[rnd][r]])
        res[r] = (params, zs.state_bytes())

    _run_on_all([lambda r=r, s=s: run(r, s) for r, s in enumerate(sessions)])
    for r in range(np_):
        for i in range(len(p0)):
            np.testing.assert_array_equal(
                res[r][0][i], ref[i], err_msg=f"np={np_} rank={r} tensor={i}",
            )
    # plain SGD state = the f32 shard master only: ~1/k of the params
    total = sum(s for s in _SIZES) * 4
    assert res[0][1] <= total // np_ + 4 * len(_SIZES) * 2


def test_sharded_async_bit_identical_momentum(clusters, monkeypatch):
    """Momentum SGD through the async scheduler: shuffled per-rank
    submission, multi-bucket plan, back-to-back rounds WITHOUT
    wait_params between them (weight all-gathers of round r overlap
    round r+1's submissions), still bit-identical to the replicated
    formula."""
    monkeypatch.setenv("KF_CONFIG_ASYNC", "on")
    monkeypatch.setattr(HostSession, "GROUP_BUCKET_BYTES", 1200)
    np_ = 3
    cluster = clusters(np_)
    sessions = _sessions(cluster)
    rng = np.random.default_rng(23)
    p0 = [rng.integers(-8, 9, s).astype(np.float32) for s in _SIZES]
    rounds = 4
    gr = {
        rnd: {r: [rng.integers(-8, 9, s).astype(np.float32) for s in _SIZES]
              for r in range(np_)}
        for rnd in range(rounds)
    }
    ref = _replicated_sgd(p0, [gr[rnd] for rnd in range(rounds)], np_,
                          0.1, momentum=0.9)
    res = {}

    def run(r, sess):
        params = [p.copy() for p in p0]
        zs = ShardedUpdateSession(params, ShardedSGD(0.1, momentum=0.9),
                                  name="async", session=sess)
        assert zs.bucket_count() >= 2  # the 1200-byte cap split the set
        order_rng = np.random.default_rng(1000 * r)
        for rnd in range(rounds):
            for i in order_rng.permutation(len(_SIZES)):
                zs.submit_grad(int(i), gr[rnd][r][int(i)].copy())
            zs.flush(timeout=90)
        zs.wait_params(timeout=60)
        res[r] = (params, sess.scheduler().stats(), zs)

    _run_on_all([lambda r=r, s=s: run(r, s) for r, s in enumerate(sessions)])
    for r in range(np_):
        for i in range(len(p0)):
            np.testing.assert_array_equal(
                res[r][0][i], ref[i], err_msg=f"rank={r} tensor={i}",
            )
    st = res[0][1]
    assert st["zero_units"] == rounds * res[0][2].bucket_count(), st
    assert st["rounds"] == rounds
    _close_all(sessions)


def test_sharded_bf16_weight_ag_error_bound(clusters, monkeypatch):
    """bf16 weight all-gather: params land within one bf16 step of the
    f32 replicated reference (the masters integrate exactly; only the
    broadcast mirror is quantized — the error does NOT accumulate over
    steps), and all peers stay bit-identical to each other."""
    monkeypatch.setenv("KF_CONFIG_WIRE", "bf16")
    monkeypatch.setattr(HostSession, "WIRE_MIN_BYTES", 0)
    np_ = 2
    cluster = clusters(np_)
    sessions = _sessions(cluster)
    rng = np.random.default_rng(5)
    p0 = [rng.standard_normal(s).astype(np.float32) for s in (64, 500)]
    rounds = 6
    gr = {
        rnd: {r: [rng.standard_normal(s).astype(np.float32) * 0.1
                  for s in (64, 500)] for r in range(np_)}
        for rnd in range(rounds)
    }
    res = {}

    def run(r, sess):
        params = [p.copy() for p in p0]
        zs = ShardedUpdateSession(params, ShardedSGD(0.05),
                                  name="bf16", session=sess)
        for rnd in range(rounds):
            zs.step([g.copy() for g in gr[rnd][r]])
        res[r] = params

    _run_on_all([lambda r=r, s=s: run(r, s) for r, s in enumerate(sessions)])
    # cross-peer bit-identity (every peer decodes the same encodings)
    for i in range(len(p0)):
        np.testing.assert_array_equal(res[0][i], res[1][i])
    # masters integrate in f32: the mirror is within ONE quantization of
    # the f32 reference after 6 steps (non-accumulating error). The RS
    # leg is raw, so the float sums match the reference's association
    # (k=2 chain) exactly.
    ref = _replicated_sgd(p0, [gr[rnd] for rnd in range(rounds)], np_, 0.05)
    for i in range(len(p0)):
        err = np.abs(res[0][i] - ref[i])
        bound = np.abs(ref[i]) * 2.0 ** -8 + 1e-7
        assert (err <= bound).all(), float(err.max())


# ---------------------------------------------------------------------------
# KF_CONFIG_ZERO: consensus + mode resolution
# ---------------------------------------------------------------------------

def test_zero_knob_consensus_divergence(clusters):
    """KF_CONFIG_ZERO is in the engine-knob consensus: a peer that
    resolved a different mode raises a RuntimeError NAMING the knob
    within seconds (never a rendezvous deadlock)."""
    cluster = clusters(2)
    sessions = _sessions(cluster)
    knobs = dict(sessions[0].engine_knobs())
    assert "KF_CONFIG_ZERO" in knobs
    sessions[1].zero_mode = "on"  # diverge one peer's resolved mode
    errs = {}
    t0 = time.monotonic()

    def check(r, sess):
        try:
            sess.check_knob_consensus()
            errs[r] = None
        except RuntimeError as e:
            errs[r] = str(e)

    _run_on_all([lambda r=r, s=s: check(r, s)
                 for r, s in enumerate(sessions)])
    assert time.monotonic() - t0 < 10
    for r in range(2):
        assert errs[r] is not None and "KF_CONFIG_ZERO" in errs[r], errs


def test_zero_mode_resolution(clusters, monkeypatch):
    cluster = clusters(2)
    monkeypatch.setenv("KF_CONFIG_ZERO", "auto")
    sess = _sessions(cluster)[0]
    assert sess.zero_enabled()  # auto: on for >= 2 peers
    monkeypatch.setenv("KF_CONFIG_ZERO", "off")
    assert not _sessions(cluster)[0].zero_enabled()
    monkeypatch.setenv("KF_CONFIG_ZERO", "bogus")
    with pytest.raises(ValueError, match="KF_CONFIG_ZERO"):
        _sessions(cluster)[0]


# ---------------------------------------------------------------------------
# elastic re-shard: grow 2->4 and shrink 4->2
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k_before,k_after", [(2, 4), (4, 2)])
def test_reshard_across_epochs_bit_identical(k_before, k_after, clusters):
    """Resize mid-run with sharded state: run steps at k_before, export
    the state (one-shot exact all-gather), rebuild on a k_after session
    epoch with restore_state, run more steps — params AND the re-sharded
    momentum must be bit-identical to a fresh replicated run over the
    same gradient schedule (zero-step-loss). Every rank carries the
    IDENTICAL integer gradients each round, so the averaged gradient
    (k·g)·(1/k) is exact and equal at every power-of-two k — the
    reference is k-independent."""
    cluster = clusters(4)
    rng = np.random.default_rng(31)
    p0 = [rng.integers(-8, 9, s).astype(np.float32) for s in (40, 333)]
    lr, mom = 0.1, 0.9
    _totals = {
        rnd: [rng.integers(-8, 9, p.size).astype(np.float32) for p in p0]
        for rnd in range(4)
    }

    def grads_for(rnd, k):
        return {r: [t.copy() for t in _totals[rnd]] for r in range(k)}

    # fresh replicated reference over all 4 rounds (any k: same average)
    ref_all = _replicated_sgd(
        p0, [grads_for(rnd, 1) for rnd in range(4)], 1, lr, momentum=mom
    )
    # replicated momentum state after all rounds (for the shard check)
    ref_bufs = [np.zeros(p.size, np.float32) for p in p0]
    for rnd in range(4):
        for i in range(len(p0)):
            g = _totals[rnd][i].copy()
            ref_bufs[i] = np.float32(mom) * ref_bufs[i] + g

    # epoch A: k_before peers, rounds 0-1
    sessions_a = _sessions(cluster, subset=k_before)
    state = {}

    def run_a(r, sess):
        params = [p.copy() for p in p0]
        zs = ShardedUpdateSession(
            params, ShardedSGD(lr, momentum=mom),
            name=f"rz{k_before}{k_after}", session=sess,
        )
        for rnd in range(2):
            zs.step([g.copy() for g in grads_for(rnd, k_before)[r]])
        blob = zs.export_state()
        state[r] = (params, blob)

    _run_on_all([lambda r=r, s=s: run_a(r, s)
                 for r, s in enumerate(sessions_a)])
    blobs = [state[r][1] for r in range(k_before)]
    assert all(b == blobs[0] for b in blobs), "export must be identical"

    # epoch B: k_after peers, restore, rounds 2-3. Joining peers start
    # from the blob + current params (the elastic state-sync contract).
    sessions_b = _sessions(cluster, subset=k_after)
    res = {}

    def run_b(r, sess):
        params = (
            [p.copy() for p in state[r][0]] if r in state
            else [p.copy() for p in p0]  # joiner: any placeholder —
        )                                 # restore overwrites from blob
        zs = ShardedUpdateSession(
            params, ShardedSGD(lr, momentum=mom),
            name=f"rz{k_before}{k_after}", session=sess,
            restore_state=blobs[0],
        )
        for rnd in (2, 3):
            zs.step([g.copy() for g in grads_for(rnd, k_after)[r]])
        res[r] = (params, zs)

    _run_on_all([lambda r=r, s=s: run_b(r, s)
                 for r, s in enumerate(sessions_b)])
    for r in range(k_after):
        for i in range(len(p0)):
            np.testing.assert_array_equal(
                res[r][0][i], ref_all[i],
                err_msg=f"{k_before}->{k_after} rank={r} tensor={i}",
            )
    # re-sharded momentum bit-identical to the fresh replicated run's
    # shard at the new bounds
    full_mom = np.concatenate(ref_bufs)
    zs0 = res[0][1]
    off = 0
    for b in zs0._buckets:
        np.testing.assert_array_equal(
            b.state["momentum"], full_mom[off + b.ob: off + b.oe],
            err_msg=f"momentum shard bucket {b.index}",
        )
        off += b.total


# ---------------------------------------------------------------------------
# drain / close semantics
# ---------------------------------------------------------------------------

def test_mid_flight_gather_drains_and_closed_raises(clusters, monkeypatch):
    """flush() returns with weight all-gathers possibly still walking;
    a session close right then must drain (or cancel) them cleanly —
    scheduler threads provably dead, params either fully updated or
    untouched per bucket — and the old handles raise SchedulerClosed."""
    monkeypatch.setenv("KF_CONFIG_ASYNC", "on")
    np_ = 2
    cluster = clusters(np_)
    sessions = _sessions(cluster)
    rng = np.random.default_rng(41)
    res = {}

    def run(r, sess):
        params = [rng.integers(-8, 9, 50_000).astype(np.float32)]
        zs = ShardedUpdateSession(params, ShardedSGD(0.1),
                                  name="drain", session=sess)
        zs.submit_grad(0, np.ones(50_000, np.float32))
        zs.flush(timeout=60)
        # no wait_params: the weight all-gather may be mid-flight
        res[r] = (sess, sess.scheduler(), list(sess.scheduler()._threads))

    _run_on_all([lambda r=r, s=s: run(r, s) for r, s in enumerate(sessions)])
    _close_all([res[r][0] for r in range(np_)])
    for r in range(np_):
        for t in res[r][2]:
            t.join(10)
            assert not t.is_alive(), "scheduler thread outlived close()"
        with pytest.raises(SchedulerClosed):
            res[r][1].flush(timeout=5)
        try:
            # bounded either way: the gather DRAINED (clean return) or
            # was cancelled past the budget (closed) — never a hang
            res[r][1].wait_gather(timeout=5)
        except SchedulerClosed:
            pass


def test_zero_submit_requires_handler_consistency(clusters, monkeypatch):
    """A tensor registered as sharded cannot later be submitted as a
    plain allreduce (the kind is part of the registered identity), and
    a second handler is rejected."""
    monkeypatch.setenv("KF_CONFIG_ASYNC", "on")
    np_ = 2
    cluster = clusters(np_)
    sessions = _sessions(cluster)
    zss = {}

    def round1(r, sess):
        params = [np.zeros(32, np.float32)]
        zs = ShardedUpdateSession(params, ShardedSGD(0.1),
                                  name="hc", session=sess)
        zs.submit_grad(0, np.ones(32, np.float32))
        zs.flush(timeout=30)
        zs.wait_params(timeout=30)
        zss[r] = zs

    _run_on_all([lambda r=r, s=s: round1(r, s)
                 for r, s in enumerate(sessions)])
    sched = sessions[0].scheduler()
    x = np.ones(32, np.float32)
    with pytest.raises(ValueError, match="unregistered"):
        sched.submit(Workspace(send=x, recv=np.empty_like(x),
                               op=ReduceOp.SUM, name="kungfu::zero:hc:0"))
    params2 = [np.zeros(32, np.float32)]
    zs2 = ShardedUpdateSession(params2, ShardedSGD(0.1),
                               name="hc", session=sessions[0])
    with pytest.raises(ValueError, match="ONE sharded-update handler"):
        zs2.submit_grad(0, x)
    _close_all(sessions)


def test_mixed_sharded_and_allreduce_round(clusters, monkeypatch):
    """A round carrying sharded gradients AND a plain async allreduce
    (e.g. a metrics lane): both complete, the allreduce recv holds the
    sum, the params hold the sharded update."""
    monkeypatch.setenv("KF_CONFIG_ASYNC", "on")
    np_ = 2
    cluster = clusters(np_)
    sessions = _sessions(cluster)
    rng = np.random.default_rng(53)
    p0 = [rng.integers(-8, 9, 200).astype(np.float32)]
    gr = {r: [rng.integers(-8, 9, 200).astype(np.float32)] for r in range(np_)}
    ref = _replicated_sgd(p0, [gr], np_, 0.1)
    res = {}

    def run(r, sess):
        params = [p.copy() for p in p0]
        zs = ShardedUpdateSession(params, ShardedSGD(0.1),
                                  name="mix", session=sess)
        sched = sess.scheduler()
        metric = np.full(8, float(r + 1), np.float64)
        mout = np.empty_like(metric)
        zs.submit_grad(0, gr[r][0].copy())
        sched.submit(Workspace(send=metric, recv=mout, op=ReduceOp.SUM,
                               name="mix:metric"))
        sched.flush(timeout=60)
        zs.wait_params(timeout=30)
        res[r] = (params, mout)

    _run_on_all([lambda r=r, s=s: run(r, s) for r, s in enumerate(sessions)])
    for r in range(np_):
        np.testing.assert_array_equal(res[r][0][0], ref[0])
        np.testing.assert_allclose(res[r][1], 3.0)
    _close_all(sessions)


# ---------------------------------------------------------------------------
# optax frontend (device plane, 8-dev CPU mesh)
# ---------------------------------------------------------------------------

def test_optax_zero_sharded_matches_ssgd():
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    from kungfu_tpu.optimizers import synchronous_sgd, zero_sharded
    from kungfu_tpu.parallel import make_mesh
    from jax import shard_map

    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh")
    mesh = make_mesh({"dp": 8})

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] + params["b"] - y) ** 2)

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (64, 4))
    y = x @ jax.random.normal(jax.random.PRNGKey(1), (4, 2))
    params0 = {
        "w": jax.random.normal(jax.random.PRNGKey(2), (4, 2)),
        "b": jax.random.normal(jax.random.PRNGKey(3), (2,)),
    }

    def train(opt, state_specs):
        def local(params, state, bx, by):
            grads = jax.grad(loss_fn)(params, (bx, by))
            updates, state = opt.update(grads, state, params)
            return optax.apply_updates(params, updates), state

        step = jax.jit(shard_map(
            local, mesh=mesh,
            in_specs=(P(), state_specs, P("dp"), P("dp")),
            out_specs=(P(), state_specs), check_vma=False,
        ))
        init = jax.jit(shard_map(
            lambda p: opt.init(p), mesh=mesh, in_specs=(P(),),
            out_specs=state_specs, check_vma=False,
        ))
        params, state = params0, init(params0)
        for _ in range(10):
            params, state = step(params, state, x, y)
        return params

    p_ref = train(synchronous_sgd(optax.sgd(0.05, momentum=0.9), "dp"), P())
    p_zero = train(
        zero_sharded(optax.sgd(0.05, momentum=0.9), axis_size=8, axis_name="dp"),
        P("dp"),
    )
    for k in params0:
        np.testing.assert_allclose(
            np.asarray(p_zero[k]), np.asarray(p_ref[k]), rtol=2e-5, atol=1e-6,
        )


# ---------------------------------------------------------------------------
# torch frontend (cluster of one; np=2 e2e lives in the kfrun test)
# ---------------------------------------------------------------------------

def test_torch_zero_mode_flip_state_blob(monkeypatch):
    """export_state blobs are mode-portable: a resize can flip the
    resolved KF_CONFIG_ZERO mode (e.g. `auto` shrinking to one peer),
    so BOTH modes serialize the canonical bucket-shaped layout and each
    can restore the other's blob — masters refresh the params, state
    leaves re-shard/de-shard."""
    torch = pytest.importorskip("torch")
    from kungfu_tpu import api as kf_api
    from kungfu_tpu import torch as kf_torch

    sess = kf_api.get_default_peer().current_session()
    torch.manual_seed(3)
    model = torch.nn.Linear(5, 3, bias=True)

    monkeypatch.setattr(sess, "zero_mode", "off")  # replicated leg
    opt = kf_torch.ZeroSGDOptimizer(model, lr=0.1, momentum=0.9)
    for _ in range(2):
        opt.zero_grad()
        model(torch.ones(2, 5)).pow(2).sum().backward()
        opt.step()
    assert opt._mode == "replicated"
    blob_r = opt.export_state()
    params_after = [p.detach().clone() for p in model.parameters()]
    mom_after = [st["momentum"].copy() for st in opt._repl_state]

    # replicated blob -> sharded rebuild (k=1 shard == full)
    monkeypatch.setattr(sess, "zero_mode", "on")
    opt.rebuild(blob_r)
    assert opt._mode == "sharded"
    for p, want in zip(model.parameters(), params_after):
        np.testing.assert_array_equal(p.detach().numpy(), want.numpy())
    restored = np.concatenate(
        [b.state["momentum"] for b in opt._zs._buckets]
    )
    np.testing.assert_array_equal(restored, np.concatenate(mom_after))

    # sharded blob -> replicated rebuild
    blob_s = opt.export_state()
    monkeypatch.setattr(sess, "zero_mode", "off")
    opt.rebuild(blob_s)
    assert opt._mode == "replicated"
    for p, want in zip(model.parameters(), params_after):
        np.testing.assert_array_equal(p.detach().numpy(), want.numpy())
    np.testing.assert_array_equal(
        np.concatenate([st["momentum"] for st in opt._repl_state]),
        np.concatenate(mom_after),
    )


def test_zero_api_e2e_np3_kfrun():
    """kfrun np=3: api.reduce_scatter / api.all_gather / a
    sharded_update_session training loop / torch ZeroSGDOptimizer under
    KF_CONFIG_ZERO=auto — the api-level acceptance where the singleton
    peer actually spans processes (in-process tests above use explicit
    sessions)."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    agent = os.path.join(repo, "tests", "integration", "zero_api_agent.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["KF_CONFIG_ZERO"] = "auto"
    r = subprocess.run(
        [
            sys.executable, "-m", "kungfu_tpu.runner.cli",
            *kfrun_ports().args,  # this xdist worker's block
            "-np", "3", "-H", "127.0.0.1:3",
            sys.executable, agent,
        ],
        env=env, capture_output=True, text=True, timeout=300, cwd=repo,
    )
    out = r.stdout + r.stderr
    assert r.returncode == 0, out
    for rank in range(3):
        assert f"ZERO rank={rank} ALL OK" in r.stdout, out


def test_torch_zero_optimizer_single(monkeypatch):
    """Cluster of one: both modes produce the exact SGD-with-momentum
    formula. Which mode runs depends on when the process-wide default
    peer's session was built relative to KF_CONFIG_ZERO (a full-suite
    run may have created it already) — assert per the DECIDED mode;
    the sharded mode at k>1 is covered by the kfrun e2e above."""
    torch = pytest.importorskip("torch")
    monkeypatch.setenv("KF_CONFIG_ZERO", "on")
    from kungfu_tpu import torch as kf_torch

    torch.manual_seed(0)
    model = torch.nn.Linear(3, 2, bias=True)
    ref = [p.detach().clone() for p in model.parameters()]
    bufs = [torch.zeros_like(p) for p in ref]
    opt = kf_torch.ZeroSGDOptimizer(model, lr=0.5, momentum=0.9)
    for _ in range(3):
        opt.zero_grad()
        model(torch.ones(4, 3)).pow(2).sum().backward()
        grads = [p.grad.detach().clone() for p in model.parameters()]
        opt.step()
        for i, g in enumerate(grads):
            bufs[i] = 0.9 * bufs[i] + g
            ref[i] = ref[i] - 0.5 * bufs[i]
    for p, r in zip(model.parameters(), ref):
        np.testing.assert_allclose(p.detach().numpy(), r.numpy(), rtol=1e-6)
    n = sum(p.numel() for p in model.parameters())
    if opt._mode == "sharded":
        # momentum shard + master shard at k=1 == full size each
        assert opt.state_bytes() == 2 * n * 4
    else:
        # replicated fallback: full momentum, no masters
        assert opt._mode == "replicated"
        assert opt.state_bytes() == n * 4
