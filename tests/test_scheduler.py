"""Async collective scheduler (ISSUE 10 tentpole).

Covers: out-of-order per-tensor submission at np in {2,3,4} bit-identical
to the synchronous group path on exact payloads (including multi-bucket
plans, singles, mixed dtypes and the wire codec), the once-per-epoch
registration consensus (divergent registration raises a named error
instead of deadlocking), mid-flight drain on resize (Peer._update_to
closes the old epoch's scheduler), real-error propagation through
flush(), plan determinism, and the np=4 kfrun smoke under
KF_DEBUG_LOCKS=1 asserting zero lock-order findings.

Exactness note: like test_segmented, equivalence cases reduce
INTEGER-VALUED payloads so SUM is associativity-free and "bit-identical
to the sync path" is well-defined; the async path builds the same
buckets in the same registered order, so even float results match the
sync path bit-for-bit — asserted with exact integer payloads to keep
the contract crisp.
"""

import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from ports import kfrun_ports

from kungfu_tpu.base.ops import ReduceOp
from kungfu_tpu.base.strategy import Strategy
from kungfu_tpu.base.workspace import Workspace
from kungfu_tpu.collective.host_session import HostSession
from kungfu_tpu.collective.scheduler import SchedulerClosed
from kungfu_tpu.peer import Peer
from kungfu_tpu.plan.peer import PeerID, PeerList
from kungfu_tpu.runner.env import WorkerConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AGENT = os.path.join(REPO, "tests", "integration", "bench_host_agent.py")


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


def _sessions(cluster, strategy, timeout=60.0):
    peer_list = cluster[0].config.peers
    return [
        HostSession(strategy, p.self_id, peer_list, p.client, p.collective,
                    timeout=timeout)
        for p in cluster
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


# tensor set: 6 f32 (fused; tiny bucket cap splits them into several
# buckets), 2 int32 singles (below FUSE_MIN per group), 1 f64 single
_SIZES_F32 = [100, 300, 50, 700, 20, 401]
_SIZES_I32 = [64, 9]
_SIZES_F64 = [33]


def _inputs(rng, np_):
    ins = {}
    for r in range(np_):
        ts = [rng.integers(-8, 9, s).astype(np.float32) for s in _SIZES_F32]
        ts += [rng.integers(-8, 9, s).astype(np.int32) for s in _SIZES_I32]
        ts += [rng.integers(-8, 9, s).astype(np.float64) for s in _SIZES_F64]
        ins[r] = ts
    return ins


def _sync_reference(cluster, strategy, ins, np_, tag):
    """The synchronous group path's results on the same inputs."""
    sessions = _sessions(cluster, strategy)
    outs = {r: [np.empty_like(x) for x in ins[r]] for r in range(np_)}

    def run(r, sess):
        ws = [
            Workspace(send=x, recv=o, op=ReduceOp.SUM, name=f"sync:{tag}:{i}")
            for i, (x, o) in enumerate(zip(ins[r], outs[r]))
        ]
        sess.group_all_reduce(ws)

    _run_on_all([lambda r=r, s=s: run(r, s) for r, s in enumerate(sessions)])
    return outs


@pytest.mark.parametrize("np_", [2, 3, 4])
def test_out_of_order_submission_bit_identical(np_, clusters, monkeypatch):
    """Per-rank shuffled submission order, several rounds, multi-bucket
    plan — results bit-identical to the synchronous group path. The
    first round uses `priority` to pin the negotiated order (canonical
    tensor index) while ARRIVING shuffled, proving registration order
    and arrival order are decoupled."""
    monkeypatch.setenv("KF_CONFIG_ASYNC", "on")
    monkeypatch.setattr(HostSession, "SEGMENT_MIN_BYTES", 0)
    monkeypatch.setattr(HostSession, "GROUP_BUCKET_BYTES", 1200)
    cluster = clusters(np_)
    rng = np.random.default_rng(11 + np_)
    ins = _inputs(rng, np_)
    want = _sync_reference(cluster, Strategy.RING_SEGMENTED, ins, np_,
                           f"ref{np_}")
    sessions = _sessions(cluster, Strategy.RING_SEGMENTED)
    n_tensors = len(ins[0])
    outs = {r: [np.empty_like(x) for x in ins[r]] for r in range(np_)}
    rounds = 3

    def run(r, sess):
        sched = sess.scheduler()
        order_rng = np.random.default_rng(1000 * r)  # per-rank order!
        for rnd in range(rounds):
            order = order_rng.permutation(n_tensors)
            for i in order:
                ws = Workspace(
                    send=ins[r][i], recv=outs[r][i], op=ReduceOp.SUM,
                    name=f"grad:{i}",
                )
                # round 0: arrival is shuffled, but priority pins the
                # negotiated registered order to the canonical index on
                # every peer; later rounds ignore priority entirely
                sched.submit(ws, priority=int(i) if rnd == 0 else None)
            sched.flush(timeout=90)
            for i in range(n_tensors):
                np.testing.assert_array_equal(
                    outs[r][i], want[r][i],
                    err_msg=f"np={np_} rank={r} round={rnd} tensor={i}",
                )

    _run_on_all([lambda r=r, s=s: run(r, s) for r, s in enumerate(sessions)])
    # the plan really was multi-unit (buckets + singles), i.e. the
    # out-of-order coverage exercised readiness gating, not one big walk
    st = sessions[0].scheduler().stats()
    assert st["units"] >= rounds * 4, st
    assert st["buckets"] >= rounds * 2, st
    assert st["rounds"] == rounds
    _close_all(sessions)


def test_async_with_wire_codec_matches_sync(clusters, monkeypatch):
    """Async + bf16 wire codec: the fused bucket takes the compressed
    single-buffer pack path; results still bit-identical to the sync
    path under the same codec (exact payloads are exactly representable
    in bf16)."""
    monkeypatch.setenv("KF_CONFIG_ASYNC", "on")
    monkeypatch.setenv("KF_CONFIG_WIRE", "bf16")
    monkeypatch.setattr(HostSession, "SEGMENT_MIN_BYTES", 0)
    monkeypatch.setattr(HostSession, "WIRE_MIN_BYTES", 0)
    np_ = 2
    cluster = clusters(np_)
    rng = np.random.default_rng(77)
    ins = _inputs(rng, np_)
    want = _sync_reference(cluster, Strategy.RING_SEGMENTED, ins, np_, "wref")
    sessions = _sessions(cluster, Strategy.RING_SEGMENTED)
    outs = {r: [np.empty_like(x) for x in ins[r]] for r in range(np_)}

    def run(r, sess):
        sched = sess.scheduler()
        for i, x in enumerate(ins[r]):
            sched.submit(Workspace(send=x, recv=outs[r][i],
                                   op=ReduceOp.SUM, name=f"wg:{i}"))
        sched.flush(timeout=90)

    _run_on_all([lambda r=r, s=s: run(r, s) for r, s in enumerate(sessions)])
    for r in range(np_):
        for i in range(len(ins[r])):
            np.testing.assert_array_equal(outs[r][i], want[r][i])
    _close_all(sessions)


def test_registration_divergence_raises_named_error(clusters, monkeypatch):
    """Peers that register different tensor sets must get an immediate
    RuntimeError naming the registration consensus — not a rendezvous
    deadlock (the check_knob_consensus machinery reused)."""
    monkeypatch.setenv("KF_CONFIG_ASYNC", "on")
    np_ = 2
    cluster = clusters(np_)
    sessions = _sessions(cluster, Strategy.STAR, timeout=20)
    failures = {}

    def run(r, sess):
        sched = sess.scheduler()
        x = np.ones(10, np.float32)
        o = np.empty_like(x)
        # rank 0 registers "a", rank 1 registers "b": divergent
        sched.submit(Workspace(send=x, recv=o, op=ReduceOp.SUM,
                               name="a" if r == 0 else "b"))
        try:
            sched.flush(timeout=30)
        except RuntimeError as e:
            failures[r] = str(e)

    _run_on_all([lambda r=r, s=s: run(r, s) for r, s in enumerate(sessions)])
    assert set(failures) == {0, 1}
    assert all("registration diverged" in m for m in failures.values())
    _close_all(sessions)


def test_submit_contract_errors(clusters, monkeypatch):
    """Unregistered and double submissions fail fast with named errors;
    flush with missing tensors refuses to wait forever."""
    monkeypatch.setenv("KF_CONFIG_ASYNC", "on")
    np_ = 2
    cluster = clusters(np_)
    sessions = _sessions(cluster, Strategy.STAR, timeout=20)

    def first_round(r, sess):
        sched = sess.scheduler()
        for i in range(2):
            x = np.full(8, r + 1.0, np.float32)
            sched.submit(Workspace(send=x, recv=np.empty_like(x),
                                   op=ReduceOp.SUM, name=f"t:{i}"))
        sched.flush(timeout=30)

    _run_on_all([lambda r=r, s=s: first_round(r, s)
                 for r, s in enumerate(sessions)])
    sched = sessions[0].scheduler()
    x = np.ones(8, np.float32)
    with pytest.raises(ValueError, match="unregistered"):
        sched.submit(Workspace(send=x, recv=np.empty_like(x),
                               op=ReduceOp.SUM, name="rogue"))
    sched.submit(Workspace(send=x, recv=np.empty_like(x),
                           op=ReduceOp.SUM, name="t:0"))
    with pytest.raises(ValueError, match="submitted twice"):
        sched.submit(Workspace(send=x, recv=np.empty_like(x),
                               op=ReduceOp.SUM, name="t:0"))
    with pytest.raises(RuntimeError, match="not submitted this round"):
        sched.flush(timeout=5)
    _close_all(sessions)


def test_walk_error_propagates_real_error(clusters, monkeypatch):
    """A transport failure inside a scheduled walk must surface the REAL
    error from flush() — and permanently poison the scheduler (no silent
    half-reduced rounds)."""
    monkeypatch.setenv("KF_CONFIG_ASYNC", "on")
    np_ = 2
    cluster = clusters(np_)
    sessions = _sessions(cluster, Strategy.STAR, timeout=20)

    def ok_round(r, sess):
        sched = sess.scheduler()
        x = np.full(8, r + 1.0, np.float32)
        sched.submit(Workspace(send=x, recv=np.empty_like(x),
                               op=ReduceOp.SUM, name="g"))
        sched.flush(timeout=30)

    _run_on_all([lambda r=r, s=s: ok_round(r, s)
                 for r, s in enumerate(sessions)])

    class Boom(RuntimeError):
        pass

    def broken_walk(w, cancel=None, defer_decode=False):
        raise Boom("injected transport failure")

    for sess in sessions:
        # symmetric injection at the engine-dispatch seam: every
        # scheduled walk fails identically on both peers, so the test
        # sees the scheduler's error channel, not transport asymmetry
        monkeypatch.setattr(sess, "_allreduce_ws", broken_walk,
                            raising=False)
    failures = {}

    def bad_round(r, sess):
        sched = sess.scheduler()
        x = np.full(8, r + 1.0, np.float32)
        sched.submit(Workspace(send=x, recv=np.empty_like(x),
                               op=ReduceOp.SUM, name="g"))
        try:
            sched.flush(timeout=30)
        except Boom as e:
            failures[r] = str(e)

    _run_on_all([lambda r=r, s=s: bad_round(r, s)
                 for r, s in enumerate(sessions)])
    assert set(failures) == {0, 1}
    assert all("injected transport failure" in m for m in failures.values())
    # the scheduler is dead: the next submit re-raises the real error
    with pytest.raises(Boom):
        sessions[0].scheduler().submit(Workspace(
            send=np.ones(8, np.float32), recv=np.empty(8, np.float32),
            op=ReduceOp.SUM, name="g",
        ))
    _close_all(sessions)


def test_resize_drains_scheduler_mid_flight(monkeypatch):
    """An elastic resize with a half-submitted round in flight: the old
    epoch's scheduler drains/cancels inside Peer._update_to (no hang, no
    orphan threads), pending-but-unlaunched tensors are dropped, and the
    old scheduler handle reports SchedulerClosed instead of wedging."""
    monkeypatch.setenv("KF_CONFIG_ASYNC", "on")
    cluster = make_peer_cluster(2)
    try:
        # round 1 on the peers' CURRENT sessions: registers + starts
        # the scheduler threads on the live epoch
        def round1(p):
            sched = p.current_session().scheduler()
            for i in range(3):
                x = np.full(16, p.current_session().rank + 1.0, np.float32)
                sched.submit(Workspace(send=x, recv=np.empty_like(x),
                                       op=ReduceOp.SUM, name=f"rz:{i}"))
            sched.flush(timeout=60)

        _run_on_all([lambda p=p: round1(p) for p in cluster])
        old_scheds = [p.current_session().scheduler() for p in cluster]
        old_threads = [list(s._threads) for s in old_scheds]
        assert all(ts for ts in old_threads)
        # mid-flight: submit a PARTIAL round (1 of 3 tensors) — the
        # launcher is now parked waiting for the rest
        for p in cluster:
            x = np.full(16, 1.0, np.float32)
            p.current_session().scheduler().submit(Workspace(
                send=x, recv=np.empty_like(x), op=ReduceOp.SUM, name="rz:0"))
        # shrink 2 -> 1: both peers run the resize protocol; _update_to
        # must close the old scheduler BEFORE swapping sessions
        results = {}

        def resize(idx, p):
            results[idx] = p.resize_cluster(1)

        _run_on_all([lambda i=i, p=p: resize(i, p)
                     for i, p in enumerate(cluster)])
        assert results[0] == (True, False)   # survivor
        assert results[1] == (True, True)    # detached
        # the old epoch's threads are gone and its handle is closed
        for ts in old_threads:
            for t in ts:
                t.join(10)
                assert not t.is_alive(), "scheduler thread outlived epoch"
        with pytest.raises(SchedulerClosed):
            old_scheds[0].flush(timeout=5)
        # the surviving peer's NEW session works (k=1 round trip)
        survivor = cluster[0].current_session()
        assert survivor.size == 1
        sched = survivor.scheduler()
        x = np.full(4, 7.0, np.float32)
        o = np.empty_like(x)
        sched.submit(Workspace(send=x, recv=o, op=ReduceOp.SUM, name="rz:0"))
        sched.flush(timeout=30)
        np.testing.assert_array_equal(o, x)
    finally:
        for p in cluster:
            p.stop()


def test_empty_flush_noop_and_round_aware_flush(clusters, monkeypatch):
    """A defensive flush with nothing submitted must be a true no-op —
    before registration it must NOT freeze an empty registry, and at a
    clean round boundary it must not raise or advance the round. And
    flush_round (AsyncGroupResult.wait's form) is idempotent per round:
    the second caller observes the advanced round and returns."""
    monkeypatch.setenv("KF_CONFIG_ASYNC", "on")
    np_ = 2
    cluster = clusters(np_)
    sessions = _sessions(cluster, Strategy.STAR, timeout=20)

    def run(r, sess):
        sched = sess.scheduler()
        sched.flush(timeout=5)  # pre-registration: no-op, no consensus
        assert sched._registry is None
        x = np.full(8, r + 1.0, np.float32)
        o = np.empty_like(x)
        rnd = sched.round_index()
        sched.submit(Workspace(send=x, recv=o, op=ReduceOp.SUM, name="e"))
        sched.flush_round(rnd, timeout=30)   # first wait: flushes
        np.testing.assert_array_equal(o, np.full(8, 3.0, np.float32))
        sched.flush_round(rnd, timeout=5)    # second wait: no-op
        sched.flush(timeout=5)               # clean boundary: no-op
        assert sched.round_index() == rnd + 1

    _run_on_all([lambda r=r, s=s: run(r, s) for r, s in enumerate(sessions)])
    _close_all(sessions)


def test_plan_determinism_and_bucket_layout(clusters, monkeypatch):
    """The negotiated plan is a pure function of the registered order
    and the cluster-agreed knobs: fused units respect the byte cap and
    preserve registered order; sub-FUSE_MIN groups launch as singles."""
    monkeypatch.setenv("KF_CONFIG_ASYNC", "on")
    monkeypatch.setattr(HostSession, "GROUP_BUCKET_BYTES", 1200)
    np_ = 2
    cluster = clusters(np_)
    sessions = _sessions(cluster, Strategy.STAR, timeout=20)

    def run(r, sess):
        sched = sess.scheduler()
        for i, s in enumerate(_SIZES_F32):
            x = np.full(s, r + 1.0, np.float32)
            sched.submit(Workspace(send=x, recv=np.empty_like(x),
                                   op=ReduceOp.SUM, name=f"pd:{i}"))
        x = np.ones(5, np.int32)
        sched.submit(Workspace(send=x, recv=np.empty_like(x),
                               op=ReduceOp.SUM, name="pd:i"))
        sched.flush(timeout=30)

    _run_on_all([lambda r=r, s=s: run(r, s) for r, s in enumerate(sessions)])
    plans = [s.scheduler()._plan for s in sessions]
    layouts = [
        [(u.fused, tuple(k[0] for k in u.keys)) for u in plan]
        for plan in plans
    ]
    assert layouts[0] == layouts[1]
    fused_units = [u for u in plans[0] if u.fused]
    assert len(fused_units) >= 2  # the 1200-byte cap split the f32 run
    cap = sessions[0].GROUP_BUCKET_BYTES
    for u in fused_units:
        if len(u.keys) > 1:
            assert sum(k[1] * 4 for k in u.keys) <= cap
    # registered order preserved across the fused units
    flat = [k[0] for u in fused_units for k in u.keys]
    assert flat == [f"pd:{i}" for i in range(len(_SIZES_F32))]
    singles = [u for u in plans[0] if not u.fused]
    assert [u.keys[0][0] for u in singles] == ["pd:i"]
    _close_all(sessions)


# ---------------------------------------------------------------------------
# np=4 kfrun smoke: the scheduler under the runtime lock-order detector
# ---------------------------------------------------------------------------

def test_scheduler_bench_smoke_np4_lockwatch():
    """ISSUE 10 acceptance: the async bench path at np=4 under
    KF_DEBUG_LOCKS=1 — real kfrun cluster, scheduler threads live, the
    OVERLAP report printed, and ZERO lock-order findings (the detector
    is proven live in workers by test_bench_host_smoke's positive
    control)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["KF_CONFIG_SEGMENT_MIN_BYTES"] = "0"
    env["KF_BENCH_MODEL"] = "tiny"
    env["KF_BENCH_ITERS"] = "3"
    env["KF_BENCH_ALGO"] = "segmented"
    env["KF_BENCH_ASYNC"] = "on"
    env["KF_DEBUG_LOCKS"] = "1"
    # startup legitimately holds singleton-init/dial locks for seconds
    # on a loaded box (see test_bench_host_smoke) — the walk itself must
    # stay clean far below this
    env["KF_DEBUG_LOCKS_HELD_MS"] = "10000"
    r = subprocess.run(
        [
            sys.executable, "-m", "kungfu_tpu.runner.cli",
            *kfrun_ports().args,  # this xdist worker's block
            "-np", "4", "-H", "127.0.0.1:4",
            sys.executable, AGENT,
        ],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO,
    )
    out = r.stdout + r.stderr
    assert r.returncode == 0, out
    assert "RESULT:" in r.stdout, out
    assert "OVERLAP" in r.stdout, out
    assert "lock_order_violation" not in out, out
    assert "lock_long_held" not in out, out
