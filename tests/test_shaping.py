"""Shaped-link transport harness (ISSUE 14 tentpole, part c) + the
tier-1 k=32 two-host DCN smoke.

Covers: KF_SHAPE_LINKS grammar (entries, wildcard dst, src filtering,
rate suffixes, malformed specs warn-and-disable rather than silently
dropping the shape), the token-bucket pacing math under a fake clock,
deterministic jitter (LCG, no RNG — identical across reruns), the
deprecated KF_TEST_SLOW_EDGE alias (warns but keeps injecting), live
Client integration (the shaped delay lands inside the timed send window
so the link table's passive bandwidth estimate converges to the shaped
rate), and the acceptance smoke: a k=32 in-process cluster under a
two-host DCN shape (interleaved host assignment — the naive ring's
worst case) whose MEASURED matrix reflects the shape, whose lockstep
re-plan adopts a ring with exactly 2 cross-host crossings (vs 32
naive), and whose post-adoption walks stay exact.
"""

import threading

import numpy as np
import pytest

from kungfu_tpu.base.ops import ReduceOp
from kungfu_tpu.base.strategy import Strategy
from kungfu_tpu.base.workspace import Workspace
from kungfu_tpu.collective.host_session import HostSession
from kungfu_tpu.telemetry import link as tlink
from kungfu_tpu.transport import shaping


# ---------------------------------------------------------------------------
# spec parsing
# ---------------------------------------------------------------------------

def test_parse_entry_kinds():
    shapes = shaping.parse_spec(
        "a:1>b:2=lat:30;b:2=bw:8MiB,jitter:2;*=lat:1", "a:1"
    )
    assert set(shapes) == {"b:2", "*"}
    assert shapes["b:2"].bw_bps == 8 << 20
    assert shapes["b:2"].jitter_s == pytest.approx(0.002)
    assert shapes["*"].lat_s == pytest.approx(0.001)


def test_parse_src_filter():
    spec = "a:1>b:2=lat:30;c:3>b:2=lat:50"
    assert shaping.parse_spec(spec, "a:1")["b:2"].lat_s == pytest.approx(0.030)
    assert shaping.parse_spec(spec, "c:3")["b:2"].lat_s == pytest.approx(0.050)
    assert shaping.parse_spec(spec, "d:4") == {}
    # '*' src applies everywhere
    assert shaping.parse_spec("*>b:2=lat:10", "zz:9")["b:2"].lat_s \
        == pytest.approx(0.010)


def test_parse_rates():
    assert shaping._parse_rate("20MiB") == 20 << 20
    assert shaping._parse_rate("20mibps") == 20 << 20
    assert shaping._parse_rate("5kb") == 5000
    assert shaping._parse_rate("1.5GiB") == 1.5 * (1 << 30)
    assert shaping._parse_rate("123456") == 123456.0


@pytest.mark.parametrize("bad", [
    "b:2",                # no '='
    "=lat:30",            # no dst
    "b:2=lat",            # param without value separator
    "b:2=speed:9",        # unknown key
    "b:2=lat:-3",         # negative
    "b:2=bw:fast",        # unparseable rate
])
def test_parse_malformed_raises(bad):
    with pytest.raises(ValueError):
        shaping.parse_spec(bad, "a:1")


def test_from_env_malformed_warns_and_disables(monkeypatch):
    monkeypatch.setenv("KF_SHAPE_LINKS", "b:2=speed:9")
    assert shaping.from_env("a:1") is None
    monkeypatch.setenv("KF_SHAPE_LINKS", "")
    assert shaping.from_env("a:1") is None


def test_slow_edge_alias_still_injects(monkeypatch):
    """The DEPRECATED KF_TEST_SLOW_EDGE keeps working as a lat-only
    shape (a stale e2e env must not silently stop injecting)."""
    monkeypatch.delenv("KF_SHAPE_LINKS", raising=False)
    monkeypatch.setenv("KF_TEST_SLOW_EDGE", "a:1>b:2=40")
    shaper = shaping.from_env("a:1")
    assert shaper is not None
    assert shaper.shape_for("b:2").lat_s == pytest.approx(0.040)
    assert shaping.from_env("zz:9") is None  # src filter still applies
    # malformed legacy value: warns, injects nothing, never raises
    monkeypatch.setenv("KF_TEST_SLOW_EDGE", "nonsense")
    assert shaping.from_env("a:1") is None
    # both knobs set: entries merge (the alias rides along)
    monkeypatch.setenv("KF_TEST_SLOW_EDGE", "b:2=40")
    monkeypatch.setenv("KF_SHAPE_LINKS", "c:3=lat:5")
    shaper = shaping.from_env("a:1")
    assert shaper.shape_for("b:2").lat_s == pytest.approx(0.040)
    assert shaper.shape_for("c:3").lat_s == pytest.approx(0.005)


# ---------------------------------------------------------------------------
# pacing math
# ---------------------------------------------------------------------------

def test_token_bucket_converges_to_rate():
    """Under a fake clock, a steady stream of sends is paced so that
    total delay ≈ bytes/rate once the initial burst is spent."""
    now = [0.0]
    shaper = shaping.LinkShaper(
        {"d": shaping.EdgeShape(bw_bps=1 << 20)}, clock=lambda: now[0]
    )
    sent = 0
    slept = 0.0
    for _ in range(50):
        d = shaper.delay("d", 256 << 10)
        slept += d
        now[0] += d + 0.001  # the real send itself is fast
        sent += 256 << 10
    # effective rate within 15% of the shaped 1 MiB/s
    assert sent / (now[0]) == pytest.approx(1 << 20, rel=0.15)


def test_latency_and_burst():
    now = [0.0]
    shaper = shaping.LinkShaper(
        {"d": shaping.EdgeShape(lat_s=0.010, bw_bps=1 << 20)},
        clock=lambda: now[0],
    )
    # first small send: within the burst, latency only
    assert shaper.delay("d", 1024) == pytest.approx(0.010)
    # unshaped destination: zero
    assert shaper.delay("other", 1 << 20) == 0.0
    # latency() never pays pacing
    assert shaper.latency("d") == pytest.approx(0.010)


def test_jitter_deterministic():
    mk = lambda: shaping.LinkShaper(
        {"d": shaping.EdgeShape(jitter_s=0.010)}, clock=lambda: 0.0
    )
    a, b = mk(), mk()
    seq_a = [a.delay("d", 1) for _ in range(16)]
    seq_b = [b.delay("d", 1) for _ in range(16)]
    assert seq_a == seq_b  # identical across instances/reruns
    assert len(set(seq_a)) > 1  # but actually jittering
    assert all(0.0 <= d <= 0.010 for d in seq_a)


# ---------------------------------------------------------------------------
# live transport integration + the k=32 two-host DCN smoke
# ---------------------------------------------------------------------------

def _run_on_all(fns, join=180):
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


def _vote(sessions) -> list:
    """One lockstep re-plan round on every peer: what each adopted, or
    None."""
    results = {}
    _run_on_all([
        lambda r=r, s=s: results.__setitem__(
            r, s.check_replan(want=True, min_gain=1.0))
        for r, s in enumerate(sessions)
    ], join=240)
    return [results[r] for r in range(len(sessions))]


def _host_of(rank: int) -> int:
    """Interleaved two-'host' assignment — the naive ring's worst case
    (every rank-order hop crosses the DCN)."""
    return rank % 2


def _dcn_spec(ids) -> str:
    """Shape every cross-host directed edge: DCN-ish latency + bandwidth
    (intra-host edges stay unshaped loopback — orders of magnitude
    faster, like shm vs a real DCN)."""
    entries = []
    for i, src in enumerate(ids):
        for j, dst in enumerate(ids):
            if i != j and _host_of(i) != _host_of(j):
                entries.append(f"{src}>{dst}=lat:1,bw:16MiB")
    return ";".join(entries)


def _crossings(order) -> int:
    k = len(order)
    return sum(
        1 for i in range(k)
        if _host_of(order[i]) != _host_of(order[(i + 1) % k])
    )


def test_k32_shaped_smoke(monkeypatch):
    """ISSUE 14 acceptance smoke (fast, tier-1): k=32 on one box under
    a two-host DCN shape — the measured matrix reflects the shape, the
    lockstep re-plan vote adopts a host-grouped ring (2 crossings vs 32
    naive), and the reordered walk stays exact."""
    from kungfu_tpu.cmd import _reserve_ports
    from kungfu_tpu.peer import Peer
    from kungfu_tpu.plan.peer import PeerID, PeerList
    from kungfu_tpu.runner.env import WorkerConfig

    k = 32
    ports = _reserve_ports(k)
    ids = [PeerID("127.0.0.1", p) for p in ports]
    labels = [str(i) for i in ids]
    monkeypatch.setenv("KF_SHAPE_LINKS", _dcn_spec(labels))
    monkeypatch.setenv("KF_CONFIG_SHM", "0")  # DCN-like: sockets only
    monkeypatch.setattr(HostSession, "SEGMENT_MIN_BYTES", 0)
    peers = PeerList(ids)
    cluster = [
        Peer(WorkerConfig(
            self_id=me, peers=peers, runners=PeerList(), parent=None,
            cluster_version=0, strategy=Strategy.STAR, config_server="",
            elastic_mode="", init_progress=0,
        ))
        for me in ids
    ]
    try:
        _run_on_all([p.start for p in cluster], join=240)
        # per-PEER link tables (the process singleton would blend every
        # in-process worker's row into one): assign after construction —
        # Client.send and the session read the handle per call. The low
        # bw gate lets ~16 KiB segment sends feed the estimator.
        tables = [
            tlink.LinkTable(registry=None, bw_min_bytes=1024)
            for _ in range(k)
        ]
        for p, t in zip(cluster, tables):
            p.client._links = t
        sessions = [
            HostSession(Strategy.RING_SEGMENTED, p.self_id, peers,
                        p.client, p.collective, timeout=120.0)
            for p in cluster
        ]
        for s, t in zip(sessions, tables):
            s._links = t
            s.replan_mode = "auto"

        def walk(r, sess, tag, rounds=2, n=128 * 1024):
            for i in range(rounds):
                x = np.full(n, np.float32(r + 1))
                out = np.empty_like(x)
                sess.all_reduce(Workspace(
                    send=x, recv=out, op=ReduceOp.SUM, name=f"{tag}:{i}",
                ))
                assert out[0] == k * (k + 1) / 2

        # a couple of naive-ring rounds feed the estimators over the
        # ring edges (every one cross-host under the interleaved
        # assignment), exercising the shaped segmented walk end to end
        _run_on_all([
            lambda r=r, s=s: walk(r, s, "shape-feed")
            for r, s in enumerate(sessions)
        ], join=240)

        # ... and an all-edge probe burst stands in for the broader
        # traffic mix of a real run (gather/broadcast/state-sync cross
        # many edges over time): 2 frames per directed edge — the first
        # send to a fresh peer dials and is excluded as a bw sample —
        # so EVERY edge gets a measured estimate, intra-host at loopback
        # speed, cross-host at the shaped rate
        from kungfu_tpu.transport.message import ConnType

        payload = bytes(16 << 10)

        def probe(r, burst):
            me = cluster[r]
            for j in range(k):
                if j == r:
                    continue
                for t in range(2):
                    me.client.send(
                        ids[j], f"probe:{burst}:{r}:{j}:{t}", payload,
                        ConnType.COLLECTIVE,
                    )
            for j in range(k):
                if j == r:
                    continue
                for t in range(2):
                    msg = me.collective.recv(
                        ids[j], f"probe:{burst}:{j}:{r}:{t}", 60.0)
                    if msg.release is not None:
                        msg.release()

        def matrix():
            cross, intra = [], []
            for i in range(k):
                for j in range(k):
                    if i == j:
                        continue
                    bw = tables[i].bandwidth(ids[j])
                    assert bw is not None, f"no estimate on edge {i}->{j}"
                    (cross if _host_of(i) != _host_of(j) else intra).append(bw)
            return cross, intra

        def shows_the_shape(cross, intra):
            return ((16 << 20) / 8 < np.median(cross) < (16 << 20) * 1.7
                    and np.median(intra) > 4 * np.median(cross))

        # -- the measured matrix reflects the shape, and the lockstep ------
        # -- re-plan fires and adopts a host-grouped ring ------------------
        # An estimate is a moving average of timed sends and the predicted
        # gain is clamped by the busiest peer's CPU share: on a machine
        # that other work loads, one burst of probes and one vote can fall
        # short (a depressed estimate, a ring with a stray crossing, no
        # majority for a clamped gain). What is asserted is that measuring
        # and voting get there, in a few rounds at most; a vote with
        # nothing left to win adopts nothing, so the last adoption stands.
        plans = None
        for burst in range(10):
            _run_on_all([lambda r=r: probe(r, burst) for r in range(k)],
                        join=240)
            cross, intra = matrix()
            voted = _vote(sessions)
            if all(p is not None for p in voted):
                plans = voted
            if (plans is not None and shows_the_shape(cross, intra)
                    and _crossings(plans[0].order) == 2):
                break
        # cross-host edges pace at the shaped 16 MiB/s; intra-host stays
        # loopback-fast — the separation the optimizer needs. The upper
        # bound proves the shape applied (unshaped loopback measures
        # orders of magnitude higher); the lower bound is loose because
        # scheduling noise adds to the timed send window, honestly
        # depressing the estimate.
        assert np.median(cross) < (16 << 20) * 1.7
        assert np.median(cross) > (16 << 20) / 8
        assert np.median(intra) > 4 * np.median(cross)
        assert plans is not None, "re-plan did not fire"
        assert len({p.to_bytes() for p in plans}) == 1
        order = plans[0].order
        assert sorted(order) == list(range(k))
        assert _crossings(order) == 2, (
            f"expected a host-grouped ring (2 crossings), got "
            f"{_crossings(order)}: {order}"
        )
        assert _crossings(range(k)) == k  # what the naive ring paid

        # -- the reordered walk is live and exact -------------------------
        _run_on_all([
            lambda r=r, s=s: walk(r, s, "post-replan", rounds=1)
            for r, s in enumerate(sessions)
        ], join=240)
    finally:
        for p in cluster:
            p.stop()


# ---------------------------------------------------------------------------
# shared-uplink bucket (ISSUE 19 tentpole, part c)
# ---------------------------------------------------------------------------

def test_parse_uplinks_grammar_and_membership(tmp_path, monkeypatch):
    monkeypatch.setenv("KF_TELEMETRY_DIR", str(tmp_path))
    spec = "uplink:hostA=bw:16MiB;a:1>b:2=lat:3;uplink:c:3|c:4=bw:8MiB"
    # edge entries and uplink entries split cleanly
    shapes = shaping.parse_spec(spec, "a:1")
    assert set(shapes) == {"b:2"}
    # bare-hostname form covers every sender on the host
    ups = shaping.parse_uplinks(spec, "hostA:9000", make_bucket=False)
    assert [u.token for u in ups] == ["hostA"]
    assert ups[0].crosses("hostB:1") and not ups[0].crosses("hostA:2")
    # member-list form (the in-process harness): exact peer specs
    ups = shaping.parse_uplinks(spec, "c:4", make_bucket=False)
    assert [u.token for u in ups] == ["c:3|c:4"]
    assert ups[0].crosses("d:9") and not ups[0].crosses("c:3")
    # non-members see no uplink
    assert shaping.parse_uplinks(spec, "d:9", make_bucket=False) == []
    # canonical identity is member-order independent (same bucket file)
    a = shaping.Uplink("c:3|c:4", 8 << 20)
    b = shaping.Uplink("c:4|c:3", 8 << 20)
    assert a.canonical() == b.canonical()


@pytest.mark.parametrize("bad", [
    "uplink:=bw:8MiB",        # no host
    "uplink:hostA",           # no params
    "uplink:hostA=lat:3",     # uplinks are bandwidth-only
    "uplink:hostA=bw:0",      # zero rate shapes nothing = operator error
    "uplink:hostA=bw:fast",   # unparseable rate
])
def test_parse_uplinks_malformed_raises(bad):
    with pytest.raises(ValueError):
        shaping.parse_uplinks(bad, "hostA:1", make_bucket=False)


def test_from_env_malformed_uplink_warns_and_disables(monkeypatch):
    warned = []
    from kungfu_tpu.telemetry import log as tlog
    monkeypatch.setattr(tlog, "warn",
                        lambda msg, *a: warned.append(msg % a if a else msg))
    monkeypatch.setenv("KF_SHAPE_LINKS", "uplink:hostA=lat:3")
    assert shaping.from_env("hostA:1") is None
    assert any("uplink" in w for w in warned)


def test_slow_edge_host_spec_suggests_uplink(monkeypatch):
    """DEPRECATION (ISSUE 19 satellite): a KF_TEST_SLOW_EDGE naming a
    bare HOST matches no host:port destination — warn with the
    uplink: syntax the intent actually wants."""
    warned = []
    from kungfu_tpu.telemetry import log as tlog
    monkeypatch.setattr(tlog, "warn",
                        lambda msg, *a: warned.append(msg % a if a else msg))
    monkeypatch.delenv("KF_SHAPE_LINKS", raising=False)
    monkeypatch.setenv("KF_TEST_SLOW_EDGE", "hostB=40")
    shaping.from_env("a:1")
    assert any("uplink:hostB=bw:" in w for w in warned)
    # a proper host:port spec does NOT trigger the host warning
    warned.clear()
    monkeypatch.setenv("KF_TEST_SLOW_EDGE", "b:2=40")
    shaping.from_env("a:1")
    assert not any("uplink:" in w for w in warned)


def test_shared_bucket_drains_across_instances(tmp_path):
    """Two SharedBuckets on the same file = two processes on one host:
    bytes sent by either drain the ONE pool (per-edge buckets would
    give each sender its own full rate)."""
    now = [0.0]
    rate = 1 << 20
    path = str(tmp_path / "bucket")
    b1 = shaping.SharedBucket(path, rate, clock=lambda: now[0])
    b2 = shaping.SharedBucket(path, rate, clock=lambda: now[0])
    try:
        sent, slept = 0, 0.0
        for i in range(50):
            d = (b1 if i % 2 else b2).delay(256 << 10)
            slept += d
            now[0] += d + 0.001
            sent += 256 << 10
        # the COMBINED stream paces at the shared rate
        assert sent / now[0] == pytest.approx(rate, rel=0.15)
        # an isolated per-sender pair would have paced at ~2x
        assert slept > 0.5 * sent / rate
    finally:
        b1.close()
        b2.close()


def test_linkshaper_uplink_only_is_active(tmp_path, monkeypatch):
    monkeypatch.setenv("KF_TELEMETRY_DIR", str(tmp_path))
    monkeypatch.delenv("KF_TEST_SLOW_EDGE", raising=False)
    monkeypatch.setenv("KF_SHAPE_LINKS", "uplink:h1=bw:1MiB")
    shaper = shaping.from_env("h1:1")
    assert shaper is not None and bool(shaper)
    # intra-host send: free; cross-host: drains the bucket (burst
    # first, then paced)
    assert shaper.delay("h1:2", 1 << 20) == 0.0
    total = sum(shaper.delay("h2:9", 256 << 10) for _ in range(12))
    assert total > 0.0


def _hier_host_of(rank: int) -> int:
    return rank % 4


def _hier_groups(labels):
    groups = {}
    for i, lab in enumerate(labels):
        groups.setdefault(_hier_host_of(i), []).append(lab)
    return [groups[h] for h in sorted(groups)]


def _hier_spec(labels) -> str:
    """Four virtual hosts: per-edge DCN latency/bw on cross-host edges
    (what the matrix measures and clusters on) + ONE shared uplink
    bucket per host (what the two-level plan wins against)."""
    entries = []
    for i, src in enumerate(labels):
        for j, dst in enumerate(labels):
            if i != j and _hier_host_of(i) != _hier_host_of(j):
                entries.append(f"{src}>{dst}=lat:1,bw:16MiB")
    for grp in _hier_groups(labels):
        entries.append(f"uplink:{'|'.join(grp)}=bw:64MiB")
    return ";".join(entries)


def test_k32_hier_adoption_smoke(monkeypatch, tmp_path):
    """ISSUE 19 tier-1 smoke: k=32 on one box under a 4-host shape with
    SHARED per-host uplinks — the lockstep hier vote adopts a two-level
    plan (measured clustering recovers the 4 hosts, one head each) and
    the two-level walk stays exact under the shape. Budget-bounded like
    the flat k=32 smoke above."""
    from kungfu_tpu.cmd import _reserve_ports
    from kungfu_tpu.peer import Peer
    from kungfu_tpu.plan.peer import PeerID, PeerList
    from kungfu_tpu.runner.env import WorkerConfig

    k = 32
    ports = _reserve_ports(k)
    ids = [PeerID("127.0.0.1", p) for p in ports]
    labels = [str(i) for i in ids]
    monkeypatch.setenv("KF_TELEMETRY_DIR", str(tmp_path))
    monkeypatch.setenv("KF_SHAPE_LINKS", _hier_spec(labels))
    monkeypatch.setenv("KF_CONFIG_SHM", "0")
    monkeypatch.setattr(HostSession, "SEGMENT_MIN_BYTES", 0)
    peers = PeerList(ids)
    cluster = [
        Peer(WorkerConfig(
            self_id=me, peers=peers, runners=PeerList(), parent=None,
            cluster_version=0, strategy=Strategy.STAR, config_server="",
            elastic_mode="", init_progress=0,
        ))
        for me in ids
    ]
    try:
        _run_on_all([p.start for p in cluster], join=240)
        tables = [
            tlink.LinkTable(registry=None, bw_min_bytes=1024)
            for _ in range(k)
        ]
        for p, t in zip(cluster, tables):
            p.client._links = t
        sessions = [
            HostSession(Strategy.RING_SEGMENTED, p.self_id, peers,
                        p.client, p.collective, timeout=120.0)
            for p in cluster
        ]
        for s, t in zip(sessions, tables):
            s._links = t
            s.replan_mode = "hier"

        def walk(r, sess, tag, rounds=2, n=64 * 1024):
            for i in range(rounds):
                x = np.full(n, np.float32(r + 1))
                out = np.empty_like(x)
                sess.all_reduce(Workspace(
                    send=x, recv=out, op=ReduceOp.SUM, name=f"{tag}:{i}",
                ))
                assert out[0] == k * (k + 1) / 2

        _run_on_all([
            lambda r=r, s=s: walk(r, s, "hier-feed")
            for r, s in enumerate(sessions)
        ], join=240)

        from kungfu_tpu.transport.message import ConnType

        payload = bytes(16 << 10)

        def probe(r, burst):
            me = cluster[r]
            for j in range(k):
                if j == r:
                    continue
                for t in range(2):
                    me.client.send(
                        ids[j], f"hprobe:{burst}:{r}:{j}:{t}", payload,
                        ConnType.COLLECTIVE,
                    )
            for j in range(k):
                if j == r:
                    continue
                for t in range(2):
                    msg = me.collective.recv(
                        ids[j], f"hprobe:{burst}:{j}:{r}:{t}", 60.0)
                    if msg.release is not None:
                        msg.release()

        hosts = [sorted(r for r in range(k) if _hier_host_of(r) == hh)
                 for hh in range(4)]

        # -- the lockstep hier vote adopts a two-level plan ---------------
        # measured under whatever else loads the machine (the flat smoke
        # above has why): probe and vote until the adopted hierarchy is
        # the four shaped hosts, a few rounds at most
        fired = False
        for burst in range(10):
            _run_on_all([lambda r=r: probe(r, burst) for r in range(k)],
                        join=240)
            voted = _vote(sessions)
            fired = fired or all(p is not None for p in voted)
            hiers = [s.hier_plan() for s in sessions]
            if fired and all(h is not None and sorted(
                    sorted(g) for g in h.groups) == hosts for h in hiers):
                break
        assert fired, "hier re-plan did not fire"
        assert all(h is not None for h in hiers)
        assert len({h.to_bytes() for h in hiers}) == 1
        h = hiers[0]
        # measured clustering recovered the 4 shaped hosts
        assert len(h.groups) == 4
        assert sorted(sorted(g) for g in h.groups) == hosts
        for g, head in zip(h.groups, h.heads):
            assert head == g[0]
            assert len({_hier_host_of(r) for r in g}) == 1

        # -- the adopted two-level walk is live and exact -----------------
        _run_on_all([
            lambda r=r, s=s: walk(r, s, "post-hier", rounds=1)
            for r, s in enumerate(sessions)
        ], join=240)
    finally:
        for p in cluster:
            p.stop()
