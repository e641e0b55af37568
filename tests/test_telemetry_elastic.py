"""Elastic + collective telemetry integration (ISSUE 1 satellites):

- a schedule-driven elastic resize (StepBasedSchedule -> config server
  -> resize_cluster_from_url) emits exactly ONE audit record per peer
  with the correct old/new sizes;
- spans nest correctly across a simulated collective step;
- the acceptance run: a 4-peer cluster under KF_TELEMETRY=metrics,trace
  serves a Prometheus /metrics page with per-peer transport counters, a
  collective-latency histogram, a resize audit record, and a valid
  Chrome-trace JSON (ph/ts/dur) on /trace.
"""

import contextlib
import json
import threading
import urllib.request

import numpy as np
import pytest

from kungfu_tpu.base.strategy import Strategy
from kungfu_tpu.plan.peer import PeerID, PeerList
from kungfu_tpu.runner.env import WorkerConfig
from kungfu_tpu.telemetry import audit, config as tconfig, tracing


def _reserve_low_ports(n):
    """Free ports whose +10000 sibling is still a valid port (the
    telemetry endpoint binds peer_port + 10000)."""
    from kungfu_tpu.cmd import _reserve_ports

    out = []
    for _ in range(20):
        out += [p for p in _reserve_ports(n) if p + 10000 <= 65535]
        out = list(dict.fromkeys(out))
        if len(out) >= n:
            return out[:n]
    pytest.skip("could not reserve low ports")


def _make_peers(n, config_server="", strategy=Strategy.STAR):
    from kungfu_tpu.peer import Peer

    ids = [PeerID("127.0.0.1", p) for p in _reserve_low_ports(n)]
    peers = PeerList(ids)
    out = []
    for me in ids:
        out.append(
            Peer(
                WorkerConfig(
                    self_id=me,
                    peers=peers,
                    runners=PeerList(),
                    parent=None,
                    cluster_version=0,
                    strategy=strategy,
                    config_server=config_server,
                    elastic_mode="",
                    init_progress=0,
                )
            )
        )
    threads = [threading.Thread(target=p.start) for p in out]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
        assert not t.is_alive(), "peer start timed out"
    return out


def _par(fns, timeout=120):
    errs = []

    def run(fn):
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    ts = [threading.Thread(target=run, args=(f,)) for f in fns]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout)
        assert not t.is_alive(), "worker thread timed out"
    assert not errs, errs


@pytest.fixture
def telemetry_on(monkeypatch):
    monkeypatch.setenv("KF_TELEMETRY", "metrics,trace")
    tconfig.refresh()
    yield
    monkeypatch.delenv("KF_TELEMETRY", raising=False)
    tconfig.refresh()


@contextlib.contextmanager
def _resizable(n):
    """n in-process peers behind a config server that holds their cluster,
    and a stand-in runner (clusters must carry a runner per worker host to
    validate, and rank 0 notifies it of the accepted stage): yields
    (peers, notified messages)."""
    from kungfu_tpu.elastic.configserver import ConfigServer
    from kungfu_tpu.plan.cluster import Cluster
    from kungfu_tpu.transport.message import ConnType
    from kungfu_tpu.transport.server import Server

    (runner_port,) = _reserve_low_ports(1)
    runner_id = PeerID("127.0.0.1", runner_port)
    runner_srv = Server(runner_id, use_unix=False)
    notified = []
    runner_srv.register(ConnType.CONTROL, lambda src, msg: notified.append(msg))
    runner_srv.start()
    runners = PeerList([runner_id])

    peers = _make_peers(n)
    srv = ConfigServer(
        0,
        initial=Cluster(runners=runners, workers=peers[0].config.peers),
        host="127.0.0.1",
    )
    srv.start()
    for p in peers:
        p.config.config_server = f"http://127.0.0.1:{srv.port}"
        p.config.runners = runners
    audit.clear()
    tracing.clear()
    try:
        yield peers, notified
    finally:
        srv.stop()
        runner_srv.stop()
        for p in peers:
            p.stop()
        audit.clear()
        tracing.clear()


def _resize_spans(tid_of, version):
    """{thread: {span name: event}} of the `resize.*` spans of one resize."""
    out = {}
    for e in tracing.full_events("resize."):
        if (e.args or {}).get("version") == version and e.tid in tid_of:
            out.setdefault(tid_of[e.tid], {})[e.name] = e
    return out


def test_schedule_driven_resize_emits_one_audit_record(telemetry_on, monkeypatch):
    """The full elastic path — StepBasedSchedule proposes to the config
    server, every peer adopts via consensus — leaves exactly one audit
    record per surviving peer, with the old/new sizes and the
    config_server trigger; each phase is the duration of its span."""
    import kungfu_tpu.elastic.schedule as sched_mod
    from kungfu_tpu.elastic.schedule import StepBasedSchedule

    with _resizable(3) as (peers, notified):
        # drive the schedule from the acting rank 0 (the api module binds
        # to the process singleton, which in-process multi-peer tests
        # don't use — bind its accessors to peer 0 instead)
        monkeypatch.setattr(sched_mod.api, "current_rank", lambda: peers[0].rank)
        monkeypatch.setattr(sched_mod.api, "cluster_size", lambda: peers[0].size)
        monkeypatch.setattr(
            sched_mod.api, "propose_new_size", peers[0].propose_new_size
        )
        sched = StepBasedSchedule("2:100")
        assert sched.maybe_propose(0) == 2  # published to the config server

        results, tid_of = {}, {}

        def resize(i, p):
            tid_of[threading.get_ident()] = i
            results[i] = p.resize_cluster_from_url()

        _par([lambda i=i, p=p: resize(i, p) for i, p in enumerate(peers)])
        assert results[0] == (True, False)
        assert results[1] == (True, False)
        assert results[2] == (True, True)  # shrunk out

        spans = _resize_spans(tid_of, version=1)
        for i, p in enumerate(peers):
            recs = audit.records(kind="resize", peer=str(p.self_id))
            assert len(recs) == 1, (i, [r.to_json() for r in recs])
            (rec,) = recs
            assert rec.old_size == 3
            assert rec.new_size == 2
            assert rec.trigger == "config_server"
            assert rec.detached == (i == 2)
            assert rec.cluster_version == 1
            assert rec.phases_ms and "update_ms" in rec.phases_ms
            # one clock a phase: the span is the measurement
            want = {"resize.wait_config", "resize.consensus", "resize.update"}
            assert set(spans[i]) == want | ({"resize.notify"} if i == 0 else set())
            for name, e in spans[i].items():
                assert e.args == {"mode": "delta", "version": 1,
                                  "old_size": 3, "new_size": 2}, (name, e.args)
            assert p.last_resize_phases == rec.phases_ms == {
                name.split(".")[1] + "_ms": round(e.duration * 1e3, 1)
                for name, e in spans[i].items()
            }
        assert [m.name for m in notified] == ["update"]  # rank 0 told the runner
        # a second no-change poll must NOT add records
        _par([lambda p=p: p.resize_cluster_from_url() for p in peers[:2]])
        assert len(audit.records(kind="resize")) == 3


def test_reload_agreement_runs_under_the_delta_paths_span_names(telemetry_on):
    """`change_cluster`: the wait, the agreement, the hook and the notify
    each under a span that says which resize it belongs to; the phases are
    the spans' durations, the audit record has them, and the Stage carries
    the proposer's marks to the runner."""
    import time

    with _resizable(3) as (peers, notified):
        # nothing to change yet: one wait, no agreement, nothing recorded
        assert peers[0].propose_new_size(3) is None
        _par([lambda p=p: p.change_cluster(7) for p in peers])
        assert {e.name for e in tracing.full_events("resize.")} == {
            "resize.wait_config"}
        assert not audit.records(kind="resize") and not notified
        tracing.clear()

        peers[0].propose_new_size(2)
        results, tid_of, hooked = {}, {}, []
        t_before = time.time()

        def reload(i, p):
            tid_of[threading.get_ident()] = i
            results[i] = p.change_cluster(
                8, before_notify=lambda: hooked.append((i, len(notified))))

        _par([lambda i=i, p=p: reload(i, p) for i, p in enumerate(peers)])
        assert results == {i: (True, True) for i in range(3)}
        # the hook ran on every worker, and before the runner heard
        assert sorted(hooked) == [(0, 0), (1, 0), (2, 0)]

        spans = _resize_spans(tid_of, version=1)
        for i, p in enumerate(peers):
            want = ["resize.wait_config", "resize.consensus", "resize.on_reload"]
            assert sorted(spans[i]) == sorted(want + ["resize.notify"] * (i == 0))
            for name, e in spans[i].items():
                assert e.args == {"mode": "reload", "version": 1,
                                  "old_size": 3, "new_size": 2}, (name, e.args)
            phases = {name.split(".")[1] + "_ms": round(e.duration * 1e3, 1)
                      for name, e in spans[i].items()}
            assert p.last_resize_phases == phases
            (rec,) = audit.records(kind="resize", peer=str(p.self_id))
            assert rec.trigger == "reload" and rec.detached
            assert rec.phases_ms == phases
            assert (rec.old_size, rec.new_size, rec.progress) == (3, 2, 8)

        (msg,) = notified
        stage = json.loads(msg.data.decode())
        assert stage["Reload"] and stage["Version"] == 1 and stage["Progress"] == 8
        marks = stage["Marks"]
        assert t_before <= marks["t_propose"] <= time.time()
        assert (marks["mode"], marks["old_size"]) == ("reload", 3)
        # what rank 0 knew when it sent the Stage: all but the notify
        assert marks["phases_ms"] == {
            k: v for k, v in peers[0].last_resize_phases.items()
            if k != "notify_ms"}
        assert "ChipCoords" not in stage  # no device plane told of any


def test_spans_nest_across_collective_step(telemetry_on):
    """A simulated training step: collective spans recorded on the
    calling thread sit UNDER the step span (depth + containment), and
    the walk/transport spans land in the same buffer."""
    from kungfu_tpu.base.ops import ReduceOp
    from kungfu_tpu.base.workspace import Workspace

    peers = _make_peers(2)
    tracing.clear()
    try:
        def step(p):
            with tracing.span("train_step", rank=p.rank):
                x = np.ones(512, np.float32)
                o = np.empty_like(x)
                p.current_session().all_reduce(
                    Workspace(x, o, ReduceOp.SUM, "t_nest")
                )
                assert o[0] == 2.0

        _par([lambda p=p: step(p) for p in peers])
        evs = tracing.full_events()
        steps = [e for e in evs if e.name == "train_step"]
        colls = [e for e in evs if e.name == "collective.all_reduce"]
        assert len(steps) == 2 and len(colls) >= 2
        for c in colls:
            # each collective span nests inside the step span of its thread
            parent = next(s for s in steps if s.tid == c.tid)
            assert c.depth == parent.depth + 1
            assert parent.start <= c.start
            assert c.start + c.duration <= parent.start + parent.duration + 1e-9
            assert c.args["bytes"] == 512 * 4
        # the engine's own spans (graph walk) recorded below
        assert any(e.name.startswith("host.walk") for e in evs)
    finally:
        for p in peers:
            p.stop()


def test_four_peer_acceptance_metrics_trace_audit(telemetry_on):
    """ISSUE 1 acceptance: 4 simulated peers, KF_TELEMETRY=metrics,trace
    -> /metrics has per-peer transport counters + a collective-latency
    histogram + a resize audit record, /trace is Chrome-trace JSON."""
    from kungfu_tpu.base.ops import ReduceOp
    from kungfu_tpu.base.workspace import Workspace

    peers = _make_peers(4)
    audit.clear()
    try:
        def reduce_on(p):
            x = np.ones(2048, np.float32)
            o = np.empty_like(x)
            p.current_session().all_reduce(
                Workspace(x, o, ReduceOp.SUM, "t_acc")
            )
            assert o[0] == 4.0

        _par([lambda p=p: reduce_on(p) for p in peers])
        _par([lambda p=p: p.resize_cluster(3) for p in peers])

        srv = peers[0].metrics_server
        assert srv is not None, "per-worker telemetry endpoint missing"
        base = f"http://127.0.0.1:{srv.port}"
        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            body = r.read().decode()
        # per-peer transport counters
        assert 'kungfu_egress_bytes_total{peer="' in body
        assert 'kungfu_ingress_bytes_total{peer="' in body
        # >= 1 collective-latency histogram
        assert 'kungfu_collective_latency_seconds_bucket{collective="all_reduce"' in body
        assert "kungfu_collective_latency_seconds_count" in body
        # >= 1 resize audit record, also visible as the resize counter
        # (value unchecked: the registry is process-global across tests)
        assert 'kungfu_resize_total{trigger="explicit"}' in body
        assert len(audit.records(kind="resize")) == 4  # one per in-process peer

        with urllib.request.urlopen(base + "/trace", timeout=10) as r:
            doc = json.loads(r.read().decode())
        evs = doc["traceEvents"]
        complete = [e for e in evs if e["ph"] == "X"]
        assert complete, "no complete events in the Chrome trace"
        for e in complete:
            assert "ts" in e and "dur" in e
        assert any(e["name"] == "collective.all_reduce" for e in complete)

        with urllib.request.urlopen(base + "/audit", timeout=10) as r:
            au = json.loads(r.read().decode())
        assert any(
            a["kind"] == "resize" and a["old_size"] == 4 and a["new_size"] == 3
            for a in au
        )
    finally:
        for p in peers:
            p.stop()
        audit.clear()
