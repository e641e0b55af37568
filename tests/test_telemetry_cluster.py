"""Cluster observability plane (ISSUE 2): runner-side aggregation,
merged views, straggler detection feeding adaptation.

- promparse: exposition parsing + federation merge (peer labels,
  exported_* collision rule);
- StragglerScorer: robust-z flagging on synthetic skewed step times;
- TelemetryAggregator: scrape/merge against in-process
  TelemetryServers, clock-offset alignment, trace merge;
- /cluster/* endpoints on the watcher's DebugServer;
- `info top` one-shot rendering;
- acceptance: a 4-peer cluster with one artificially delayed peer is
  flagged within two scrape intervals, emits an audit event, and the
  signal lands in PolicyContext.metrics.
"""

import json
import math
import time
import urllib.request

import pytest

from kungfu_tpu.telemetry import audit, metrics
from kungfu_tpu.telemetry import cluster as tcluster
from kungfu_tpu.telemetry import promparse
from kungfu_tpu.telemetry.http import TelemetryServer
from kungfu_tpu.telemetry.straggler import StragglerScorer


# ---------------------------------------------------------------------------
# promparse
# ---------------------------------------------------------------------------

class TestPromparse:
    def test_parse_basic_and_labels(self):
        text = (
            "# HELP kf_x_total help text\n"
            "# TYPE kf_x_total counter\n"
            "kf_x_total 3\n"
            'kf_y_bytes{peer="h:1",kind="a b"} 1.5\n'
            'kf_z{esc="q\\"uo\\\\te\\nnl"} +Inf\n'
        )
        samples = promparse.parse_text(text)
        assert promparse.sample_value(samples, "kf_x_total") == 3
        assert promparse.sample_value(samples, "kf_y_bytes", peer="h:1") == 1.5
        z = [s for s in samples if s.name == "kf_z"][0]
        assert z.labels_dict()["esc"] == 'q"uo\\te\nnl'
        assert z.value == math.inf

    def test_parse_skips_garbage(self):
        assert promparse.parse_text("not a line\n# comment\n\n") == []

    def test_inject_label_collision_rule(self):
        s = promparse.parse_line('kf_egress_bytes_total{peer="h:2"} 9')
        out = promparse.inject_label(s, "peer", "h:1")
        d = out.labels_dict()
        assert d["peer"] == "h:1"
        assert d["exported_peer"] == "h:2"

    def test_merge_expositions_groups_families(self):
        page_a = (
            "# TYPE kf_steps_total counter\nkf_steps_total 10\n"
            "# TYPE kf_g gauge\nkf_g 1\n"
        )
        page_b = "# TYPE kf_steps_total counter\nkf_steps_total 20\n"
        merged = promparse.merge_expositions([("w0", page_a), ("w1", page_b)])
        assert merged.count("# TYPE kf_steps_total counter") == 1
        assert 'kf_steps_total{peer="w0"} 10' in merged
        assert 'kf_steps_total{peer="w1"} 20' in merged
        # family samples are consecutive: w1's sample precedes kf_g's TYPE
        assert merged.index('kf_steps_total{peer="w1"}') < merged.index(
            "# TYPE kf_g"
        )

    def test_merge_roundtrips_registry_render(self):
        reg = metrics.Registry()
        reg.counter("kf_m_total", "m", ("peer",)).labels("remote:9").inc(4)
        reg.histogram("kf_h_seconds", "h", buckets=(0.1, 1.0)).observe(0.5)
        merged = promparse.merge_expositions([("w0", reg.render())])
        samples = promparse.parse_text(merged)
        assert promparse.sample_value(
            samples, "kf_m_total", peer="w0", exported_peer="remote:9"
        ) == 4
        assert promparse.sample_value(
            samples, "kf_h_seconds_count", peer="w0"
        ) == 1


# ---------------------------------------------------------------------------
# straggler scorer
# ---------------------------------------------------------------------------

class TestStragglerScorer:
    def feed(self, scorer, series, rounds=4):
        for _ in range(rounds):
            for peer, v in series.items():
                scorer.observe(peer, v)

    def test_homogeneous_cluster_stays_quiet(self):
        s = StragglerScorer()
        self.feed(s, {f"w{i}": 0.05 + 0.0001 * i for i in range(8)})
        assert s.stragglers() == []
        assert all(not ps.flagged for ps in s.scores().values())

    def test_slow_peer_flagged(self):
        s = StragglerScorer()
        self.feed(s, {"w0": 0.05, "w1": 0.051, "w2": 0.049, "w3": 0.5})
        assert s.stragglers() == ["w3"]
        scores = s.scores()
        assert scores["w3"].score >= s.z_threshold
        assert scores["w0"].flagged is False
        assert s.skew() == pytest.approx(10.0, rel=0.1)

    def test_fast_outlier_not_flagged(self):
        # stragglers are SLOW peers; an unusually fast peer is not one
        s = StragglerScorer()
        self.feed(s, {"w0": 0.05, "w1": 0.05, "w2": 0.05, "w3": 0.001})
        assert s.stragglers() == []

    def test_min_peers_guard(self):
        s = StragglerScorer(min_peers=3)
        self.feed(s, {"w0": 0.05, "w1": 5.0})
        assert s.stragglers() == []

    def test_recovery_clears_flag(self):
        s = StragglerScorer(window=4)
        self.feed(s, {"w0": 0.05, "w1": 0.05, "w2": 0.05, "w3": 0.9})
        assert s.stragglers() == ["w3"]
        # w3 speeds back up; its rolling median falls within the window
        self.feed(s, {"w0": 0.05, "w1": 0.05, "w2": 0.05, "w3": 0.05},
                  rounds=4)
        assert s.stragglers() == []

    def test_forget_drops_ghost_peers(self):
        s = StragglerScorer()
        self.feed(s, {"w0": 0.05, "w1": 0.05, "w2": 0.05, "w3": 0.5})
        s.forget(["w0", "w1", "w2"])
        assert "w3" not in s.scores()
        assert s.stragglers() == []


# ---------------------------------------------------------------------------
# aggregator against in-process TelemetryServers
# ---------------------------------------------------------------------------

class FakeWorker:
    """An in-process worker endpoint: its own registry + TelemetryServer,
    with a knob for how slow its synthetic steps are."""

    def __init__(self, step_time_s):
        self.step_time_s = step_time_s
        self.registry = metrics.Registry()
        self._steps = self.registry.counter(
            "kungfu_steps_total", "Training steps completed by this worker"
        )
        self._hist = self.registry.histogram(
            "kungfu_step_duration_seconds", "Wall-clock duration per step"
        )
        self._egress = self.registry.counter(
            "kungfu_egress_bytes_total", "bytes", ("peer",)
        )
        self.server = TelemetryServer(0, host="127.0.0.1", registry=self.registry)
        self.server.start()
        self.label = f"127.0.0.1:{self.server.port}"
        self.url = f"http://127.0.0.1:{self.server.port}"

    def step(self, n=5):
        for _ in range(n):
            self._steps.inc()
            self._hist.observe(self.step_time_s)
        self._egress.labels("other:1").inc(n * 1000)

    def stop(self):
        self.server.stop()


@pytest.fixture
def cluster4():
    workers = [FakeWorker(0.05) for _ in range(3)] + [FakeWorker(0.75)]
    agg = tcluster.TelemetryAggregator(
        interval=0.1, registry=metrics.Registry()
    )
    agg.set_peers([(w.label, w.url) for w in workers])
    try:
        yield workers, agg
    finally:
        agg.stop()
        for w in workers:
            w.stop()


def _run_scrapes(workers, agg, rounds=2):
    for _ in range(rounds):
        for w in workers:
            w.step()
        agg.scrape_once()


class TestAggregator:
    def test_scrape_merge_and_health(self, cluster4):
        workers, agg = cluster4
        audit.clear()
        try:
            _run_scrapes(workers, agg)
            health = agg.cluster_health()
            delayed = workers[-1].label
            # every peer scraped, has step stats and fresh age
            assert set(health["peers"]) == {w.label for w in workers}
            for label, info in health["peers"].items():
                assert info["error"] is None
                assert info["step_rate"] > 0
                assert info["last_scrape_age_s"] < 5
                assert info["bytes_tx"] == pytest.approx(10_000)
            # acceptance: the delayed peer is flagged within two scrapes
            assert health["stragglers"] == [delayed]
            assert health["peers"][delayed]["straggler"] is True
            assert health["peers"][delayed]["step_time_p99_ms"] > 500
            assert health["step_skew"] == pytest.approx(15.0, rel=0.2)
            # ...and emitted exactly one audit event for the transition
            events = audit.records(kind="straggler")
            assert len(events) == 1
            assert events[0].peer == delayed
            assert events[0].detail["step_time_ms"] > 500
        finally:
            audit.clear()

    def test_federated_metrics(self, cluster4):
        workers, agg = cluster4
        _run_scrapes(workers, agg, rounds=1)
        merged = agg.cluster_metrics()
        samples = promparse.parse_text(merged)
        for w in workers:
            assert promparse.sample_value(
                samples, "kungfu_steps_total", peer=w.label
            ) == 5
            # the worker's own per-remote-peer label survives as exported_peer
            assert promparse.sample_value(
                samples, "kungfu_egress_bytes_total",
                peer=w.label, exported_peer="other:1",
            ) == 5000
        assert merged.count("# TYPE kungfu_steps_total counter") == 1

    def test_clock_offset_estimated_and_bounded(self, cluster4):
        workers, agg = cluster4
        _run_scrapes(workers, agg, rounds=1)
        for st in agg.peers():
            # same machine, same perf_counter epoch: offset ~ 0, and the
            # estimate's error bound is the scrape RTT (loopback, small)
            assert st.clock_offset_us is not None
            assert abs(st.clock_offset_us) < 1e6
            assert st.best_rtt_s < 5.0

    def test_cluster_trace_merges_peers(self, cluster4):
        workers, agg = cluster4
        from kungfu_tpu.telemetry import tracing

        tracing.clear()
        with tracing.span("t_cluster_span"):
            pass
        _run_scrapes(workers, agg, rounds=1)
        doc = agg.cluster_trace()
        evs = doc["traceEvents"]
        pids = {e["pid"] for e in evs}
        assert pids == set(range(len(workers)))  # one process per peer
        names = {
            e["args"]["name"] for e in evs if e["name"] == "process_name"
        }
        assert names == {w.label for w in workers}
        # worker spans survive the merge with shifted timestamps
        assert any(e["name"] == "t_cluster_span" for e in evs)

    def test_unreachable_peer_reported_not_fatal(self, cluster4):
        workers, agg = cluster4
        dead = workers[0]
        # healthy first: the peer accumulates live-looking numbers
        _run_scrapes(workers, agg, rounds=2)
        assert agg.cluster_health()["peers"][dead.label]["step_rate"] > 0
        dead.stop()
        _run_scrapes(workers[1:], agg, rounds=1)
        health = agg.cluster_health()
        info = health["peers"][dead.label]
        assert info["error"] is not None
        # no frozen-healthy numbers for a dead worker
        assert info["step_rate"] is None
        assert info["step_time_p50_ms"] is None
        live = [w.label for w in workers[1:]]
        for label in live:
            assert health["peers"][label]["error"] is None

    def test_dead_endpoint_clears_straggler_flag(self, cluster4):
        """A flagged peer whose telemetry endpoint goes dark must not
        stay flagged off frozen window data — a patience-based policy
        would shed a possibly-healthy worker hours later."""
        workers, agg = cluster4
        audit.clear()
        try:
            _run_scrapes(workers, agg)
            delayed = workers[-1]
            assert agg.cluster_health()["stragglers"] == [delayed.label]
            delayed.stop()
            _run_scrapes(workers[:-1], agg, rounds=1)
            health = agg.cluster_health()
            assert health["stragglers"] == []
            assert health["peers"][delayed.label]["straggler"] is False
            assert [r.peer for r in audit.records(kind="straggler_cleared")] \
                == [delayed.label]
            # the dead peer is gone from the METRICS view too: no frozen
            # exposition page, no stale healthy-looking gauges (the
            # scrape-error counter and age gauge rightly keep its label)
            merged = promparse.parse_text(agg.cluster_metrics())
            for fam in (
                "kungfu_steps_total",
                "kungfu_cluster_step_rate",
                "kungfu_cluster_step_time_seconds",
                "kungfu_cluster_straggler_score",
            ):
                assert promparse.sample_value(
                    merged, fam, peer=delayed.label
                ) is None, fam
            assert promparse.sample_value(
                merged, "kungfu_cluster_scrape_errors_total",
                peer=delayed.label,
            ) >= 1
        finally:
            audit.clear()

    def test_membership_change_drops_ghosts(self, cluster4):
        workers, agg = cluster4
        _run_scrapes(workers, agg)
        delayed = workers[-1]
        assert agg.cluster_health()["stragglers"] == [delayed.label]
        # the slow peer leaves the cluster (e.g. a shrink shed it)
        agg.set_peers([(w.label, w.url) for w in workers[:-1]])
        _run_scrapes(workers[:-1], agg, rounds=1)
        health = agg.cluster_health()
        assert delayed.label not in health["peers"]
        assert health["stragglers"] == []

    def test_synchronous_training_scores_compute_not_wall(self):
        """Under synchronous collectives every peer's WALL step time
        converges to the straggler's (the fast ones wait in allreduce).
        The scorer must use compute = step - collective wait, so the
        peer that spends its step computing gets flagged, not the ones
        waiting on it."""
        workers = [FakeWorker(0.5) for _ in range(4)]  # equal wall time
        coll = [
            w.registry.histogram(
                "kungfu_collective_latency_seconds", "lat", ("collective",)
            )
            for w in workers
        ]
        agg = tcluster.TelemetryAggregator(
            interval=0.1, registry=metrics.Registry()
        )
        agg.set_peers([(w.label, w.url) for w in workers])
        try:
            for _ in range(2):
                for i, w in enumerate(workers):
                    w.step()
                    # fast peers waited 0.45s of each 0.5s step; the
                    # straggler (last) waited almost nothing
                    wait = 0.02 if i == len(workers) - 1 else 0.45
                    for _ in range(5):
                        coll[i].labels("all_reduce").observe(wait)
                agg.scrape_once()
            health = agg.cluster_health()
            assert health["stragglers"] == [workers[-1].label]
            flagged = health["peers"][workers[-1].label]
            assert flagged["compute_time_ms"] == pytest.approx(480, rel=0.05)
            ok = health["peers"][workers[0].label]
            assert ok["compute_time_ms"] == pytest.approx(50, rel=0.1)
            # wall-clock quantiles stay honest (everyone ~500ms)
            assert ok["step_time_p50_ms"] > 250
        finally:
            agg.stop()
            for w in workers:
                w.stop()

    def test_background_scrape_thread(self, cluster4):
        workers, agg = cluster4
        for w in workers:
            w.step(20)
        agg.start()
        deadline = time.time() + 5.0
        while time.time() < deadline:
            if any(st.scrapes >= 2 for st in agg.peers()):
                break
            time.sleep(0.05)
        agg.stop()
        assert any(st.scrapes >= 2 for st in agg.peers())


# ---------------------------------------------------------------------------
# /cluster/* endpoints on the watcher's DebugServer
# ---------------------------------------------------------------------------

class _StubWatcher:
    def __init__(self, aggregator=None):
        self.aggregator = aggregator

    def debug_dump(self):
        return {"self": "stub", "stages": [], "workers": {}}


class TestClusterEndpoints:
    def test_cluster_routes_roundtrip(self, cluster4):
        from kungfu_tpu.runner.watch import DebugServer

        workers, agg = cluster4
        _run_scrapes(workers, agg)
        srv = DebugServer(_StubWatcher(agg), 0)
        srv.start()
        base = f"http://127.0.0.1:{srv.port}"
        try:
            with urllib.request.urlopen(base + "/cluster/health", timeout=5) as r:
                health = json.loads(r.read().decode())
            assert health["stragglers"] == [workers[-1].label]
            with urllib.request.urlopen(base + "/cluster/metrics", timeout=5) as r:
                body = r.read().decode()
                assert r.headers["Content-Type"].startswith("text/plain")
            assert f'kungfu_steps_total{{peer="{workers[0].label}"}}' in body
            # the aggregator's OWN gauges ride the federated page
            assert "kungfu_cluster_straggler_score" in body
            with urllib.request.urlopen(base + "/cluster/trace", timeout=5) as r:
                doc = json.loads(r.read().decode())
            # the four workers, and the runner's own ring as one more process
            assert {e["pid"] for e in doc["traceEvents"]} == set(range(5))
            assert {"name": "process_name", "ph": "M", "pid": 4, "tid": 0,
                    "args": {"name": "runner"}} in doc["traceEvents"]
            # query strings must not demote a cluster view to the dump
            with urllib.request.urlopen(
                base + "/cluster/health?t=123", timeout=5
            ) as r:
                assert "stragglers" in json.loads(r.read().decode())
            # a typo'd cluster path is a 404, not the wrong document
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(base + "/cluster/nope", timeout=5)
            # any other path keeps the old Stage-dump contract
            with urllib.request.urlopen(base + "/", timeout=5) as r:
                dump = json.loads(r.read().decode())
            assert dump["self"] == "stub"
        finally:
            srv.stop()

    def test_cluster_route_without_aggregator_falls_back(self):
        from kungfu_tpu.runner.watch import DebugServer

        srv = DebugServer(_StubWatcher(None), 0)
        srv.start()
        try:
            url = f"http://127.0.0.1:{srv.port}/cluster/health"
            with urllib.request.urlopen(url, timeout=5) as r:
                dump = json.loads(r.read().decode())
            assert dump["self"] == "stub"  # stage dump, not a 500
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# info top
# ---------------------------------------------------------------------------

class TestInfoTop:
    HEALTH = {
        "peers": {
            "10.0.0.1:20001": {
                "step_rate": 19.8, "step_time_p50_ms": 50.2,
                "step_time_p99_ms": 61.0, "bytes_tx": 5 << 20,
                "bytes_rx": 4 << 20, "rtt_ms": 0.21,
                "last_scrape_age_s": 1.2, "error": None,
                "straggler": False, "rtt_outlier": False,
            },
            "10.0.0.2:20001": {
                "step_rate": 2.1, "step_time_p50_ms": 480.0,
                "step_time_p99_ms": 590.0, "bytes_tx": 1 << 20,
                "bytes_rx": 1 << 20, "rtt_ms": 3.4,
                "last_scrape_age_s": 1.2, "error": None,
                "straggler": True, "rtt_outlier": True,
            },
        },
        "stragglers": ["10.0.0.2:20001"],
        "step_skew": 9.56,
    }

    def test_render_top_table(self):
        from kungfu_tpu.info.__main__ import render_top

        out = render_top(self.HEALTH)
        lines = out.splitlines()
        assert "2 peers" in lines[0] and "step skew 9.56x" in lines[0]
        assert "STRAGGLERS: 10.0.0.2:20001" in lines[0]
        assert lines[1].startswith("PEER")
        row = [l for l in lines if l.startswith("10.0.0.2")][0]
        assert "STRAGGLER,RTT" in row
        assert "480.0" in row and "5.0MiB" not in row
        row_ok = [l for l in lines if l.startswith("10.0.0.1")][0]
        assert row_ok.endswith("ok")
        assert "5.0MiB" in row_ok

    def test_info_top_one_shot_over_http(self, cluster4, capsys):
        from kungfu_tpu.info.__main__ import _cmd_top
        from kungfu_tpu.runner.watch import DebugServer

        workers, agg = cluster4
        _run_scrapes(workers, agg)
        srv = DebugServer(_StubWatcher(agg), 0)
        srv.start()
        try:
            rc = _cmd_top([f"http://127.0.0.1:{srv.port}/cluster/health"])
        finally:
            srv.stop()
        assert rc == 0
        out = capsys.readouterr().out
        for w in workers:
            assert w.label in out
        assert "STRAGGLER" in out

    def test_info_top_requires_url(self, monkeypatch, capsys):
        from kungfu_tpu.info.__main__ import _cmd_top

        monkeypatch.delenv("KF_CLUSTER_HEALTH_URL", raising=False)
        assert _cmd_top([]) == 2


# ---------------------------------------------------------------------------
# monitor/policy integration: the monitor -> adapt loop
# ---------------------------------------------------------------------------

class TestAdaptationSignals:
    def test_health_signals_flatten(self, cluster4):
        workers, agg = cluster4
        _run_scrapes(workers, agg)
        tcluster.set_aggregator(agg)
        try:
            sig = tcluster.health_signals(self_peer=workers[-1].label)
            assert sig["cluster/stragglers"] == [workers[-1].label]
            assert sig["cluster/self_straggler"] is True
            assert sig["cluster/step_skew"] > 5
            assert workers[-1].label in sig["cluster/straggler_score"]
            sig2 = tcluster.health_signals(self_peer=workers[0].label)
            assert sig2["cluster/self_straggler"] is False
        finally:
            tcluster.set_aggregator(None)

    def test_policy_context_sees_straggler_within_two_scrapes(self, cluster4):
        """Acceptance: delayed peer flagged -> audit event -> signal in
        PolicyContext.metrics, all within two scrape intervals."""
        from kungfu_tpu.monitor import cluster_health
        from kungfu_tpu.policy import PolicyRunner

        workers, agg = cluster4
        audit.clear()
        tcluster.set_aggregator(agg)
        try:
            _run_scrapes(workers, agg, rounds=2)  # two scrape intervals
            assert cluster_health()["cluster/stragglers"] == [workers[-1].label]
            with PolicyRunner([], batch_size=8) as runner:
                with runner.step():
                    pass
            assert (
                runner.ctx.metrics["cluster/stragglers"]
                == [workers[-1].label]
            )
            assert runner.ctx.metrics["cluster/step_skew"] > 5
            assert audit.records(kind="straggler")
        finally:
            tcluster.set_aggregator(None)
            audit.clear()

    def test_policy_metrics_empty_without_plane(self, monkeypatch):
        from kungfu_tpu.policy import PolicyRunner

        monkeypatch.delenv("KF_CLUSTER_HEALTH_URL", raising=False)
        tcluster.set_aggregator(None)
        with PolicyRunner([], batch_size=8) as runner:
            with runner.step():
                pass
        assert "cluster/stragglers" not in runner.ctx.metrics

    def test_remote_health_url_fetch(self, cluster4, monkeypatch):
        """Workers read the runner's /cluster/health via the env var the
        watcher injects at spawn."""
        from kungfu_tpu.runner.watch import DebugServer

        workers, agg = cluster4
        _run_scrapes(workers, agg)
        srv = DebugServer(_StubWatcher(agg), 0)
        srv.start()
        tcluster.set_aggregator(None)

        def reset_cache():
            tcluster._remote_cache.update(
                t=0.0, attempt_t=0.0, data=None, url="", fetching=False
            )

        try:
            monkeypatch.setenv(
                tcluster.HEALTH_URL_ENV,
                f"http://127.0.0.1:{srv.port}/cluster/health",
            )
            reset_cache()
            # wait=True runs the overdue refresh inline (tests/CLIs); the
            # default is non-blocking and returns the cache as-is
            sig = tcluster.health_signals(max_age=0.5, wait=True)
            assert sig["cluster/stragglers"] == [workers[-1].label]
            stamped = sig["cluster/updated_at"]
            # second read inside max_age hits the cache (no fetch)
            srv.stop()
            sig2 = tcluster.health_signals(max_age=60.0)
            assert sig2["cluster/stragglers"] == [workers[-1].label]
            # a FAILED refresh keeps the old snapshot AND its old stamp:
            # dead-runner flags must read as stale, not as news
            tcluster._remote_cache["t"] = 0.0
            tcluster._remote_cache["attempt_t"] = 0.0
            sig3 = tcluster.health_signals(max_age=0.01, wait=True)
            assert sig3["cluster/updated_at"] == stamped
        finally:
            try:
                srv.stop()
            except Exception:
                pass
            reset_cache()

    def test_straggler_policy_fires_after_patience(self):
        """A STEADY straggler (identical flag list every refresh) must
        reach patience — freshness comes from cluster/updated_at, not
        from the flag list changing."""
        from kungfu_tpu.policy import PolicyContext, StragglerPolicy

        fired = []
        pol = StragglerPolicy(
            patience=3, on_straggler=lambda ctx, peers: fired.append(peers)
        )
        ctx = PolicyContext(batch_size=8)
        ctx.metrics["cluster/stragglers"] = ["w3"]
        for refresh in range(3):
            ctx.metrics["cluster/updated_at"] = 1000.0 + refresh
            # many steps per refresh: counted once per refresh
            pol.after_step(ctx)
            pol.after_step(ctx)
        assert fired == [["w3"]]
        # cleared peer stops accumulating; a different peer starts fresh
        fired.clear()
        ctx.metrics["cluster/stragglers"] = ["w1"]
        for refresh in range(2):
            ctx.metrics["cluster/updated_at"] = 2000.0 + refresh
            pol.after_step(ctx)
        assert fired == []

    def test_policy_runner_publishes_step_series(self):
        """The worker-side half of the loop: steps land in the registry
        the aggregator scrapes (kungfu_steps_total + duration histogram)."""
        from kungfu_tpu.policy import PolicyRunner
        from kungfu_tpu.telemetry import config

        config.refresh(forced=frozenset({"metrics"}))
        try:
            with PolicyRunner([], batch_size=4) as runner:
                for _ in range(3):
                    with runner.step():
                        pass
            reg = metrics.get_registry()
            assert reg.get("kungfu_steps_total").value >= 3
            assert reg.get("kungfu_step_duration_seconds").count >= 3
        finally:
            config.refresh()
