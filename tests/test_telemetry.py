"""Unit tests for the unified telemetry subsystem (ISSUE 1):

- metrics registry: concurrent increments, histogram quantiles,
  Prometheus text exposition;
- tracing: span nesting, Chrome-trace JSON export round-trip;
- config: shared truthy parsing + KF_TELEMETRY feature selection;
- log: structured fields, level filtering, echo;
- http: /metrics + /trace + /audit endpoint.
"""

import json
import math
import threading
import urllib.request

import pytest

from kungfu_tpu.telemetry import audit, config, log, metrics, tracing


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

class TestCounters:
    def test_concurrent_increments(self):
        reg = metrics.Registry()
        c = reg.counter("t_total", "test", ("worker",))
        n_threads, n_incs = 8, 2000

        def run(i):
            child = c.labels(str(i % 2))
            for _ in range(n_incs):
                child.inc()

        ts = [threading.Thread(target=run, args=(i,)) for i in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        total = sum(v for _, _, v in c.samples())
        assert total == n_threads * n_incs
        assert c.labels("0").value == n_threads * n_incs / 2

    def test_counter_rejects_negative(self):
        c = metrics.Registry().counter("t_total")
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_labelled_family_requires_labels(self):
        c = metrics.Registry().counter("t_total", "", ("peer",))
        with pytest.raises(ValueError):
            c.inc()

    def test_reregistration_is_idempotent_but_typed(self):
        reg = metrics.Registry()
        a = reg.counter("x_total")
        assert reg.counter("x_total") is a
        with pytest.raises(ValueError):
            reg.gauge("x_total")
        with pytest.raises(ValueError):
            reg.counter("x_total", labelnames=("p",))

    def test_gauge_set_inc_dec(self):
        g = metrics.Registry().gauge("g")
        g.set(5)
        g.inc(2)
        g.dec()
        assert g.value == 6


class TestHistogram:
    def test_quantiles(self):
        reg = metrics.Registry()
        h = reg.histogram("lat_seconds", buckets=(0.01, 0.1, 1.0, 10.0))
        for _ in range(100):
            h.observe(0.05)  # all in the (0.01, 0.1] bucket
        # interpolation inside the owning bucket
        assert 0.01 < h.quantile(0.5) <= 0.1
        assert h.quantile(0.0) <= h.quantile(0.5) <= h.quantile(1.0)
        assert h.count == 100
        assert h.sum == pytest.approx(5.0)

    def test_quantile_empty_is_nan(self):
        h = metrics.Registry().histogram("h", buckets=(1.0,))
        assert math.isnan(h.quantile(0.5))

    def test_quantile_spread(self):
        h = metrics.Registry().histogram(
            "h", buckets=(1.0, 2.0, 4.0, 8.0)
        )
        for v in (0.5, 1.5, 3.0, 6.0):
            h.observe(v)
        assert h.quantile(0.25) <= 1.0
        assert 4.0 <= h.quantile(1.0) <= 8.0

    def test_concurrent_observes(self):
        h = metrics.Registry().histogram("h", buckets=(0.5,))

        def run():
            for _ in range(1000):
                h.observe(0.1)

        ts = [threading.Thread(target=run) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert h.count == 4000


class TestExposition:
    def test_prometheus_text_format(self):
        reg = metrics.Registry()
        c = reg.counter("kf_bytes_total", "bytes", ("peer",))
        c.labels('ho"st:1').inc(3)
        g = reg.gauge("kf_gauge", "a gauge")
        g.set(1.5)
        h = reg.histogram("kf_lat_seconds", "lat", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(5.0)
        text = reg.render()
        assert "# TYPE kf_bytes_total counter" in text
        assert "# HELP kf_bytes_total bytes" in text
        # label escaping per the exposition spec
        assert 'kf_bytes_total{peer="ho\\"st:1"} 3' in text
        assert "# TYPE kf_gauge gauge" in text
        assert "kf_gauge 1.5" in text
        # cumulative buckets + +Inf + sum/count
        assert 'kf_lat_seconds_bucket{le="0.1"} 1' in text
        assert 'kf_lat_seconds_bucket{le="1"} 1' in text
        assert 'kf_lat_seconds_bucket{le="+Inf"} 2' in text
        assert "kf_lat_seconds_count 2" in text
        assert text.endswith("\n")

    def test_bad_metric_names_rejected(self):
        reg = metrics.Registry()
        for bad in ("", "1abc", "a-b", "a b"):
            with pytest.raises(ValueError):
                reg.counter(bad)

    def test_extra_renderer_appended(self):
        reg = metrics.Registry()
        reg.add_renderer(lambda: "# custom block\ncustom 1\n")
        assert "custom 1" in reg.render()


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

class TestTracing:
    def test_span_nesting_depths_and_containment(self):
        tracing.clear()
        with tracing.span("t_outer", step=1):
            with tracing.span("t_inner"):
                pass
            with tracing.span("t_inner2"):
                pass
        evs = {e.name: e for e in tracing.full_events("t_")}
        assert evs["t_outer"].depth == 0
        assert evs["t_inner"].depth == 1
        assert evs["t_inner2"].depth == 1
        # children temporally contained in the parent
        out = evs["t_outer"]
        for name in ("t_inner", "t_inner2"):
            e = evs[name]
            assert out.start <= e.start
            assert e.start + e.duration <= out.start + out.duration + 1e-9
        assert evs["t_outer"].args == {"step": 1}

    def test_depth_resets_after_exception(self):
        tracing.clear()
        with pytest.raises(RuntimeError):
            with tracing.span("t_err"):
                raise RuntimeError("x")
        with tracing.span("t_after"):
            pass
        evs = {e.name: e for e in tracing.full_events("t_")}
        assert evs["t_err"].depth == 0
        assert evs["t_after"].depth == 0  # stack unwound despite the raise

    def test_chrome_trace_json_roundtrip(self):
        tracing.clear()
        with tracing.span("t_step", bytes=1024):
            with tracing.span("t_child"):
                pass
        tracing.instant("t_mark", reason="test")
        doc = json.loads(tracing.chrome_trace_json("t_"))
        evs = doc["traceEvents"]
        by_name = {e["name"]: e for e in evs}
        step = by_name["t_step"]
        assert step["ph"] == "X"
        assert step["dur"] >= by_name["t_child"]["dur"]
        assert step["args"]["bytes"] == 1024
        mark = by_name["t_mark"]
        assert mark["ph"] == "i"
        for e in evs:
            assert {"name", "ph", "ts", "pid", "tid"} <= set(e)
            assert isinstance(e["ts"], float)
            if e["ph"] == "X":
                assert "dur" in e

    def test_chrome_trace_json_writes_a_loadable_file(self, tmp_path):
        tracing.clear()
        with tracing.span("t_io"):
            pass
        path = tmp_path / "trace.json"
        path.write_text(tracing.chrome_trace_json("t_"))
        with open(path) as f:
            doc = json.load(f)
        assert any(e["name"] == "t_io" for e in doc["traceEvents"])

    def test_legacy_shim_api(self):
        """The call shapes of the old scoped tracer (record, summary_ms)
        and the ring's own reading, under the one name its call sites
        import now."""
        from kungfu_tpu.telemetry import tracing as shim

        shim.clear()
        shim.record("t_legacy", 0.25)
        with shim.span("t_scoped"):
            pass
        names = [e.name for e in shim.full_events("t_")]
        assert "t_legacy" in names and "t_scoped" in names
        assert shim.summary_ms("t_legacy")["t_legacy"] == pytest.approx(250.0)
        assert any(
            e["name"] == "t_legacy" for e in tracing.chrome_trace()["traceEvents"]
        )


# ---------------------------------------------------------------------------
# config: truthy parsing + feature selection
# ---------------------------------------------------------------------------

class TestConfig:
    def test_truthy_variants(self):
        for v in ("1", "true", "TRUE", "yes", "On", " on ", "y"):
            assert config.truthy(v), v
        for v in ("", "0", "false", "off", "no", "garbage", "None"):
            assert not config.truthy(v), v

    def test_feature_parsing(self, monkeypatch):
        cases = {
            "metrics,trace": {"metrics", "trace"},
            "all": set(config.KNOWN_FEATURES),
            "1": set(config.KNOWN_FEATURES),
            "trace": {"trace"},
            "": set(),
            "0": set(),
            "bogus": set(),
            "metrics, bogus": {"metrics"},
        }
        for raw, want in cases.items():
            monkeypatch.setenv(config.TELEMETRY_ENV, raw)
            config.refresh()
            assert set(config.features()) == want, raw
        config.refresh()

    def test_monitoring_env_variants_enable_metrics(self, monkeypatch):
        """Satellite: KF_CONFIG_ENABLE_MONITORING "yes"/"on" used to be
        silently rejected by monitor.net.enabled()."""
        from kungfu_tpu.monitor import net

        monkeypatch.delenv(config.TELEMETRY_ENV, raising=False)
        config.refresh()
        for v in ("1", "true", "yes", "on", "ON", "Yes"):
            monkeypatch.setenv("KF_CONFIG_ENABLE_MONITORING", v)
            assert net.enabled(), v
        monkeypatch.setenv("KF_CONFIG_ENABLE_MONITORING", "0")
        assert not net.enabled()
        monkeypatch.delenv("KF_CONFIG_ENABLE_MONITORING")
        assert not net.enabled()


# ---------------------------------------------------------------------------
# log
# ---------------------------------------------------------------------------

class TestLog:
    def test_structured_fields_and_levels(self, capsys):
        log.set_level("INFO")
        try:
            log.info("resize landed", old=4, new=3)
            log.debug("hidden")
            err = capsys.readouterr().err
            assert "resize landed old=4 new=3" in err
            assert "hidden" not in err
        finally:
            log.set_level("INFO")

    def test_percent_args_still_work(self, capsys):
        log.warn("workers exited %s; restarting", [1, 0])
        assert "workers exited [1, 0]; restarting" in capsys.readouterr().err

    def test_echo_goes_to_stdout_unfiltered(self, capsys):
        log.set_level("OFF")
        try:
            log.echo("RESULT: 1.0 GiB/s")
            out = capsys.readouterr().out
            assert out == "RESULT: 1.0 GiB/s\n"
        finally:
            log.set_level("INFO")


# ---------------------------------------------------------------------------
# http endpoint + dump
# ---------------------------------------------------------------------------

def test_telemetry_server_routes():
    from kungfu_tpu.telemetry.http import TelemetryServer

    metrics.counter("t_http_total", "x").inc(7)
    tracing.clear()
    with tracing.span("t_http_span"):
        pass
    srv = TelemetryServer(0, host="127.0.0.1")
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        with urllib.request.urlopen(base + "/metrics", timeout=5) as r:
            body = r.read().decode()
        assert "t_http_total 7" in body
        # a scraper's cache-buster query must not 404 the route
        with urllib.request.urlopen(base + "/metrics?t=1", timeout=5) as r:
            assert "t_http_total 7" in r.read().decode()
        with urllib.request.urlopen(base + "/trace", timeout=5) as r:
            doc = json.loads(r.read().decode())
        assert any(e["name"] == "t_http_span" for e in doc["traceEvents"])
        with urllib.request.urlopen(base + "/audit", timeout=5) as r:
            assert isinstance(json.loads(r.read().decode()), list)
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/nope", timeout=5)
    finally:
        srv.stop()
    # clean shutdown released the port: a new server can bind it at once
    from kungfu_tpu.telemetry.http import TelemetryServer as TS2

    srv2 = TS2(srv.port, host="127.0.0.1")
    srv2.stop()


def test_dump_shape():
    from kungfu_tpu import telemetry

    d = telemetry.dump()
    assert set(d) >= {"features", "metrics", "trace", "audit", "spans"}
    assert isinstance(d["trace"]["traceEvents"], list)
    json.dumps(d["trace"])  # must be JSON-serializable


def test_audit_record_shape():
    audit.clear()
    try:
        rec = audit.record_resize(
            peer="h:1",
            cluster_version=3,
            trigger="config_server",
            old_peers=["h:1", "h:2"],
            new_peers=["h:1"],
            phases_ms={"consensus_ms": 1.0, "update_ms": 2.5},
            progress=128,
        )
        assert rec.old_size == 2 and rec.new_size == 1
        assert rec.duration_ms == pytest.approx(3.5)
        (got,) = audit.records(kind="resize")
        assert got.trigger == "config_server"
        assert audit.annotate_last(peer="h:1", checkpoint_version=9)
        assert audit.records()[0].checkpoint_version == 9
        line = audit.to_jsonl().strip()
        assert json.loads(line)["progress"] == 128
        # the config-server WAIT is recorded but excluded from duration
        # (it measures idling before agreement, not resize work)
        rec2 = audit.record_resize(
            peer="h:1",
            trigger="config_server",
            old_peers=["h:1", "h:2"],
            new_peers=["h:1"],
            phases_ms={"wait_config_ms": 15000.0, "update_ms": 2.0},
        )
        assert rec2.duration_ms == pytest.approx(2.0)
    finally:
        audit.clear()
