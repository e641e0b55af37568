"""Scalable telemetry plane (ISSUE 18): the aggregator at k=256.

Covers the tentpole end to end against an in-process simulated fleet
behind the injectable transport hook (256 real HTTP servers per test
would be a fork bomb):

- hierarchical fan-in: host digests sweep O(hosts), offsets composed
  across the two hops, digest-less hosts fall back to direct scrapes;
- two-hop NTP composition property: the composed estimate's error is
  bounded by the SUM of the per-hop RTT/2 bounds;
- sampled link matrix: rotation coverage (every row refreshed within
  one rotation window), retained slowest edges never sampled out,
  payload bounded O(k)/sweep;
- delta scrapes: ?since semantics across ring wraparound for the step
  ring, the audit log (stable seq identity, useq re-stamp on
  annotate) and the decision ledger;
- self-observability: sweep gauges, payload accounting by endpoint,
  overload backoff + aggregator_overload audit, plane envelope on the
  merged views, `info top` plane-health line;
- flat-mode contract: k<=8 stays byte-identical to the pre-scale
  merges (same merge functions, no sampled keys, no digest fetches);
- ReplanPolicy's staleness gate: no yes-vote off link rows older than
  the knob.
"""

import collections
import json
import math
import threading
import time

import pytest

from kungfu_tpu.telemetry import audit, metrics, promparse
from kungfu_tpu.telemetry import cluster as tcluster
from kungfu_tpu.telemetry import decisions as tdecisions
from kungfu_tpu.telemetry import link as tlink
from kungfu_tpu.telemetry import steptrace as tsteptrace
from kungfu_tpu.telemetry.http import CLOCK_HEADER


# ---------------------------------------------------------------------------
# simulated fleet behind the transport hook
# ---------------------------------------------------------------------------


def _worker_page(label, dsts, step_time_s=0.05, steps=200, bw=None):
    """A minimal but real exposition page: steps + duration histogram +
    this worker's link-matrix row (bw per dst)."""
    sum_s = steps * step_time_s
    lines = [
        "# TYPE kungfu_steps_total counter",
        f"kungfu_steps_total {steps}",
        "# TYPE kungfu_step_duration_seconds histogram",
        f'kungfu_step_duration_seconds_bucket{{le="0.1"}} {steps}',
        f'kungfu_step_duration_seconds_bucket{{le="+Inf"}} {steps}',
        f"kungfu_step_duration_seconds_sum {sum_s}",
        f"kungfu_step_duration_seconds_count {steps}",
        "# TYPE kungfu_link_bandwidth_bytes_per_second gauge",
    ]
    for dst in dsts:
        v = bw.get(dst, 1e8) if bw else 1e8
        lines.append(
            f'kungfu_link_bandwidth_bytes_per_second{{dst="{dst}"}} {v}'
        )
    return "\n".join(lines) + "\n"


class Fleet:
    """k simulated workers on `hosts` hosts, served through a
    `fetch(base_url, path, timeout)` hook. Each worker has a known TRUE
    clock offset (head offset + within-host offset) so the NTP
    composition is checkable; each host's lowest-label worker serves a
    /host/telemetry digest exactly shaped like HostSubAggregator's."""

    def __init__(self, hosts=4, per_host=4, neighbors=4, delay_s=0.0,
                 serve_digests=True):
        self.delay_s = delay_s
        self.serve_digests = serve_digests
        self.calls = collections.Counter()  # endpoint -> fetches
        self.since_seen = collections.defaultdict(list)  # path -> cursors
        self._call_lock = threading.Lock()
        self.targets = []  # (label, url)
        self.host_of = {}
        self.pages = {}
        self.true_offset_us = {}
        self.head_offset_us = {}
        self.heads = {}
        labels = [
            f"h{h:02d}:{9000 + i}"
            for h in range(hosts) for i in range(per_host)
        ]
        for h in range(hosts):
            host = f"h{h:02d}"
            self.head_offset_us[host] = (h + 1) * 1e6
            for i in range(per_host):
                label = f"{host}:{9000 + i}"
                self.host_of[label] = host
                self.true_offset_us[label] = (
                    self.head_offset_us[host] + i * 1e3
                )
                self.targets.append((label, f"http://{host}:{9000 + i}"))
            self.heads[host] = f"{host}:{9000}"
        # link rows: each worker reports `neighbors` following labels
        self.rows = {}
        k = len(labels)
        for idx, label in enumerate(labels):
            dsts = [labels[(idx + 1 + j) % k] for j in range(neighbors)]
            self.rows[label] = dsts
            self.pages[label] = _worker_page(label, dsts)
        # plane documents (identical per worker — the merge keys on the
        # scrape label, not the document body)
        store = tsteptrace.StepStore(keep=8)
        for r in (1, 2, 3):
            rec = store.begin_step(0, r)
            rec.finish(flush_wait_s=0.001, busy_s=0.04)
        self.step_doc = store.export(peer="fleet")
        self.decision_doc = tdecisions.DecisionLedger(keep=8).export()
        self.resource_doc = {"peer": "fleet", "wall_time_s": time.time()}
        self.memory_doc = {"peer": "fleet", "wall_time_s": time.time()}

    def set_slow_edge(self, src, dst, bw):
        self.pages[src] = _worker_page(
            src, self.rows[src], bw={dst: bw}
        )

    def _label(self, base_url):
        hostport = base_url.split("//", 1)[1]
        return hostport

    def _digest(self, host):
        workers = {}
        for label, url in self.targets:
            if self.host_of[label] != host:
                continue
            text = self.pages[label]
            workers[label] = {
                "url": url,
                "metrics_text": text,
                "parsed": tcluster.parsed_to_doc(
                    tcluster.parse_worker_page(text)
                ),
                "rtt_s": 1e-4,
                # the head's estimate of its sibling: the within-host
                # hop of the two-hop composition
                "clock_offset_us": (
                    self.true_offset_us[label] - self.head_offset_us[host]
                ),
                "steptrace": self.step_doc,
                "decisions": self.decision_doc,
                "resources": self.resource_doc,
                "memory": self.memory_doc,
            }
        return {
            "enabled": True, "host": host,
            "wall_time": time.time(), "workers": workers,
        }

    def fetch(self, base_url, path, timeout):
        label = self._label(base_url)
        endpoint, _, query = path.partition("?")
        with self._call_lock:
            self.calls[endpoint] += 1
            if query.startswith("since="):
                self.since_seen[endpoint].append(int(query[6:]))
        if self.delay_s:
            time.sleep(self.delay_s)
        headers = {
            CLOCK_HEADER: repr(
                time.perf_counter() * 1e6 - self.true_offset_us[label]
            )
        }
        if endpoint == tcluster.HOST_DIGEST_PATH:
            if self.serve_digests and self.heads.get(
                self.host_of[label]
            ) == label:
                doc = self._digest(self.host_of[label])
            else:
                doc = {"enabled": False}
            return json.dumps(doc).encode(), headers
        if endpoint == "/metrics":
            return self.pages[label].encode(), headers
        doc = {
            "/steptrace": self.step_doc,
            "/decisions": self.decision_doc,
            "/resources": self.resource_doc,
            "/memory": self.memory_doc,
        }.get(endpoint)
        if doc is None:
            raise OSError(f"404 {endpoint}")
        return json.dumps(doc).encode(), headers


def _mk_agg(fleet, interval=5.0, **kw):
    agg = tcluster.TelemetryAggregator(
        interval=interval, registry=metrics.Registry(),
        fetch=fleet.fetch, **kw,
    )
    agg.set_peers(fleet.targets)
    return agg


# ---------------------------------------------------------------------------
# the k=256 harness
# ---------------------------------------------------------------------------


class TestScaleHarness:
    @pytest.fixture
    def fleet256(self, monkeypatch):
        monkeypatch.setenv("KF_AGG_HIER_MIN_PEERS", "32")
        monkeypatch.setenv("KF_AGG_LINK_ROTATION_SWEEPS", "8")
        monkeypatch.setenv("KF_AGG_LINK_TOP_EDGES", "16")
        fleet = Fleet(hosts=16, per_host=16, neighbors=8)
        agg = _mk_agg(fleet, interval=5.0)
        try:
            yield fleet, agg
        finally:
            agg.stop()

    def test_k256_sweep_within_interval_and_hier(self, fleet256):
        fleet, agg = fleet256
        health = agg.scrape_once()
        plane = health["plane"]
        assert plane["mode"] == "hier"
        assert plane["sweep_seconds"] < agg.interval
        assert plane["scraped_peers"] == 256
        assert plane["stale_peers"] == 0
        # O(hosts) fan-in: 16 digest fetches, zero direct worker fetches
        assert fleet.calls[tcluster.HOST_DIGEST_PATH] == 16
        assert fleet.calls["/metrics"] == 0
        assert fleet.calls["/steptrace"] == 0
        # payload accounting: every fetched byte attributed by endpoint
        paid = agg._c_payload.labels(tcluster.HOST_DIGEST_PATH).value
        assert paid > 0
        assert agg._c_deadline.value == 0

    def test_k256_two_hop_offsets_composed(self, fleet256):
        fleet, agg = fleet256
        agg.scrape_once()
        # in-process round trips are sub-millisecond, so the composed
        # estimate must land within a loose 50ms of the true offset —
        # the hops are 1e6-scale, so a composition bug is unmissable
        for st in agg.peers():
            true = fleet.true_offset_us[st.label]
            assert st.clock_offset_us == pytest.approx(true, abs=5e4)

    def test_k256_sampled_links_payload_and_rotation(self, fleet256):
        fleet, agg = fleet256
        rot = 8
        slow_src, slow_dst = "h03:9005", "h03:9006"
        fleet.set_slow_edge(slow_src, slow_dst, 1e3)
        t0 = time.monotonic()
        seen_rows = set()
        for sweep in range(rot):
            agg.scrape_once()
            doc = agg.cluster_links()
            assert doc["mode"] == "sampled"
            seen_rows.update(doc["edges"])
        elapsed = time.monotonic() - t0
        doc = agg.cluster_links()
        # rotation coverage: every row ingested within one window
        assert seen_rows == {label for label, _ in fleet.targets}
        assert doc["coverage"] == 1.0
        assert doc["oldest_row_age_s"] <= elapsed + 1.0
        assert doc["row_age_s"][slow_src] >= 0.0
        # the slowest edge is elected over the WHOLE cache and retained
        assert doc["slowest_edge"] == [slow_src, slow_dst]
        assert doc["min_bw"] == pytest.approx(1e3)
        retained = [
            (e["src"], e["dst"]) for e in doc["slowest_edges"]
        ]
        assert (slow_src, slow_dst) in retained
        # retention: many more sweeps, the slow row re-ingests every
        # sweep (never rotates out of freshness)
        for _ in range(3):
            before = time.monotonic()
            agg.scrape_once()
            doc = agg.cluster_links()
            assert slow_src in doc["edges"]
            assert doc["row_age_s"][slow_src] <= (
                time.monotonic() - before + 0.5
            )
        # payload bound: the sampled document ships O(k) edges per
        # sweep (rotation slice + retained rows), not the k x neighbors
        # full matrix
        full_rows = {
            label: {
                dst: {"bw": 1e8} for dst in fleet.rows[label]
            }
            for label, _ in fleet.targets
        }
        full_bytes = len(json.dumps(tlink.merge_matrix(full_rows)))
        sampled_bytes = len(json.dumps(doc))
        assert sum(len(r) for r in doc["edges"].values()) <= (
            (math.ceil(256 / rot) + 16) * 8
        )
        # byte win is modest here because the fixture's rows are sparse
        # (8 neighbors) and the coverage metadata is O(k); the >=4x
        # demonstration at realistic edge density lives in the bench
        assert sampled_bytes * 2 < full_bytes

    def test_k256_health_and_signals_carry_plane(self, fleet256):
        fleet, agg = fleet256
        agg.scrape_once()
        health = agg.cluster_health()
        assert health["plane"]["mode"] == "hier"
        assert health["links"]["oldest_row_age_s"] is not None
        tcluster.set_aggregator(agg)
        try:
            sig = tcluster.health_signals()
        finally:
            tcluster.set_aggregator(None)
        assert sig["plane/mode"] == "hier"
        assert sig["plane/stale_peers"] == 0
        assert sig["plane/sweep_seconds"] == health["plane"]["sweep_seconds"]
        assert "links/oldest_row_age_s" in sig
        # merged step plane flowed through the digests (newest round
        # held back per the merge contract)
        agg.scrape_once()
        steps = agg.cluster_steps()
        assert steps["plane"]["mode"] == "hier"
        assert [s["round"] for s in steps["steps"]] == [1, 2]

    def test_k256_digestless_host_falls_back_to_direct(self, fleet256):
        fleet, agg = fleet256
        fleet.heads["h07"] = None  # h07's head lost the role
        agg.scrape_once()
        # the other 15 hosts still swept via digest; h07's 16 workers
        # were scraped directly and are NOT stale
        assert fleet.calls["/metrics"] == 16
        assert agg.cluster_health()["plane"]["stale_peers"] == 0


# ---------------------------------------------------------------------------
# two-hop NTP composition property
# ---------------------------------------------------------------------------


class TestTwoHopClock:
    def test_error_bounded_by_sum_of_hop_rtt_halves(self, monkeypatch):
        """Composition property: with hop delays large enough to
        measure, |estimate - true| <= rtt1/2 + rtt2/2."""
        monkeypatch.setenv("KF_AGG_HIER_MIN_PEERS", "2")
        head_off, worker_off = 3e6, 7e3
        hop_delay = 0.02

        def fetch(base_url, path, timeout):
            time.sleep(hop_delay)
            off = head_off if base_url.endswith(":9000") else 0.0
            headers = {
                CLOCK_HEADER: repr(time.perf_counter() * 1e6 - off)
            }
            if path == tcluster.HOST_DIGEST_PATH:
                doc = {
                    "enabled": True, "host": "hx",
                    "wall_time": time.time(),
                    "workers": {
                        "hx:9000": {
                            "url": "http://hx:9000",
                            "metrics_text": "", "parsed": {},
                            "rtt_s": 2 * hop_delay,
                            "clock_offset_us": 0.0,
                        },
                        "hx:9001": {
                            "url": "http://hx:9001",
                            "metrics_text": "", "parsed": {},
                            "rtt_s": 2 * hop_delay,
                            "clock_offset_us": worker_off,
                        },
                    },
                }
                return json.dumps(doc).encode(), headers
            raise OSError("digest only")

        agg = tcluster.TelemetryAggregator(
            interval=5.0, registry=metrics.Registry(), fetch=fetch
        )
        agg.set_peers([
            ("hx:9000", "http://hx:9000"), ("hx:9001", "http://hx:9001"),
        ])
        try:
            agg.scrape_once()
            st = {s.label: s for s in agg.peers()}["hx:9001"]
            true = head_off + worker_off
            # hop 1 error bound: the root's measured digest RTT / 2;
            # hop 2's: the head-side rtt the digest reported / 2
            head = {s.label: s for s in agg.peers()}["hx:9000"]
            bound = head.best_rtt_s * 1e6 / 2 + (2 * hop_delay) * 1e6 / 2
            assert abs(st.clock_offset_us - true) <= bound
        finally:
            agg.stop()

    def test_note_clock_keeps_best_rtt_estimate(self):
        st = tcluster.PeerState("w", "http://w:1")
        t = time.perf_counter()
        tcluster._note_clock(st, 0.010, repr(t * 1e6 - 100.0), t, t + 0.010)
        first = st.clock_offset_us
        # a worse-RTT estimate must not replace the tighter one
        tcluster._note_clock(
            st, 0.100, repr(t * 1e6 - 999999.0), t, t + 0.100
        )
        assert st.clock_offset_us == first
        # a better-RTT estimate does
        tcluster._note_clock(st, 0.001, repr(t * 1e6 - 100.0), t, t + 0.001)
        assert st.best_rtt_s == 0.001


# ---------------------------------------------------------------------------
# sampled-matrix rotation properties (direct, no transport)
# ---------------------------------------------------------------------------


class TestSampledRotation:
    def _agg_with_rows(self, monkeypatch, k=12, rot=4):
        monkeypatch.setenv("KF_AGG_HIER_MIN_PEERS", "4")
        monkeypatch.setenv("KF_AGG_LINK_ROTATION_SWEEPS", str(rot))
        monkeypatch.setenv("KF_AGG_LINK_TOP_EDGES", "2")
        agg = tcluster.TelemetryAggregator(
            interval=5.0, registry=metrics.Registry(),
            fetch=lambda *a: (_ for _ in ()).throw(OSError("unused")),
        )
        targets = [(f"w{i:02d}", f"http://h:{9000 + i}") for i in range(k)]
        agg.set_peers(targets)
        agg._scale = True
        for st in agg.peers():
            st.links = {
                f"w{(int(st.label[1:]) + 1) % k:02d}": {"bw": 1e8}
            }
        return agg

    def test_every_row_within_rotation_window(self, monkeypatch):
        k, rot = 12, 4
        agg = self._agg_with_rows(monkeypatch, k=k, rot=rot)
        try:
            windows = []
            for _ in range(2 * rot):
                agg._ingest_links_sampled(agg.peers())
                windows.append(set(agg._ingested_links))
            labels = {st.label for st in agg.peers()}
            # any rot consecutive sweeps cover every row
            for i in range(rot, len(windows) + 1):
                union = set().union(*windows[i - rot:i])
                assert union >= labels
        finally:
            agg.stop()

    def test_slowest_edges_never_sampled_out(self, monkeypatch):
        agg = self._agg_with_rows(monkeypatch, k=12, rot=4)
        try:
            slow = {s.label: s for s in agg.peers()}["w03"]
            slow.links = {"w04": {"bw": 5.0}}
            for sweep in range(8):
                agg._ingest_links_sampled(agg.peers())
                if any(e["src"] == "w03" for e in agg._slow_edges):
                    break
            # once retained, its source re-ingests EVERY sweep
            for _ in range(6):
                agg._ingest_links_sampled(agg.peers())
                assert "w03" in agg._ingested_links
                assert agg._slow_edges[0]["src"] == "w03"
        finally:
            agg.stop()

    def test_departed_peer_row_evicted(self, monkeypatch):
        agg = self._agg_with_rows(monkeypatch, k=12, rot=4)
        try:
            for _ in range(4):
                agg._ingest_links_sampled(agg.peers())
            assert "w05" in agg._link_cache
            survivors = [
                (st.label, st.url) for st in agg.peers()
                if st.label != "w05"
            ]
            agg.set_peers(survivors)
            agg._ingest_links_sampled(agg.peers())
            assert "w05" not in agg._link_cache
            assert all(e["src"] != "w05" for e in agg._slow_edges)
        finally:
            agg.stop()


# ---------------------------------------------------------------------------
# ?since delta semantics across ring wraparound
# ---------------------------------------------------------------------------


class TestDeltaSince:
    def test_steptrace_since_across_wraparound(self):
        store = tsteptrace.StepStore(keep=4)
        cursor = 0
        delivered = []
        for batch in range(4):
            # 3 new rounds per scrape against a keep=4 ring
            for r in range(batch * 3 + 1, batch * 3 + 4):
                rec = store.begin_step(0, r)
                rec.finish(flush_wait_s=0.0, busy_s=0.01)
            doc = store.export(since=cursor)
            assert doc["next_since"] >= cursor
            cursor = doc["next_since"]
            delivered.extend(
                (t["epoch"], t["round"]) for t in doc["timelines"]
            )
        # exactly-once for everything still in the ring at scrape time:
        # no duplicates even though the ring wrapped repeatedly
        assert len(delivered) == len(set(delivered))
        assert delivered == sorted(delivered)
        # and a cursor re-read ships nothing new
        assert store.export(since=cursor)["timelines"] == []

    def test_steptrace_seq_not_in_merged_lanes(self):
        store = tsteptrace.StepStore(keep=4)
        rec = store.begin_step(0, 1)
        rec.finish(flush_wait_s=0.0, busy_s=0.01)
        doc = store.export(since=0)
        assert doc["timelines"][0]["seq"] == 1
        aligned = tsteptrace.align_timeline(doc["timelines"][0], 0.0)
        assert "seq" not in aligned

    def test_audit_since_wraparound_and_annotate(self, monkeypatch):
        monkeypatch.setattr(audit, "MAX_RECORDS", 4)
        audit.clear()
        base = audit.next_since()
        cursor = base
        got = {}
        for batch in range(3):
            for i in range(3):
                audit.record_event("resize_probe", trigger=f"b{batch}i{i}")
            for rec in audit.records(since=cursor):
                # stable identity: seq never re-stamped, so a record
                # arrives at most once per mutation
                assert rec.seq not in got
                got[rec.seq] = rec.trigger
            cursor = audit.next_since()
        # everything still in the bounded ring was delivered
        ring = {r.seq: r.trigger for r in audit.records()}
        assert set(ring).issubset(got)
        assert all(got[s] == t for s, t in ring.items())
        # annotate re-stamps useq: the record re-ships past the cursor
        assert audit.records(since=cursor) == []
        assert audit.annotate_last("resize_probe", note="late")
        again = audit.records(since=cursor)
        assert len(again) == 1
        assert again[0].detail["note"] == "late"
        assert again[0].seq in got  # same identity, new cursor stamp
        audit.clear()

    def test_decisions_since_reships_mutations(self):
        led = tdecisions.DecisionLedger(keep=4, window=2, settle=1)
        for _ in range(3):  # baseline window — else the record never closes
            led.note_step(0.10)
        led.open("strategy_switch", peer="w0", trigger="test",
                 predicted_gain=1.2)
        doc = led.export(since=0)
        assert len(doc["decisions"]) == 1
        cursor = doc["next_since"]
        assert led.export(since=cursor)["decisions"] == []
        # closing the record mutates it -> re-stamped past the cursor
        for _ in range(8):
            led.note_step(0.05)
        doc2 = led.export(since=cursor)
        assert len(doc2["decisions"]) == 1
        assert doc2["decisions"][0]["seq"] == doc["decisions"][0]["seq"]

    def test_flat_delta_cursors_via_aggregator(self, monkeypatch):
        """KF_AGG_DELTA=on in flat mode: _fetch_all sends each peer's
        stored cursor and merged steps accumulate across delta scrapes
        (the pending pool releases held-back rounds)."""
        monkeypatch.setenv("KF_AGG_DELTA", "on")
        stores = {
            f"w{i}": tsteptrace.StepStore(keep=8) for i in range(2)
        }
        since_seen = []

        def fetch(base_url, path, timeout):
            label = "w" + base_url.rsplit(":", 1)[1][-1]
            endpoint, _, query = path.partition("?")
            since = None
            if query.startswith("since="):
                since = int(query[6:])
                since_seen.append((label, since))
            if endpoint == "/steptrace":
                doc = stores[label].export(peer=label, since=since)
                return json.dumps(doc).encode(), {}
            raise OSError(f"404 {endpoint}")

        agg = tcluster.TelemetryAggregator(
            interval=5.0, registry=metrics.Registry(), fetch=fetch
        )
        agg.set_peers([
            ("w0", "http://h:9000"), ("w1", "http://h:9001"),
        ])
        try:
            for r in (1, 2):
                for s in stores.values():
                    rec = s.begin_step(0, r)
                    rec.finish(flush_wait_s=0.0, busy_s=0.01)
            agg._refresh_steps()
            assert [s["round"] for s in agg.cluster_steps()["steps"]] == [1]
            # second scrape is cursored: only round 3 ships, and the
            # pool releases round 2 (held back until a newer round)
            for s in stores.values():
                rec = s.begin_step(0, 3)
                rec.finish(flush_wait_s=0.0, busy_s=0.01)
            agg._refresh_steps()
            assert since_seen[-2:] == [("w0", 2), ("w1", 2)]
            assert [s["round"] for s in agg.cluster_steps()["steps"]] == [1, 2]
        finally:
            agg.stop()


# ---------------------------------------------------------------------------
# flat mode: byte-identical to the pre-scale merges
# ---------------------------------------------------------------------------


class TestFlatContract:
    def test_k4_flat_merges_byte_identical(self, monkeypatch):
        monkeypatch.setenv("KF_AGG_HIER_MIN_PEERS", "32")
        fleet = Fleet(hosts=2, per_host=2, neighbors=2)
        agg = _mk_agg(fleet)
        try:
            health = agg.scrape_once()
            assert health["plane"]["mode"] == "flat"
            # no digest probes, no delta cursors below the threshold
            assert fleet.calls[tcluster.HOST_DIGEST_PATH] == 0
            assert fleet.since_seen == {}
            # links: exactly the historical merge of the scraped rows
            doc = agg.cluster_links()
            assert doc.pop("plane")["mode"] == "flat"
            expected = tlink.merge_matrix(
                {st.label: st.links for st in agg.peers()}
            )
            for key, val in expected.items():
                assert doc[key] == val
            assert "row_age_s" not in doc and "coverage" not in doc
            # metrics: exactly the historical federation (worker pages
            # + the aggregator's own registry)
            pages = [
                (st.label, st.metrics_text) for st in sorted(
                    agg.peers(), key=lambda s: s.label
                )
            ]
            pages.append((None, agg.registry.render()))
            assert agg.cluster_metrics() == promparse.merge_expositions(
                pages
            )
        finally:
            agg.stop()

    def test_endpoint_staleness_tracked_per_plane(self, monkeypatch):
        """ISSUE 18 fix: a peer failing ONE endpoint mid-sweep reads as
        stale on THAT plane in health, not silently current."""
        monkeypatch.setenv("KF_AGG_HIER_MIN_PEERS", "0")
        fleet = Fleet(hosts=1, per_host=2, neighbors=1)
        broken = fleet.targets[1][0]
        real_fetch = fleet.fetch

        def fetch(base_url, path, timeout):
            if (
                fleet._label(base_url) == broken
                and path.startswith("/steptrace")
            ):
                raise OSError("boom")
            return real_fetch(base_url, path, timeout)

        agg = tcluster.TelemetryAggregator(
            interval=5.0, registry=metrics.Registry(), fetch=fetch
        )
        agg.set_peers(fleet.targets)
        try:
            agg.scrape_once()
            peers = agg.cluster_health()["peers"]
            assert peers[broken]["stale_endpoints"] == ["/steptrace"]
            ok = fleet.targets[0][0]
            assert peers[ok]["stale_endpoints"] is None
        finally:
            agg.stop()


# ---------------------------------------------------------------------------
# overload backoff + self-observability
# ---------------------------------------------------------------------------


class TestBackoff:
    def test_overload_backs_off_and_recovers(self, monkeypatch):
        monkeypatch.setenv("KF_AGG_HIER_MIN_PEERS", "2")
        monkeypatch.setenv("KF_AGG_MAX_BACKOFF", "4.0")
        audit.clear()
        fleet = Fleet(hosts=2, per_host=1, neighbors=1, delay_s=0.2,
                      serve_digests=False)
        agg = _mk_agg(fleet, interval=0.05)
        try:
            agg.scrape_once()
            assert agg._backoff == 2.0
            assert agg.effective_interval() == pytest.approx(0.1)
            events = audit.records("aggregator_overload")
            assert len(events) == 1
            d = events[0].detail
            assert d["sweep_s"] > d["interval_s"] == 0.05
            assert d["peers"] == 2
            # envelope reflects the widened cadence
            env = agg.plane_envelope()
            assert env["effective_interval_s"] == pytest.approx(0.1)
            # recovery: a sweep under half the interval halves the backoff
            # away; on a loaded machine not every sweep of two peers is
            # that fast (25 ms), so sweep until one is
            fleet.delay_s = 0.0
            deadline = time.monotonic() + 60.0
            while agg._backoff > 1.0 and time.monotonic() < deadline:
                agg.scrape_once()
            assert agg._backoff == 1.0
        finally:
            agg.stop()
            audit.clear()

    def test_flat_mode_never_backs_off(self, monkeypatch):
        monkeypatch.setenv("KF_AGG_HIER_MIN_PEERS", "32")
        audit.clear()
        fleet = Fleet(hosts=2, per_host=1, neighbors=1, delay_s=0.1)
        agg = _mk_agg(fleet, interval=0.01)
        try:
            agg.scrape_once()
            assert agg._backoff == 1.0
            assert audit.records("aggregator_overload") == []
        finally:
            agg.stop()


# ---------------------------------------------------------------------------
# consumers: info top plane line, ReplanPolicy staleness gate
# ---------------------------------------------------------------------------


class TestPlaneConsumers:
    def test_info_top_renders_plane_line(self):
        from kungfu_tpu.info.__main__ import render_top

        health = {
            "peers": {}, "stragglers": [],
            "plane": {
                "mode": "hier", "interval_s": 5.0,
                "effective_interval_s": 10.0, "sweep_seconds": 12.5,
                "sweep_age_s": 1.0, "scraped_peers": 250,
                "stale_peers": ["h01:9003"],
                "oldest_link_row_age_s": 33.0,
            },
        }
        out = render_top(health)
        line = out.splitlines()[1]
        assert "plane: hier" in line
        assert "sweep 12.50s/10s OVERLOADED" in line
        assert "250 scraped" in line
        assert "stale: h01:9003" in line
        assert "oldest link row 33s" in line
        # the real envelope ships stale_peers as a COUNT
        health["plane"]["stale_peers"] = 3
        assert "3 stale" in render_top(health).splitlines()[1]
        health["plane"]["stale_peers"] = 0
        assert "stale" not in render_top(health).splitlines()[1]
        # no envelope (pre-scale health doc): no plane line at all
        out = render_top({"peers": {}, "stragglers": []})
        assert "plane:" not in out

    def test_replan_policy_withholds_vote_on_stale_rows(self):
        from kungfu_tpu.policy import PolicyContext, ReplanPolicy

        class Sess:
            size = 3

            def __init__(self):
                self.wants = []

            def check_replan(self, want=True, min_gain=1.05, tag=""):
                self.wants.append(bool(want))
                return None

        sess = Sess()
        pol = ReplanPolicy(interval_steps=1, patience=1,
                           session_supplier=lambda: sess,
                           max_row_age_s=10.0)
        ctx = PolicyContext(batch_size=1)
        ctx.metrics["step/critical_edge"] = "b:2"
        ctx.metrics["links/oldest_row_age_s"] = 99.0
        ctx.step = 1
        pol.after_step(ctx)
        # streak >= patience, but the matrix is stale: vote withheld,
        # the lockstep check still ran
        assert sess.wants == [False]
        assert ctx.metrics["replan/vote_withheld_stale_links"] == 99.0
        # fresh rows: the vote goes through
        ctx.metrics["links/oldest_row_age_s"] = 1.0
        ctx.step = 2
        pol.after_step(ctx)
        assert sess.wants == [False, True]
        # gate disabled (knob 0): age is ignored
        pol0 = ReplanPolicy(interval_steps=1, patience=1,
                            session_supplier=lambda: sess,
                            max_row_age_s=0.0)
        ctx.metrics["links/oldest_row_age_s"] = 99.0
        ctx.step = 3
        pol0._streak = 5
        pol0._edge = "b:2"
        pol0.after_step(ctx)
        assert sess.wants == [False, True, True]

    def test_default_max_row_age_from_knob(self, monkeypatch):
        from kungfu_tpu.policy import ReplanPolicy

        monkeypatch.setenv("KF_AGG_LINK_MAX_AGE_S", "123.5")
        assert ReplanPolicy().max_row_age_s == 123.5
