"""Peer: the worker-side runtime root.

Capability parity: srcs/go/kungfu/peer/peer.go:27-308 — every worker embeds
the whole host-side communication runtime: a transport server+client, the
current cluster (version'd), a HostSession cache, and the elastic-resize
protocol (consensus on a proposed cluster, notify runners, bump version,
rebuild session, barrier).

TPU mapping: the Peer manages the HOST plane only. Device work happens in
DeviceSession (kungfu_tpu.parallel.mesh); on a resize the worker process is
expected to rebuild its DeviceSession/mesh (reload-style), which is the
TPU-native elastic mode (ICI mesh shape is fixed per slice — SURVEY §7).
"""

from __future__ import annotations

import json
import threading
import time
import urllib.request
from typing import Callable, Optional, Tuple

from kungfu_tpu.base.strategy import Strategy
from kungfu_tpu.collective.host_session import HostSession
from kungfu_tpu.plan.cluster import Cluster
from kungfu_tpu.plan.peer import PeerID, PeerList
from kungfu_tpu.runner import env as kfenv
from kungfu_tpu.store.versioned import BlobStore
from kungfu_tpu.transport.client import Client
from kungfu_tpu.transport.handlers import (
    CollectiveEndpoint,
    ControlEndpoint,
    P2PEndpoint,
    QueueEndpoint,
)
from kungfu_tpu.transport.message import ConnType, Flags, Message
from kungfu_tpu.transport.server import Server
from kungfu_tpu.telemetry import tracing as trace
from kungfu_tpu.utils import log
from kungfu_tpu.utils.stall import stall_detect

_default_peer: Optional["Peer"] = None
_default_lock = threading.Lock()


def _ms(span) -> float:
    """A closed span's duration as a phase of `last_resize_phases`."""
    return round(span.duration * 1e3, 1)


def get_default_peer() -> "Peer":
    """Process-wide singleton (parity: Peer::GetDefault, peer.hpp)."""
    global _default_peer
    with _default_lock:
        if _default_peer is None:
            with trace.span("worker.parse_config"):
                cfg = kfenv.parse_config_from_env()
            with trace.span("worker.peer_init"):
                _default_peer = Peer(cfg)
            _default_peer.start()
        return _default_peer


def finalize_default_peer() -> None:
    global _default_peer
    with _default_lock:
        if _default_peer is not None:
            _default_peer.stop()
            _default_peer = None


class Peer:
    def __init__(self, config: kfenv.WorkerConfig):
        self.config = config
        self.self_id = config.self_id
        self.cluster_version = config.cluster_version
        self.detached = False
        self._peers = config.peers
        self._session: Optional[HostSession] = None
        self._session_lock = threading.RLock()
        self._updated = True
        # number of cluster epochs this PROCESS has lived through; 1 after
        # startup, >1 once it survives a delta resize. Lets elastic state
        # sync pick a provably surviving broadcast root.
        self.epoch_count = 0
        # per-phase wall-clock (ms) of the most recent resize, as seen by
        # this (surviving) peer: wait_config / consensus / notify / update
        # (update = reconnect + new-session barrier, i.e. joiner-bounded),
        # each the duration of its `resize.*` span. A worker that a reload
        # started holds the whole pause's parts here once its first step
        # has ended (elastic/state.pause_parts).
        # Parity: the reference's ResizeProfiler phase breakdown.
        self.last_resize_phases: dict = {}
        # where the chips of this world's one-chip workers sit in the ICI
        # grid, by chip id (initialize_device_plane() gathers it); a
        # reload's Stage hands it to the runners for the next world
        self.chip_coords: dict = {}
        # KF700: config-poll/reload consensus rounds consumed, PER cluster
        # version — every member of a session epoch runs these consensus
        # rounds in lockstep (an allreduce needs all of them), so the
        # (version, rounds-this-version) pair agrees cluster-wide where a
        # process-lifetime counter would diverge for joiners
        self._cfg_consensus_seq: dict = {}

        self.store = BlobStore()
        self.client = Client(self.self_id, use_unix=not config.single_process)
        self.server = Server(self.self_id, use_unix=not config.single_process)
        self.collective = CollectiveEndpoint()
        self.queue = QueueEndpoint()
        self.p2p = P2PEndpoint(self.store, self.client, self.self_id)
        self.server.register(ConnType.COLLECTIVE, self.collective.handle)
        self.server.register(ConnType.QUEUE, self.queue.handle)
        self.server.register(ConnType.PEER_TO_PEER, self.p2p.handle)

    # ------------------------------------------------------------------
    def start(self) -> None:
        import os

        from kungfu_tpu import knobs

        spawn_ts = knobs.raw("KF_SPAWN_TS")
        if spawn_ts:
            # joiner-readiness latency: runner spawn (or standby
            # activation) -> host plane up; the term that bounds the
            # survivors' rebuild barrier during an elastic grow
            try:
                startup = time.time() - float(spawn_ts)
                trace.record("worker.startup", startup)
                log.info("worker ready %.0f ms after spawn", startup * 1e3)
            except ValueError:
                pass
        if not self.config.single_process:
            with trace.span("worker.start.server"):
                self.server.start()
        self._start_telemetry_server()
        self._start_flight_recorder()
        with trace.span("worker.start.update"):
            self._update_to(self._peers)

    def _start_telemetry_server(self) -> None:
        """Expose /metrics + /trace + /audit on self.port+10000 when any
        telemetry is on (parity: peer/peer.go:96-104, generalized from the
        old /metrics-only server in monitor/net.py)."""
        self.metrics_server = None
        from kungfu_tpu import telemetry
        from kungfu_tpu.monitor import net as _net

        want = _net.enabled() or telemetry.features()
        if want and not self.config.single_process:
            # materialize the singleton so transport counters mirror into
            # the registry this server renders
            _net.get_monitor()
            try:
                from kungfu_tpu.telemetry.http import TelemetryServer

                self.metrics_server = TelemetryServer(self.self_id.port + 10000)
                self.metrics_server.start()
            except (OSError, OverflowError) as e:
                # OverflowError: peer port within 10000 of 65535
                log.warn("telemetry server failed to start: %s", e)

    def _start_flight_recorder(self) -> None:
        """Durable flight recorder (ISSUE 3): journal telemetry
        snapshots to disk so a SIGKILL'd/OOM'd worker leaves a black
        box. kfrun injects KF_TELEMETRY_DIR, which turns it on; bare
        in-process peers (tests, single_process) stay off unless asked."""
        self.flight_recorder = None
        if self.config.single_process:
            return
        from kungfu_tpu.telemetry import flight

        self.flight_recorder = flight.start_recorder(peer=str(self.self_id))

    def stop(self) -> None:
        with self._session_lock:
            if self._session is not None:
                self._session.close(timeout=5.0)
        self.server.stop()
        self.client.close()
        if getattr(self, "metrics_server", None) is not None:
            # clean shutdown on peer exit: close the listening socket too
            self.metrics_server.stop()
        if getattr(self, "flight_recorder", None) is not None:
            from kungfu_tpu.telemetry import flight

            flight.stop_recorder(reason="peer_stop")
            self.flight_recorder = None

    # ------------------------------------------------------------------
    @property
    def rank(self) -> int:
        return self.current_session().rank

    @property
    def size(self) -> int:
        return self.current_session().size

    def current_session(self) -> HostSession:
        with self._session_lock:
            if self._session is None:
                raise RuntimeError("peer not started")
            return self._session

    def _update_to(self, peers: PeerList) -> bool:
        """Rebuild the session for a new peer list; returns False if self is
        not a member (detached). Parity: peer.updateTo (peer.go:148-170)."""
        with self._session_lock:
            old_session = self._session
            if self._session is not None:
                # session-epoch invalidation (ISSUE 10): the old epoch's
                # async scheduler must drain or cancel its in-flight
                # buckets BEFORE the transport token advances and the
                # session is replaced — a walk left running would wedge
                # on fenced messages and could write caller buffers the
                # new epoch already reuses. Detached peers drain too:
                # their epoch ended just as finally.
                with trace.span("resize.drain_scheduler"):
                    self._session.close(timeout=10.0)
            if peers.rank(self.self_id) is None:
                self.detached = True
                # a detached peer is not in the target set, so the
                # election below would clear the role anyway — but it
                # must happen even on this early exit
                self._update_host_role(peers)
                return False
            self.server.set_token(self.cluster_version)
            self.client.set_token(self.cluster_version)
            self.client.reset_connections()
            self._session = HostSession(
                self.config.strategy,
                self.self_id,
                peers,
                self.client,
                self.collective,
                cluster_version=self.cluster_version,
            )
            self._peers = peers
            self.epoch_count += 1
            # decision ledger (ISSUE 15): an engine-mode flip at a
            # session epoch (KF_CONFIG_ASYNC / KF_CONFIG_ZERO resolving
            # differently — env change under `reload`, or `auto`
            # crossing the multi-peer threshold on a resize) is an
            # adaptation like any vote: open its causal record so the
            # paired step windows measure whether it helped
            if old_session is not None:
                from kungfu_tpu.telemetry import decisions as _decisions

                for kind, was, now in (
                    ("async_mode", old_session.async_enabled(),
                     self._session.async_enabled()),
                    ("zero_mode", old_session.zero_enabled(),
                     self._session.zero_enabled()),
                ):
                    if was != now:
                        _decisions.open_decision(
                            kind,
                            peer=str(self.self_id),
                            epoch=self.cluster_version,
                            trigger="session_epoch",
                            old="on" if was else "off",
                            new="on" if now else "off",
                        )
            # link plane: drop estimators for departed destinations —
            # a shed peer's frozen bandwidth estimate must not keep
            # winning links/min_bw or walk-efficiency scoring (runners
            # stay: stable control-plane membership)
            from kungfu_tpu.telemetry import link as tlink

            if tlink.enabled():
                tlink.get_table().prune(
                    list(peers) + list(self.config.runners)
                )
            # host sub-aggregator election (ISSUE 18): at scale the
            # lowest-labelled worker per host pre-merges its siblings'
            # telemetry for the root aggregator; membership changes
            # re-elect deterministically on every peer
            self._update_host_role(peers)
        if not self.config.single_process:
            # fail-fast BEFORE the barrier: the barrier itself walks
            # strategy-dependent graphs, so knob-divergent peers would
            # hang right here instead of raising a named error
            with trace.span("worker.knob_consensus"):
                self._session.check_knob_consensus()
            self._session.barrier(tag=f":v{self.cluster_version}")
        self._updated = True
        return True

    def _update_host_role(self, peers: PeerList) -> None:
        """Recompute this worker's host sub-aggregator election (ISSUE
        18). Never lets a telemetry-plane failure touch the resize
        path: the role is an optimization the root falls back from."""
        if self.config.single_process:
            return
        if getattr(self, "metrics_server", None) is None:
            return  # no telemetry server, nothing to elect for
        try:
            from kungfu_tpu.telemetry import cluster as _cluster

            _cluster.update_host_role(self.self_id, list(peers))
        except Exception as e:  # noqa: BLE001 - telemetry must not break resizes
            log.warn("host telemetry role update failed: %s", e)

    def set_tree(self, fathers) -> None:
        """Install a runtime collective tree on the CURRENT session epoch.

        Parity: SetTree (adaptation.cpp:5-33). The father array indexes
        this epoch's rank space, so it does NOT survive a resize — like the
        reference, a new session reverts to the configured strategy and the
        caller re-probes (api.optimized_tree) if it wants a tuned topology.
        A same-size resize can swap members, so persisting would silently
        apply an MST probed on different machines (ADVICE r2)."""
        self.current_session().set_tree(list(int(f) for f in fathers))

    # ------------------------------------------------------------------
    # elastic resize protocol (parity: peer.go propose/ResizeCluster*)
    # ------------------------------------------------------------------

    def _notify_runners(self, stage: dict) -> None:
        """Send the new Stage to every runner (parity: peer.go:200-214)."""
        payload = json.dumps(stage).encode()
        log.debug("notifying %d runners: v%s", len(self.config.runners), stage.get("Version"))
        for runner in self.config.runners:
            if not self.client.wait_peer(runner, timeout=30):
                raise ConnectionError(f"runner {runner} unreachable")
            self.client.send(runner, "update", payload, ConnType.CONTROL)
            log.debug("notified runner %s", runner)

    def _propose(
        self,
        cluster: Cluster,
        progress: int = 0,
        trigger: str = "explicit",
        pre_phases: Optional[dict] = None,
    ) -> Tuple[bool, bool]:
        """Consensus-check and adopt a new cluster.

        Returns (accepted, keep): keep=False means self is detached.
        Parity: peer.propose (peer.go:181-233) including the safety check —
        peers must agree on the proposed bytes or the resize is rejected.
        `trigger` and `pre_phases` (e.g. the config-server wait) feed the
        telemetry resize audit record.
        """
        sess = self.current_session()
        attrs = self._resize_attrs("delta", cluster)
        with trace.span("resize.consensus", **attrs) as sp:
            agreed = sess.bytes_consensus(
                cluster.to_bytes(), f":propose:v{self.cluster_version}"
            )
        if not agreed:
            return False, True
        if self._peers == cluster.workers:
            return True, True  # no change
        old_peers = self._peers
        self.last_resize_phases = dict(pre_phases or {})
        self.last_resize_phases["consensus_ms"] = _ms(sp)
        stage = {
            "Version": self.cluster_version + 1,
            "Progress": progress,
            "Cluster": cluster.to_json(),
        }
        if sess.rank == 0 and self.config.runners:
            with trace.span("resize.notify", **attrs) as sp:
                self._notify_runners(stage)
            self.last_resize_phases["notify_ms"] = _ms(sp)
        # all peers advance the version together (they all ran the consensus)
        self.cluster_version += 1
        with trace.span("resize.update", **attrs) as sp:
            keep = self._update_to(cluster.workers)
        self.last_resize_phases["update_ms"] = _ms(sp)
        from kungfu_tpu.telemetry import audit as _audit

        _audit.record_resize(
            peer=str(self.self_id),
            cluster_version=self.cluster_version,
            trigger=trigger,
            old_peers=list(old_peers),
            new_peers=list(cluster.workers),
            phases_ms=self.last_resize_phases,
            progress=progress or None,
            detached=not keep,
        )
        if keep:
            # decision ledger (ISSUE 15): the resize is the capacity
            # decision ROADMAP item 4's autoscaler must trust — open the
            # outcome record on every surviving peer (a detached peer
            # has no post-flip steps to measure)
            from kungfu_tpu.telemetry import decisions as _decisions

            _decisions.open_decision(
                "resize",
                peer=str(self.self_id),
                epoch=self.cluster_version,
                trigger=trigger,
                old_size=len(old_peers),
                new_size=len(cluster.workers),
            )
        log.info(
            "resize v%d: %d -> %d workers (%s)%s",
            self.cluster_version,
            len(old_peers),
            len(cluster.workers),
            trigger,
            "" if keep else " [detached]",
        )
        return True, keep

    def _get_config(self, url: str, attempts: int = 3) -> Optional[Cluster]:
        """GET the desired cluster; a few retries absorb transient server
        blips so a published resize isn't silently dropped by the
        current-cluster fallback in _wait_new_config."""
        for i in range(attempts):
            try:
                with urllib.request.urlopen(url, timeout=5) as resp:
                    return Cluster.loads(resp.read().decode())
            except Exception as e:
                if i + 1 < attempts:
                    time.sleep(0.3)
                else:
                    log.warn("config server unreachable after %d tries "
                             "(%s): %s", attempts, url, e)
        return None

    def _wait_new_config(self, url: str) -> Cluster:
        """Poll the config server until all current peers see the same
        cluster (parity: waitNewConfig, peer.go:242-263). When the server is
        unreachable or has no config, each peer falls back to its CURRENT
        cluster (the reference's "using current config" path) — once all
        peers agree (e.g. the server is down for everyone) the resize
        degrades to a no-op instead of hanging the training loop."""
        sess = self.current_session()
        current = Cluster(runners=self.config.runners, workers=self._peers)
        # KF700: the poll retries back-to-back consensus rounds, so each
        # round gets its own rendezvous name — a slow peer's round r must
        # never consume the lanes of a fast peer's round r+1. Peers
        # iterate in lockstep (bytes_consensus resolves identically
        # cluster-wide), and the per-epoch sequence survives REPEATED
        # calls at the same version (a plain per-call attempt counter
        # would reuse names across calls)
        while True:
            cluster = self._get_config(url) or current
            with stall_detect(f"wait_new_config({url})"):
                if sess.bytes_consensus(
                    cluster.to_bytes(), self._cfg_consensus_name("cfg")
                ):
                    return cluster
            time.sleep(0.2)

    def _cfg_consensus_name(self, kind: str) -> str:
        """Round-stamped rendezvous name for the config-plane consensus
        lanes: `:{kind}:v{version}:{seq}` with seq the count of such
        rounds THIS session epoch has run (all epoch members run them in
        lockstep, so the stamp agrees cluster-wide; a joiner starts the
        new epoch at 0 together with everyone else)."""
        v = self.cluster_version
        seq = self._cfg_consensus_seq.get(v, 0)
        self._cfg_consensus_seq[v] = seq + 1
        return f":{kind}:v{v}:{seq}"

    def resize_cluster_from_url(self) -> Tuple[bool, bool]:
        """(changed, detached). Parity: ResizeClusterFromURL (peer.go:265)."""
        url = self.config.config_server
        if not url:
            return False, False
        sp, cluster = self._wait_new_config_traced(url, "delta")
        if cluster.workers == self._peers:
            return False, False
        # pre_phases rides into _propose so a REJECTED proposal never
        # splices this wait into the previous resize's phase breakdown
        accepted, keep = self._propose(
            cluster,
            trigger="config_server",
            pre_phases={"wait_config_ms": _ms(sp)},
        )
        return accepted, not keep

    def resize_cluster(self, new_size: int) -> Tuple[bool, bool]:
        """Explicit resize to new_size workers (parity: ResizeCluster)."""
        current = Cluster(runners=self.config.runners, workers=self._peers)
        cluster = current.resize(new_size)
        if cluster.workers == self._peers:
            return False, False
        accepted, keep = self._propose(cluster, trigger="explicit")
        return accepted, not keep

    def propose_new_size(self, new_size: int) -> None:
        """Publish a desired size to the config server (rank-agnostic;
        parity: ProposeNewSize -> config-server PUT)."""
        url = self.config.config_server
        if not url:
            raise RuntimeError("no config server configured")
        current = Cluster(runners=self.config.runners, workers=self._peers)
        cluster = current.resize(new_size)
        data = cluster.dumps().encode()
        req = urllib.request.Request(url, data=data, method="PUT")
        with urllib.request.urlopen(req, timeout=5) as resp:
            resp.read()

    def _resize_attrs(self, mode: str, cluster: Optional[Cluster] = None) -> dict:
        """What every span of a resize carries: the mode, the cluster
        version the resize makes, and the sizes on both sides of it."""
        attrs = {
            "mode": mode,
            "version": self.cluster_version + 1,
            "old_size": len(self._peers),
        }
        if cluster is not None:
            attrs["new_size"] = len(cluster.workers)
        return attrs

    def _wait_new_config_traced(self, url: str, mode: str):
        """(`resize.wait_config` span, cluster): the span is the wait's one
        clock, and learns the new size when the wait ends."""
        with trace.span("resize.wait_config", **self._resize_attrs(mode)) as sp:
            cluster = self._wait_new_config(url)
            sp.args["new_size"] = len(cluster.workers)
        return sp, cluster

    def change_cluster(
        self, progress: int, before_notify: Optional[Callable[[], None]] = None
    ) -> Tuple[bool, bool]:
        """Reload-mode resize: every worker exits and the runners relaunch
        from `progress` (parity: ChangeCluster, peer.go:279-291 +
        ElasticModeReload). Returns (changed, detached_all).

        `before_notify` runs on every worker once the new cluster is
        agreed and before the runners hear of it: a runner stops its
        workers as soon as it has the Stage, so whatever must outlive
        this incarnation (a checkpoint) is written there and nowhere
        later. A barrier follows it, so rank 0 tells the runners only
        when every worker's has returned. Its time with the barrier is
        `on_reload_ms`, under `resize.on_reload`.

        The Stage carries `Marks`: the wall time (`time.time()`) of this
        call and the phases up to the notify, for the new workers'
        account of the pause (`elastic/state.pause_parts`). On one host
        every process of a kfrun tree reads the same clock; across hosts
        each mark is its own host's clock, and a part between two marks
        of different hosts holds their offset as well."""
        url = self.config.config_server
        if not url:
            return False, False
        t_propose = time.time()
        sp, cluster = self._wait_new_config_traced(url, "reload")
        if cluster.workers == self._peers:
            return False, False
        phases = {"wait_config_ms": _ms(sp)}
        attrs = self._resize_attrs("reload", cluster)
        sess = self.current_session()
        # KF700: epoch-sequenced — a reload agreement must not rendezvous
        # with an earlier attempt's lanes (repeat change_cluster calls at
        # one version) nor with another epoch's
        with trace.span("resize.consensus", **attrs) as sp:
            agreed = sess.bytes_consensus(
                cluster.to_bytes(), self._cfg_consensus_name("reload")
            )
        if not agreed:
            return False, False
        phases["consensus_ms"] = _ms(sp)
        if before_notify is not None:
            with trace.span("resize.on_reload", **attrs) as sp:
                before_notify()
                sess.barrier(tag=f":on_reload:v{attrs['version']}")
            phases["on_reload_ms"] = _ms(sp)
        stage = {
            "Version": self.cluster_version + 1,
            "Progress": progress,
            "Cluster": cluster.to_json(),
            "Reload": True,
            "Marks": {
                "t_propose": t_propose,
                "mode": "reload",
                "old_size": len(self._peers),
                "phases_ms": dict(phases),
            },
        }
        if self.chip_coords:
            stage["ChipCoords"] = self.chip_coords
        if sess.rank == 0 and self.config.runners:
            with trace.span("resize.notify", **attrs) as sp:
                self._notify_runners(stage)
            phases["notify_ms"] = _ms(sp)
        self.last_resize_phases = phases
        from kungfu_tpu.telemetry import audit as _audit

        _audit.record_resize(
            peer=str(self.self_id),
            cluster_version=self.cluster_version + 1,
            trigger="reload",
            old_peers=list(self._peers),
            new_peers=list(cluster.workers),
            phases_ms=phases,
            progress=progress,
            detached=True,
        )
        # in reload mode every worker detaches; runners restart the world
        self.detached = True
        return True, True
