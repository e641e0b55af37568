"""kfrun — the per-host launcher CLI.

Capability parity: srcs/go/cmd/kungfu-run (app/kungfu-run.go:18-117) +
runner/flags.go:30-145:
  kfrun -np 4 python3 train.py              # simple run, localhost
  kfrun -np 4 -H h1:2,h2:2 ...              # multi-host plan (this host's
                                            # workers only; start kfrun per host)
  kfrun -w -config-server URL ...           # elastic watch mode
  kfrun -np 4 -auto-recover 10s ...         # failure auto-recovery
  kfrun -builtin-config-port 9100 ...       # embedded config server
  kfrun -np 4 -devices-per-host 4 ...       # one chip per worker (runner/env.py
                                            # derives each worker's libtpu env)

The runner package imports no jax, here or in any module it loads: a
chip belongs to one process at a time, and that process must be a
worker. A runner that touched the backend would hold the chips its
workers were started to open.
"""

from __future__ import annotations

import argparse
import os
import signal
import socket
import sys
import time
from typing import List, Optional

from kungfu_tpu.base.strategy import DEFAULT_STRATEGY, Strategy
from kungfu_tpu.plan.cluster import Cluster
from kungfu_tpu.plan.hostspec import HostList, parse_hostfile
from kungfu_tpu.plan.peer import PeerID, PeerList
from kungfu_tpu.runner import env as kfenv
from kungfu_tpu.runner.proc import WorkerProc, run_all

DEFAULT_RUNNER_PORT = 38080


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        "kfrun", description="TPU-native KungFu launcher", allow_abbrev=False
    )
    p.add_argument("-np", type=int, default=1, help="number of workers")
    p.add_argument("-H", dest="hosts", default="", help="host list ip:slots[:pub],...")
    p.add_argument("-hostfile", default="", help="hostfile path")
    p.add_argument("-self", dest="self_host", default="", help="this host's address")
    p.add_argument("-platform", default="",
                   help="self-discover hosts: tpu-vm | gce | auto "
                        "(parity: platforms/modelarts)")
    p.add_argument("-strategy", default="AUTO", help=f"one of {[s.name for s in Strategy]}")
    p.add_argument("-port-range", default="38000-38999")
    p.add_argument("-runner-port", type=int, default=DEFAULT_RUNNER_PORT)
    p.add_argument("-w", "--watch", action="store_true", help="elastic watch mode")
    p.add_argument("-config-server", default="", help="config server URL")
    p.add_argument("-builtin-config-port", type=int, default=-1,
                   help="embed a config server on this port (0 = ephemeral)")
    p.add_argument("-elastic-mode", default="", choices=["", "reload"])
    p.add_argument("-auto-recover", default="", help="e.g. 10s: heartbeat auto-recovery")
    p.add_argument("-monitor-port", type=int, default=7756,
                   help="heartbeat monitor port (0 = ephemeral)")
    p.add_argument("-monitor-peers", default="",
                   help="all runners' monitor host:port list (default: "
                        "every runner host on -monitor-port)")
    p.add_argument("-warm-spares", type=int, default=1,
                   help="standby workers kept warm per runner in -w mode "
                        "(0 disables); activation replaces cold joiner "
                        "spawn+import during an elastic grow")
    p.add_argument("-standby-preload", default="auto",
                   help="comma-separated modules standbys pre-import; "
                        "'auto' (default) pre-imports the device stack "
                        "(jax) since this framework's agents are jax-"
                        "based; 'none' disables")
    p.add_argument("-use-affinity", action="store_true",
                   help="pin each local worker to a disjoint, NUMA-aligned "
                        "CPU slice (parity: KUNGFU_USE_AFFINITY)")
    p.add_argument("-devices-per-host", type=int, default=0,
                   help="partition this many chip ids among local workers "
                        "(each opens only its own through libtpu's "
                        "per-process variables; 0 = no pinning)")
    p.add_argument("-debug-port", type=int, default=-1,
                   help="HTTP endpoint: Stage dumps + /cluster/{metrics,"
                        "trace,health,links} telemetry (0 = ephemeral)")
    p.add_argument("-logdir", default="")
    p.add_argument("-q", "--quiet", action="store_true")
    p.add_argument("-delay", type=float, default=0.0)
    p.add_argument("-timeout", type=float, default=0.0, help="kill workers after this many seconds")
    p.add_argument("cmd", nargs=argparse.REMAINDER, help="worker command")
    return p


def infer_self_host(hosts: HostList) -> str:
    """Pick this host's address from the host list (parity:
    runner.InferSelfIPv4; hostname/IP matching instead of NIC scanning)."""
    candidates = {h.host for h in hosts}
    if "127.0.0.1" in candidates or "localhost" in candidates:
        return "127.0.0.1" if "127.0.0.1" in candidates else "localhost"
    names = {socket.gethostname(), socket.getfqdn()}
    try:
        names.add(socket.gethostbyname(socket.gethostname()))
    except OSError:
        pass
    for h in hosts:
        if h.host in names:
            return h.host
    raise SystemExit(f"cannot find self among hosts {sorted(candidates)}; use -self")


def parse_port_range(s: str):
    a, _, b = s.partition("-")
    return (int(a), int(b or a))


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    cmd = args.cmd
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        print("kfrun: no worker command given", file=sys.stderr)
        return 2

    try:
        if args.platform:
            from kungfu_tpu.runner.platform import detect

            pc = detect(args.platform)
            if pc is None:
                print(f"kfrun: platform {args.platform!r} not detected", file=sys.stderr)
                return 2
            import dataclasses as _dc

            slots = max(1, -(-args.np // len(pc.hosts)))  # spread np over hosts
            hosts = HostList(_dc.replace(h, slots=slots) for h in pc.hosts)
            if not args.self_host:
                args.self_host = pc.self_host
        elif args.hostfile:
            with open(args.hostfile) as f:
                hosts = parse_hostfile(f.read())
        elif args.hosts:
            hosts = HostList.parse(args.hosts)
        else:
            hosts = HostList.parse(f"127.0.0.1:{args.np}")

        port_range = parse_port_range(args.port_range)
        workers = hosts.gen_peer_list(args.np, port_range)
        runners = hosts.gen_runner_list(args.runner_port)
        cluster = Cluster(runners=runners, workers=workers)
        cluster.validate()
        self_host = args.self_host or infer_self_host(hosts)
        strategy = Strategy.parse(args.strategy)
        # device-slot share is sized by host CAPACITY, stable across resizes
        args.host_capacity = next(
            (h.slots for h in hosts if h.host == self_host), 1
        )
        if args.devices_per_host not in (0,) + kfenv.HOST_CHIPS:
            raise ValueError(
                f"-devices-per-host {args.devices_per_host}: the libtpu "
                f"topology is known only for hosts of {kfenv.HOST_CHIPS} chips"
            )
        if 0 < args.devices_per_host < args.host_capacity:
            # at full capacity every local worker needs >= 1 chip, or a
            # later elastic grow would exhaust the watcher's slot pool
            raise ValueError(
                f"-devices-per-host {args.devices_per_host} < host capacity "
                f"{args.host_capacity}: not every worker could get a chip"
            )
    except (ValueError, OSError) as e:
        print(f"kfrun: {e}", file=sys.stderr)
        return 2

    # flight-recorder run dir (ISSUE 3): minted once per run and
    # inherited by every worker via the environment, so all the peer
    # journals and the runner's postmortems land under one directory.
    # An operator-set KF_TELEMETRY_DIR wins; the default base is pruned
    # so unattended loops don't grow /tmp forever.
    from kungfu_tpu.telemetry import flight

    from kungfu_tpu import knobs

    if not knobs.raw(flight.DIR_ENV):
        flight.prune_runs()
        os.environ[flight.DIR_ENV] = flight.default_run_dir()

    config_server_url = args.config_server
    builtin_server = None
    if args.builtin_config_port >= 0:
        from kungfu_tpu.elastic.configserver import ConfigServer

        builtin_server = ConfigServer(args.builtin_config_port, cluster)
        builtin_server.start()
        config_server_url = f"http://{self_host}:{builtin_server.port}/config"

    if args.delay:
        time.sleep(args.delay)

    if args.debug_port >= 0 and not args.watch:
        print(
            "kfrun: -debug-port (Stage dumps + /cluster telemetry) needs "
            "watch mode (-w); ignoring",
            file=sys.stderr,
        )

    try:
        if args.auto_recover and not args.watch:
            from kungfu_tpu.runner.monitored import monitored_run

            return monitored_run(args, cmd, cluster, self_host, strategy)
        if args.watch:
            from kungfu_tpu.runner.watch import watch_run

            return watch_run(args, cmd, cluster, self_host, strategy, config_server_url)
        return simple_run(args, cmd, cluster, self_host, strategy, config_server_url)
    finally:
        if builtin_server:
            builtin_server.stop()


def make_one_worker_proc(
    args, cmd, cluster: Cluster, worker: PeerID, self_host: str,
    strategy: Strategy, config_server_url: str = "", version: int = 0,
    progress: int = 0, device_slots=None, resize_marks=None,
    chip_coords=None,
) -> WorkerProc:
    rank = cluster.workers.rank(worker)
    spawn_ts = time.time()
    env = kfenv.worker_env(
        self_id=worker,
        peers=cluster.workers,
        runners=cluster.runners,
        parent=PeerID(self_host, args.runner_port),
        cluster_version=version,
        strategy=strategy,
        config_server=config_server_url,
        elastic_mode=args.elastic_mode,
        init_progress=progress,
        device_slots=device_slots,
        host_devices=args.devices_per_host,
        port_range=parse_port_range(args.port_range),
        # a reload's marks go on with this worker's own: one reading
        # serves KF_SPAWN_TS and the pause's `t_spawn`
        resize_marks=dict(resize_marks, t_spawn=spawn_ts) if resize_marks else None,
        chip_coords=chip_coords,
    )
    env["KF_LOG_PREFIX"] = f"{rank}/{len(cluster.workers)}"
    env["KF_SPAWN_TS"] = str(spawn_ts)
    return WorkerProc(
        name=f"{rank}/{len(cluster.workers)}",
        argv=list(cmd),
        env=env,
        rank=rank,
        logdir=args.logdir,
        quiet=args.quiet,
    )


def make_worker_procs(
    args, cmd, cluster: Cluster, self_host: str, strategy: Strategy,
    config_server_url: str = "", version: int = 0, progress: int = 0,
) -> List[WorkerProc]:
    local = [w for w in cluster.workers if w.host == self_host]
    slot_parts: List[Optional[list]] = [None] * len(local)
    n_dev = getattr(args, "devices_per_host", 0)
    if n_dev > 0 and local:
        from kungfu_tpu.runner.slots import partition

        if len(local) > n_dev:
            raise SystemExit(
                f"kfrun: {len(local)} local workers but only {n_dev} device slots"
            )
        # static membership (simple/monitored runs): rank-major stripes
        slot_parts = partition(n_dev, len(local))
    cpu_parts: List[Optional[list]] = [None] * len(local)
    if getattr(args, "use_affinity", False) and local:
        from kungfu_tpu.runner.affinity import plan_affinity

        cpu_parts = plan_affinity(len(local))
    procs = [
        make_one_worker_proc(
            args, cmd, cluster, w, self_host, strategy, config_server_url,
            version, progress, device_slots=slot_parts[i],
        )
        for i, w in enumerate(local)
    ]
    for p, cpus in zip(procs, cpu_parts):
        p.cpus = cpus
    return procs


def simple_run(args, cmd, cluster, self_host, strategy, config_server_url="") -> int:
    procs = make_worker_procs(args, cmd, cluster, self_host, strategy, config_server_url)
    if args.timeout:
        def on_alarm(sig, frame):
            for p in procs:
                p.kill()
        signal.signal(signal.SIGALRM, on_alarm)
        signal.alarm(int(args.timeout))
    codes = run_all(procs)
    bad = [c for c in codes if c != 0]
    if bad:
        print(f"kfrun: {len(bad)}/{len(codes)} workers failed", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
