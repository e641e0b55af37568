"""Worker environment contract.

Capability parity: srcs/go/kungfu/env/envs.go:4-20 + config.go:53-140 —
the runner passes cluster topology to workers via env vars; a worker
started without them becomes a single-process cluster of itself.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Optional, Sequence, Tuple

from kungfu_tpu.base.strategy import DEFAULT_STRATEGY, Strategy
from kungfu_tpu.plan.peer import PeerID, PeerList

SELF_SPEC = "KF_SELF_SPEC"
INIT_PEERS = "KF_INIT_PEERS"
INIT_RUNNERS = "KF_INIT_RUNNERS"
PARENT_ID = "KF_PARENT_ID"
INIT_CLUSTER_VERSION = "KF_INIT_CLUSTER_VERSION"
ALLREDUCE_STRATEGY = "KF_ALLREDUCE_STRATEGY"
CONFIG_SERVER = "KF_CONFIG_SERVER"
ELASTIC_MODE = "KF_ELASTIC_MODE"
INIT_PROGRESS = "KF_INIT_PROGRESS"
DEVICE_SLOTS = "KF_DEVICE_SLOTS"
DEVICE_WORLD = "KF_DEVICE_WORLD"
RESIZE_MARKS = "KF_RESIZE_MARKS"
# tuning (parity: config/config.go:24-67)
ENABLE_MONITORING = "KF_CONFIG_ENABLE_MONITORING"
ENABLE_STALL_DETECTION = "KF_CONFIG_ENABLE_STALL_DETECTION"
LOG_LEVEL = "KF_CONFIG_LOG_LEVEL"

ALL_ENV_NAMES = [
    SELF_SPEC, INIT_PEERS, INIT_RUNNERS, PARENT_ID, INIT_CLUSTER_VERSION,
    ALLREDUCE_STRATEGY, CONFIG_SERVER, ELASTIC_MODE, INIT_PROGRESS,
    DEVICE_SLOTS, DEVICE_WORLD, RESIZE_MARKS, ENABLE_MONITORING,
    ENABLE_STALL_DETECTION, LOG_LEVEL,
]

# libtpu's per-process topology on one host, as the chip accepted it in
# PR 21 (libtpu 0.0.34, TPU v5 lite; the values jax's own multi-process
# TPU test harness uses). A worker holding c chips sees them as the grid
# _CHIP_BOUNDS[c]; k such workers, worker i holding chips [i*c, (i+1)*c)
# of a 4-chip host, join into the process grid _PROCESS_BOUNDS[c, k]
# (chips 0,1 and chips 2,3 are the two columns of the 2x2). libtpu places
# each process by where its chips sit, not by its task id, so
# jax.process_index() is not the rank. Host sizes and layouts not listed
# here have not met the chip and are refused rather than guessed.
# Which chip ids are neighbours differs from one host to the next (PR 54:
# chips 0,1 were a column of the 2x2 on one machine and a row on another,
# and a world of two one-chip workers under "1,2,1" then fails in libtpu's
# mesh build, "duplicate coordinate assignment"), so where the workers of
# an earlier world have said where their chips sit (`chip_coords`), the
# process grid of one-chip workers is made from that and not from the
# table (`_grid_bounds`).
HOST_CHIPS = (1, 4)
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1"}
_PROCESS_BOUNDS = {(1, 4): "2,2,1", (2, 2): "2,1,1", (1, 2): "1,2,1"}


def _grid_bounds(coords) -> Optional[str]:
    """TPU_PROCESS_BOUNDS of one-chip processes whose chips sit at
    `coords` (x, y, z): the grid they fill, or None where they fill none
    (two chips on a diagonal of the 2x2)."""
    xs, ys = {c[0] for c in coords}, {c[1] for c in coords}
    if not len(xs) * len(ys) == len({tuple(c) for c in coords}) == len(coords):
        return None
    return f"{len(xs)},{len(ys)},1"


@dataclasses.dataclass
class WorkerConfig:
    self_id: PeerID
    peers: PeerList
    runners: PeerList
    parent: Optional[PeerID]
    cluster_version: int
    strategy: Strategy
    config_server: str
    elastic_mode: str  # "" (delta) | "reload"
    init_progress: int
    single_process: bool = False
    # chip ids this worker may open (empty = unrestricted); parity:
    # job/gpu_resource.go slot assignment via CUDA_VISIBLE_DEVICES
    device_slots: tuple = ()
    # libtpu variables that place this worker in the ONE device world
    # spanning all workers (empty = the runner described none);
    # initialize_device_plane() applies them before the backend starts
    device_world: dict = dataclasses.field(default_factory=dict)
    # wall-clock marks of the reload that started this worker (proposer's,
    # runner's, and `t_spawn`; runner/watch.Stage.marks); empty for a
    # first incarnation and for a worker no reload started
    resize_marks: dict = dataclasses.field(default_factory=dict)


def parse_config_from_env(environ=None) -> WorkerConfig:
    env = environ if environ is not None else os.environ
    self_spec = env.get(SELF_SPEC, "")
    if not self_spec:
        # single-process fallback (parity: config.go:131-140)
        me = PeerID("127.0.0.1", 10000)
        return WorkerConfig(
            self_id=me,
            peers=PeerList([me]),
            runners=PeerList(),
            parent=None,
            cluster_version=0,
            strategy=DEFAULT_STRATEGY,
            config_server=env.get(CONFIG_SERVER, ""),
            elastic_mode=env.get(ELASTIC_MODE, ""),
            init_progress=int(env.get(INIT_PROGRESS, "0") or 0),
            single_process=True,
        )
    slots_raw = env.get(DEVICE_SLOTS, "")
    return WorkerConfig(
        self_id=PeerID.parse(self_spec),
        peers=PeerList.parse(env.get(INIT_PEERS, self_spec)),
        runners=PeerList.parse(env.get(INIT_RUNNERS, "")),
        parent=PeerID.parse(env[PARENT_ID]) if env.get(PARENT_ID) else None,
        cluster_version=int(env.get(INIT_CLUSTER_VERSION, "0") or 0),
        strategy=Strategy.parse(env.get(ALLREDUCE_STRATEGY, DEFAULT_STRATEGY.name)),
        config_server=env.get(CONFIG_SERVER, ""),
        elastic_mode=env.get(ELASTIC_MODE, ""),
        init_progress=int(env.get(INIT_PROGRESS, "0") or 0),
        device_slots=tuple(int(s) for s in slots_raw.split(",") if s.strip()),
        device_world=json.loads(env.get(DEVICE_WORLD) or "{}"),
        resize_marks=json.loads(env.get(RESIZE_MARKS) or "{}"),
    )


def tpu_process_env(
    self_id: PeerID,
    peers: PeerList,
    device_slots: Sequence[int],
    host_devices: int,
    port_range: Optional[Tuple[int, int]] = None,
    chip_coords: Optional[dict] = None,
) -> dict:
    """The libtpu variables for one worker holding `device_slots` of a
    host with `host_devices` chips.

    By default every worker is its own device world: it opens only its
    chips (`TPU_VISIBLE_CHIPS`), sees them as a grid of its own
    (`TPU_CHIPS_PER_PROCESS_BOUNDS`) and waits for nobody
    (`TPU_PROCESS_BOUNDS=1,1,1`). When the workers are all on this host
    and hold its chips in rank order, `KF_DEVICE_WORLD` also carries the
    variables that join them into one world — the process grid, every
    worker's libtpu port (the worker's own port mirrored to the top of
    `port_range`) and this worker's position — for
    `initialize_device_plane()` to apply; the choice between the two
    worlds is the worker's, made before its backend starts.

    `chip_coords` ({chip id as str: [x, y, z]}, what the workers of an
    earlier world of this host reported; Stage.chip_coords) decides the
    process grid of one-chip workers where it knows all their chips.
    """
    n = len(device_slots)
    if host_devices not in HOST_CHIPS or n not in _CHIP_BOUNDS:
        raise ValueError(
            f"no libtpu topology known for {n} chips of a {host_devices}-chip "
            f"host (hosts: {HOST_CHIPS}, chips per worker: "
            f"{tuple(_CHIP_BOUNDS)})"
        )
    env = {
        "TPU_VISIBLE_CHIPS": ",".join(str(i) for i in device_slots),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": _CHIP_BOUNDS[n],
        "TPU_PROCESS_BOUNDS": "1,1,1",
        # several processes of one host each load libtpu
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }
    local = [p for p in peers if p.host == self_id.host]
    i = local.index(self_id)
    bounds = _PROCESS_BOUNDS.get((n, len(local)))
    held = [str(j) for j in range(len(local))]  # one chip a worker, in rank order
    if n == 1 and chip_coords and all(j in chip_coords for j in held):
        bounds = _grid_bounds([chip_coords[j] for j in held])
    joinable = (
        port_range is not None
        and len(local) == len(peers)
        and bounds is not None
        and list(device_slots) == list(range(i * n, (i + 1) * n))
    )
    if joinable:
        lo, hi = port_range
        ports = [hi - (p.port - lo) for p in local]
        if not all(lo <= q <= hi for q in ports) or (
            set(ports) & {p.port for p in local}
        ):
            raise ValueError(
                f"port range {lo}-{hi} leaves no room for the libtpu ports "
                f"of {len(local)} workers"
            )
        env[DEVICE_WORLD] = json.dumps({
            "TPU_PROCESS_BOUNDS": bounds,
            "TPU_PROCESS_ADDRESSES": ",".join(
                f"{p.host}:{port}" for p, port in zip(local, ports)
            ),
            "TPU_PROCESS_PORT": str(ports[i]),
            "CLOUD_TPU_TASK_ID": str(i),
        })
    return env


def worker_env(
    self_id: PeerID,
    peers: PeerList,
    runners: PeerList,
    parent: Optional[PeerID],
    cluster_version: int = 0,
    strategy: Strategy = DEFAULT_STRATEGY,
    config_server: str = "",
    elastic_mode: str = "",
    init_progress: int = 0,
    device_slots=None,
    host_devices: int = 0,
    port_range: Optional[Tuple[int, int]] = None,
    resize_marks: Optional[dict] = None,
    chip_coords: Optional[dict] = None,
) -> dict:
    """Env block a runner sets for a spawned worker (parity: job.go:35-80)."""
    env = {
        SELF_SPEC: str(self_id),
        INIT_PEERS: ",".join(str(p) for p in peers),
        INIT_RUNNERS: ",".join(str(r) for r in runners),
        PARENT_ID: str(parent) if parent is not None else "",
        INIT_CLUSTER_VERSION: str(cluster_version),
        ALLREDUCE_STRATEGY: strategy.name,
        INIT_PROGRESS: str(init_progress),
    }
    if config_server:
        env[CONFIG_SERVER] = config_server
    if elastic_mode:
        env[ELASTIC_MODE] = elastic_mode
    if resize_marks:
        env[RESIZE_MARKS] = json.dumps(resize_marks)
    if device_slots:
        env[DEVICE_SLOTS] = ",".join(str(i) for i in device_slots)
        # the TPU analog of CUDA_VISIBLE_DEVICES (job.go:35-80)
        env.update(tpu_process_env(
            self_id, peers, device_slots, host_devices, port_range, chip_coords
        ))
    return env
