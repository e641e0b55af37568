"""Per-worker device-slot allocation: pool, partition, kfrun pinning e2e,
and watcher reallocation across resizes.

Parity: srcs/go/kungfu/job/gpu_resource.go + job.go CUDA_VISIBLE_DEVICES —
N workers on one host must each see a disjoint device set.
"""

import json
import os
import subprocess
import sys

import pytest

from ports import kfrun_ports

from kungfu_tpu.runner.slots import SlotPool, partition

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestSlotPool:
    def test_get_put_roundtrip(self):
        pool = SlotPool.of_size(4)
        a = pool.get(2)
        b = pool.get(2)
        assert sorted(a + b) == [0, 1, 2, 3]
        assert not set(a) & set(b)
        with pytest.raises(RuntimeError):
            pool.get(1)  # exhausted
        pool.put(a)
        assert pool.get(2) == a  # lowest-first reuse

    def test_double_free_rejected(self):
        pool = SlotPool.of_size(2)
        got = pool.get(1)
        pool.put(got)
        with pytest.raises(ValueError):
            pool.put(got)

    def test_partition_even_and_remainder(self):
        assert partition(8, 2) == [[0, 1, 2, 3], [4, 5, 6, 7]]
        assert partition(5, 2) == [[0, 1, 2], [3, 4]]
        assert partition(4, 4) == [[0], [1], [2], [3]]


def _host_envs(n_workers, host_devices=4, port_range=(38000, 38999)):
    """worker_env of every worker of one fully used host, in rank order."""
    from kungfu_tpu.plan.peer import PeerID, PeerList
    from kungfu_tpu.runner import env as kfenv

    peers = PeerList(
        [PeerID("127.0.0.1", port_range[0] + i) for i in range(n_workers)]
    )
    return [
        kfenv.worker_env(
            self_id=p, peers=peers, runners=PeerList(),
            parent=PeerID("127.0.0.1", 38080), device_slots=slots,
            host_devices=host_devices, port_range=port_range,
        )
        for p, slots in zip(peers, partition(host_devices, n_workers))
    ]


def test_worker_env_carries_slots():
    from kungfu_tpu.runner import env as kfenv

    env = _host_envs(2)[1]
    assert env[kfenv.DEVICE_SLOTS] == "2,3"
    assert env["TPU_VISIBLE_CHIPS"] == "2,3"
    cfg = kfenv.parse_config_from_env(env)
    assert cfg.device_slots == (2, 3)
    assert cfg.device_world == json.loads(env[kfenv.DEVICE_WORLD])


@pytest.mark.parametrize("n_workers,chip_bounds,world_bounds", [
    (4, "1,1,1", "2,2,1"),
    (2, "1,2,1", "2,1,1"),
    (1, "2,2,1", None),  # one worker holds the host: nothing to join
])
def test_worker_env_tpu_topology_of_a_four_chip_host(
        n_workers, chip_bounds, world_bounds):
    """Each worker is by default a device world of its own chips, and
    carries beside it the variables that join all workers into one."""
    from kungfu_tpu.runner import env as kfenv

    envs = _host_envs(n_workers)
    # own world: disjoint chips that cover the host, same shape everywhere
    chips = [e["TPU_VISIBLE_CHIPS"].split(",") for e in envs]
    assert sorted(c for cs in chips for c in cs) == ["0", "1", "2", "3"]
    for e in envs:
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == chip_bounds
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert e["ALLOW_MULTIPLE_LIBTPU_LOAD"] == "1"
        assert e[kfenv.DEVICE_SLOTS] == e["TPU_VISIBLE_CHIPS"]
        # nothing of the joined world leaks into libtpu's own names
        assert not {"TPU_PROCESS_ADDRESSES", "TPU_PROCESS_PORT",
                    "CLOUD_TPU_TASK_ID"} & set(e)
    if world_bounds is None:
        assert all(kfenv.DEVICE_WORLD not in e for e in envs)
        return
    worlds = [json.loads(e[kfenv.DEVICE_WORLD]) for e in envs]
    # joined world: one grid and one address list for all, a port and a
    # position of its own for each
    assert {w["TPU_PROCESS_BOUNDS"] for w in worlds} == {world_bounds}
    (addresses,) = {w["TPU_PROCESS_ADDRESSES"] for w in worlds}
    addresses = addresses.split(",")
    assert len(set(addresses)) == n_workers
    for i, w in enumerate(worlds):
        assert w["CLOUD_TPU_TASK_ID"] == str(i)
        assert addresses[i] == f"127.0.0.1:{w['TPU_PROCESS_PORT']}"
    # libtpu's ports come from the top of the range, the workers' own
    # from the bottom
    ports = {int(w["TPU_PROCESS_PORT"]) for w in worlds}
    assert ports == {38999 - i for i in range(n_workers)}


def test_worker_env_refuses_layouts_the_chip_has_not_met():
    from kungfu_tpu.plan.peer import PeerID, PeerList
    from kungfu_tpu.runner import env as kfenv

    me = PeerID("127.0.0.1", 38000)
    kw = dict(self_id=me, peers=PeerList([me]), runners=PeerList(),
              parent=None)
    with pytest.raises(ValueError, match="no libtpu topology"):
        kfenv.worker_env(device_slots=[0, 1], host_devices=8, **kw)
    with pytest.raises(ValueError, match="no libtpu topology"):
        kfenv.worker_env(device_slots=[0, 1, 2], host_devices=4, **kw)
    # a port range the mirrored libtpu ports would collide in
    with pytest.raises(ValueError, match="no room"):
        _host_envs(4, port_range=(38000, 38005))


def test_partly_used_host_joins_its_first_column():
    """Two one-chip workers on chips 0,1 of a four-chip host (the state
    after a shrink) join as a 1x2 grid; three workers are no rectangle
    and get no joined world, so initialize_device_plane() would refuse."""
    from kungfu_tpu.plan.peer import PeerID, PeerList
    from kungfu_tpu.runner import env as kfenv

    def envs(k):
        peers = PeerList([PeerID("127.0.0.1", 38000 + i) for i in range(k)])
        return [
            kfenv.worker_env(
                self_id=p, peers=peers, runners=PeerList(), parent=None,
                device_slots=[i], host_devices=4, port_range=(38000, 38999),
            )
            for i, p in enumerate(peers)
        ]

    for i, env in enumerate(envs(2)):
        assert env["TPU_VISIBLE_CHIPS"] == str(i)
        world = json.loads(env[kfenv.DEVICE_WORLD])
        assert world["TPU_PROCESS_BOUNDS"] == "1,2,1"
        assert world["CLOUD_TPU_TASK_ID"] == str(i)
    assert all(kfenv.DEVICE_WORLD not in env for env in envs(3))


@pytest.mark.parametrize("coords,bounds", [
    # chips 0,1 side by side in x (the machine of PR 54's calls 1 and 3),
    # one above the other (call 2's), on a diagonal (no grid: no joined world)
    ({"0": [1, 1, 0], "1": [0, 1, 0], "2": [0, 0, 0], "3": [1, 0, 0]}, "2,1,1"),
    ({"0": [1, 0, 0], "1": [1, 1, 0], "2": [0, 1, 0], "3": [0, 0, 0]}, "1,2,1"),
    ({"0": [0, 0, 0], "1": [1, 1, 0]}, None),
    ({"0": [0, 0, 0]}, "1,2,1"),  # chip 1 unknown: the table's guess stands
])
def test_a_world_of_one_chip_workers_takes_its_grid_from_the_chips_coordinates(
        coords, bounds):
    """Which chip ids are neighbours differs between hosts; where an
    earlier world said where its chips sit, the next world's process grid
    is made from that."""
    from kungfu_tpu.plan.peer import PeerID, PeerList
    from kungfu_tpu.runner import env as kfenv

    peers = PeerList([PeerID("127.0.0.1", 38000 + i) for i in range(2)])
    for i, p in enumerate(peers):
        env = kfenv.worker_env(
            self_id=p, peers=peers, runners=PeerList(), parent=None,
            device_slots=[i], host_devices=4, port_range=(38000, 38999),
            chip_coords=coords,
        )
        if bounds is None:
            assert kfenv.DEVICE_WORLD not in env
        else:
            world = json.loads(env[kfenv.DEVICE_WORLD])
            assert world["TPU_PROCESS_BOUNDS"] == bounds


def test_standby_activation_carries_the_tpu_env(tmp_path):
    """A warm standby imports jax BEFORE it learns its identity; the
    activation spec must deliver the per-process TPU variables into its
    environment before the worker command runs."""
    from kungfu_tpu.runner import env as kfenv
    from kungfu_tpu.runner.standby import run_activated

    env = _host_envs(4)[2]
    out = tmp_path / "env.json"
    probe = (
        "import json, os; json.dump({k: v for k, v in os.environ.items() "
        f"if k.startswith(('TPU_', 'KF_DEVICE', 'ALLOW_'))}}, open({str(out)!r}, 'w'))"
    )
    code = (
        "import sys; sys.path.insert(0, sys.argv[1]); import json; "
        "from kungfu_tpu.runner.standby import run_activated; "
        "run_activated(json.loads(sys.argv[2]))"
    )
    spec = {"env": env, "argv": [sys.executable, "-c", probe]}
    clean = {k: v for k, v in os.environ.items()
             if not k.startswith(("TPU_", "KF_"))}
    subprocess.run([sys.executable, "-c", code, REPO, json.dumps(spec)],
                   env=clean, check=True, timeout=60)
    seen = json.loads(out.read_text())
    want = {k: v for k, v in env.items()
            if k.startswith(("TPU_", "KF_DEVICE", "ALLOW_"))}
    assert seen == want and kfenv.DEVICE_WORLD in seen


def test_kfrun_pins_disjoint_devices():
    """2 workers, 4 chips: each worker must see its own disjoint pair
    (asserted inside the workers via an allgather of their slot sets)."""
    agent = (
        "import os\n"
        "from kungfu_tpu import api\n"
        "from kungfu_tpu.peer import get_default_peer\n"
        "slots = get_default_peer().config.device_slots\n"
        "assert len(slots) == 2, slots\n"
        "assert os.environ['TPU_VISIBLE_CHIPS'] == ','.join(map(str, slots))\n"
        "import numpy as np\n"
        "from kungfu_tpu.base.ops import ReduceOp\n"
        "from kungfu_tpu.base.workspace import Workspace\n"
        "sess = get_default_peer().current_session()\n"
        "recv = np.zeros(4, np.int64)\n"
        "w = Workspace(np.array(slots, np.int64), recv, ReduceOp.SUM, 'slots')\n"
        "sess.all_gather(w)\n"
        "assert sorted(recv.tolist()) == [0, 1, 2, 3], recv\n"
        "print('slots ok', slots)\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [
            sys.executable, "-m", "kungfu_tpu.runner.cli",
            *kfrun_ports().args,  # this xdist worker's block
            "-np", "2", "-devices-per-host", "4",
            "--", sys.executable, "-c", agent,
        ],
        env=env, capture_output=True, text=True, timeout=90, cwd=REPO,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert r.stdout.count("slots ok") == 2


class TestWatcherReallocation:
    """apply_delta must draw joiner slots from the pool and return leavers'
    slots, never overlapping live workers (parity: watcher + GPU pool)."""

    def _watcher(self, n_dev=4, cap=4):
        import argparse

        from kungfu_tpu.runner.watch import Stage, Watcher
        from kungfu_tpu.base.strategy import Strategy
        from kungfu_tpu.plan.cluster import Cluster
        from kungfu_tpu.plan.hostspec import HostList
        from kungfu_tpu.plan.peer import PeerID, PeerList

        args = argparse.Namespace(
            runner_port=38080, elastic_mode="", logdir="", quiet=True,
            devices_per_host=n_dev, host_capacity=cap, debug_port=-1,
            port_range="38000-38999",
        )
        w = Watcher(args, [sys.executable, "-c", "import time; time.sleep(30)"],
                    "127.0.0.1", Strategy.STAR, "")

        def cluster_of(n):
            workers = PeerList([PeerID("127.0.0.1", 38000 + i) for i in range(n)])
            runners = PeerList([PeerID("127.0.0.1", 38080)])
            return Cluster(runners=runners, workers=workers)

        def stage(version, n):
            return Stage(version=version, progress=0, cluster=cluster_of(n))

        return w, stage

    def test_grow_and_shrink_keep_slots_disjoint(self):
        w, stage = self._watcher(n_dev=4, cap=2)
        try:
            w.apply_delta(stage(0, 1))
            slots_v0 = dict(w._worker_slots)
            assert list(slots_v0.values()) == [[0, 1]]

            w.apply_delta(stage(1, 2))  # grow: the joiner draws fresh ids
            all_slots = [i for s in w._worker_slots.values() for i in s]
            assert sorted(all_slots) == list(range(4))  # disjoint, full
            # the survivor kept its original stripe
            for worker, s in slots_v0.items():
                assert w._worker_slots[worker] == s

            w.apply_delta(stage(2, 1))  # shrink: the leaver's ids return
            assert w.slot_pool.available == 2
            (only,) = w._worker_slots.values()
            assert len(only) == 2
        finally:
            for p in w.current.values():
                p.kill()
            for p in w._gone:
                p.kill()

    def test_a_reload_is_spanned_and_its_marks_go_on_to_the_new_workers(self):
        """apply_full: a `runner.kill` an old worker, a `runner.spawn` a
        new one, and the new workers' environment carries the proposer's
        marks with the runner's own and the grid their chips form."""
        import time

        from kungfu_tpu.runner import env as kfenv
        from kungfu_tpu.runner.watch import Stage
        from kungfu_tpu.telemetry import tracing

        w, stage = self._watcher(n_dev=4, cap=4)
        tracing.clear()
        try:
            w.apply_delta(stage(0, 4))
            assert not any(kfenv.RESIZE_MARKS in p.env for p in w.current.values())
            assert not tracing.full_events("runner.kill")
            old = list(w.current.values())
            t0 = time.time()
            reload = Stage(
                version=1, progress=21, cluster=stage(1, 2).cluster, reload=True,
                marks={"t_propose": t0 - 2.0, "t_stage": t0 - 1.0, "mode": "reload",
                       "old_size": 4, "phases_ms": {"consensus_ms": 3.0}},
                # chips 0 and 1 side by side in x on this host
                chip_coords={"0": [1, 1, 0], "1": [0, 1, 0],
                             "2": [0, 0, 0], "3": [1, 0, 0]},
            )
            w.apply_full(reload)
            assert all(not p.running for p in old)
            kills = [e.args for e in tracing.full_events("runner.kill")]
            assert sorted(k["rank"] for k in kills) == [0, 1, 2, 3]
            for k in kills:
                assert k["version"] == 1 and k["escalated"] is False
                assert k["returncode"] == -15  # a sleeping worker: terminated
            spawns = [e.args for e in tracing.full_events("runner.spawn")]
            assert [(s["rank"], s["version"], s["slots"]) for s in spawns] == [
                (r, 0, [r]) for r in range(4)] + [(0, 1, [0]), (1, 1, [1])]
            assert len(w.current) == 2
            for p in w.current.values():
                marks = json.loads(p.env[kfenv.RESIZE_MARKS])
                assert marks["phases_ms"] == {"consensus_ms": 3.0}
                assert (marks["t_propose"] < marks["t_stage"] < marks["t_killed"]
                        <= marks["t_spawn"] <= time.time())
                assert float(p.env["KF_SPAWN_TS"]) == marks["t_spawn"]
                world = json.loads(p.env[kfenv.DEVICE_WORLD])
                assert world["TPU_PROCESS_BOUNDS"] == "2,1,1"
                cfg = kfenv.parse_config_from_env(p.env)
                assert cfg.resize_marks == marks and cfg.init_progress == 21
            # a world of two tells of two chips: the four's table stays
            w.apply_full(Stage(version=2, progress=41, cluster=stage(2, 4).cluster,
                               reload=True, chip_coords={"0": [0, 0, 0], "1": [1, 0, 0]}))
            assert len(w.chip_coords) == 4
            for p in w.current.values():
                world = json.loads(p.env[kfenv.DEVICE_WORLD])
                assert world["TPU_PROCESS_BOUNDS"] == "2,2,1"
        finally:
            for p in w.current.values():
                p.kill()
            tracing.clear()

    def test_env_of_spawned_workers_is_pinned(self):
        w, stage = self._watcher(n_dev=4, cap=2)
        try:
            w.apply_delta(stage(0, 2))
            envs = [p.env["KF_DEVICE_SLOTS"] for p in w.current.values()]
            assert sorted(envs) == ["0,1", "2,3"]
            assert sorted(p.env["TPU_VISIBLE_CHIPS"]
                          for p in w.current.values()) == ["0,1", "2,3"]
        finally:
            for p in w.current.values():
                p.kill()

    def test_short_pool_fails_the_resize(self):
        """A worker that cannot get its chips is never spawned unpinned:
        the pool's error takes the resize down."""
        w, stage = self._watcher(n_dev=4, cap=2)
        try:
            with pytest.raises(RuntimeError, match="slot pool exhausted"):
                w.apply_delta(stage(0, 3))
            assert len(w.current) == 2  # the third was not started
        finally:
            for p in w.current.values():
                p.kill()
