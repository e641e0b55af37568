"""Worker CPU affinity (parity: srcs/cpp/src/numa/placement.cpp:6-17)."""

import os
import subprocess
import sys

import pytest

from ports import kfrun_ports

from kungfu_tpu.runner.affinity import (
    apply_affinity,
    numa_nodes,
    parse_cpulist,
    partition,
    plan_affinity,
)


def test_parse_cpulist():
    assert parse_cpulist("0-3,8,10-11") == [0, 1, 2, 3, 8, 10, 11]
    assert parse_cpulist("5") == [5]
    assert parse_cpulist("") == []
    assert parse_cpulist("3,1,1-2") == [1, 2, 3]


def test_partition_disjoint_equal():
    cpus = list(range(16))
    parts = partition(cpus, 4)
    assert [len(p) for p in parts] == [4, 4, 4, 4]
    assert sorted(c for p in parts for c in p) == cpus
    # uneven: sizes differ by at most one, still disjoint + complete
    parts = partition(list(range(10)), 3)
    assert [len(p) for p in parts] == [4, 3, 3]
    assert sorted(c for p in parts for c in p) == list(range(10))


def test_plan_affinity_numa_aware():
    # 2 nodes x 8 cpus, 4 workers -> 2 workers per node, 4 cpus each,
    # never straddling a node
    nodes = [list(range(0, 8)), list(range(8, 16))]
    plan = plan_affinity(4, cpus=range(16), nodes=nodes)
    assert [len(p) for p in plan] == [4, 4, 4, 4]
    assert sorted(c for p in plan for c in p) == list(range(16))
    for p in plan:
        assert any(set(p) <= set(node) for node in nodes), f"straddles: {p}"


def test_plan_affinity_fewer_workers_than_nodes():
    nodes = [[0, 1], [2, 3], [4, 5], [6, 7]]
    plan = plan_affinity(2, cpus=range(8), nodes=nodes)
    # plain even split (a worker may span nodes; better than idling cpus)
    assert [len(p) for p in plan] == [4, 4]
    assert sorted(c for p in plan for c in p) == list(range(8))


def test_plan_affinity_no_topology():
    plan = plan_affinity(3, cpus=[0, 1, 2, 3, 4], nodes=[])
    assert sorted(c for p in plan for c in p) == [0, 1, 2, 3, 4]
    assert [len(p) for p in plan] == [2, 2, 1]


def test_plan_affinity_respects_allowed_cpus():
    # node cpus outside our allowed set must not be assigned
    nodes = [list(range(0, 8)), list(range(8, 16))]
    plan = plan_affinity(2, cpus=[0, 1, 8, 9], nodes=nodes)
    assert sorted(c for p in plan for c in p) == [0, 1, 8, 9]
    for p in plan:
        assert any(set(p) <= set(node) for node in nodes)


def test_numa_nodes_sysfs(tmp_path):
    for i, cpulist in enumerate(["0-3", "4-7"]):
        d = tmp_path / f"node{i}"
        d.mkdir()
        (d / "cpulist").write_text(cpulist + "\n")
    (tmp_path / "has_cpu").write_text("")  # non-node entry ignored
    assert numa_nodes(str(tmp_path)) == [[0, 1, 2, 3], [4, 5, 6, 7]]


@pytest.mark.skipif(not hasattr(os, "sched_setaffinity"), reason="no sched_setaffinity")
def test_apply_affinity_integration():
    """Spawn a child, pin it to our own allowed set, read the mask back."""
    allowed = sorted(os.sched_getaffinity(0))
    child = subprocess.Popen(
        [sys.executable, "-c", "import sys; sys.stdin.read()"],
        stdin=subprocess.PIPE,
    )
    try:
        assert apply_affinity(child.pid, allowed)
        assert sorted(os.sched_getaffinity(child.pid)) == allowed
    finally:
        child.stdin.close()
        child.wait(10)


def test_kfrun_use_affinity_masks():
    """kfrun -use-affinity: each worker reports a disjoint mask covering
    the runner's allowed cpus (with 1 cpu, each worker gets... the lot —
    the partition degenerates but must still not crash)."""
    script = (
        "import os, sys; sys.path.insert(0, '/root/repo'); "
        "print('MASK', sorted(os.sched_getaffinity(0)))"
    )
    r = subprocess.run(
        [
            sys.executable, "-m", "kungfu_tpu.runner.cli",
            *kfrun_ports().args,  # this xdist worker's block
            "-np", "2", "-H", "127.0.0.1:2", "-use-affinity",
            sys.executable, "-c", script,
        ],
        capture_output=True, text=True, timeout=120,
        cwd="/root/repo",
    )
    assert r.returncode == 0, r.stderr
    masks = [l for l in r.stdout.splitlines() if "MASK" in l]
    assert len(masks) == 2, r.stdout
    n_cpus = len(os.sched_getaffinity(0))
    if n_cpus >= 2:
        # disjoint masks
        sets = [eval(m.split("MASK", 1)[1]) for m in masks]
        assert not (set(sets[0]) & set(sets[1])), sets
