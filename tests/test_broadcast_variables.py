"""`initializer.broadcast_variables` between two kfrun workers that hold
different values (CPU backend, on this xdist worker's own ports), with one
and with two devices a process: rank 0's values to the bit on every device
of the mesh, from one program whose text does not depend on the rank, with
no leaf through JAX's host-side helpers."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from ports import kfrun_ports

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AGENT = os.path.join(REPO, "tests", "integration", "broadcast_agent.py")
TAG = "BROADCAST_AGENT "
LEAVES = ["float32", "bfloat16", "int32", "bool", "scalar"]
SHAPES = {"float32": [5, 3], "bfloat16": [7], "int32": [4, 2], "bool": [9],
          "scalar": []}


@pytest.fixture(scope="module", params=[1, 2], ids=["1dev", "2dev"])
def world(request):
    """What both ranks printed, by rank; `request.param` devices a process."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_NUM_CPU_DEVICES"] = str(request.param)
    r = subprocess.run(
        [sys.executable, "-m", "kungfu_tpu.runner.cli", *kfrun_ports().args,
         "-np", "2", "-H", "127.0.0.1:2", "--", sys.executable, AGENT],
        env=env, capture_output=True, text=True, timeout=240, cwd=REPO)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    workers = [json.loads(l.split(TAG, 1)[1])
               for l in r.stdout.splitlines() if TAG in l]
    assert sorted(w["rank"] for w in workers) == [0, 1]
    assert {w["local_devices"] for w in workers} == {request.param}
    return {w["rank"]: w for w in workers}


@pytest.mark.parametrize("rank", [0, 1])
@pytest.mark.parametrize("leaf", LEAVES)
@pytest.mark.parametrize("kind", ["numpy", "device"])
def test_every_device_of_the_mesh_holds_rank_0s_bits(world, kind, leaf, rank):
    got = world[rank]["kinds"][kind]["leaves"][leaf]
    want = world[0]["want"][leaf]
    assert got["bits"] == want
    assert got["shards"] == [want] * world[rank]["local_devices"]
    assert got["shape"] == SHAPES[leaf]
    assert got["dtype"] == ("float32" if leaf == "scalar" else leaf)


@pytest.mark.parametrize("rank", [0, 1])
@pytest.mark.parametrize("leaf", LEAVES)
@pytest.mark.parametrize("kind", ["numpy", "device"])
def test_every_leaf_is_the_meshs_replicated_committed_array(
        world, kind, leaf, rank):
    got = world[rank]["kinds"][kind]["leaves"][leaf]
    assert got["array"] and got["sharding"] and got["replicated"]
    assert got["committed"]
    assert len(got["devices"]) == 2 * world[rank]["local_devices"]


@pytest.mark.parametrize("rank", [0, 1])
@pytest.mark.parametrize("kind", ["numpy", "device"])
def test_a_nan_of_another_rank_does_not_reach_the_result(world, kind, rank):
    """Rank 1 holds NaN where rank 0 holds numbers: a select keeps them
    out, a product with a zero flag would not."""
    for leaf, dtype in (("float32", np.float32), ("scalar", np.float32)):
        got = world[rank]["kinds"][kind]["leaves"][leaf]["bits"]
        assert not np.isnan(np.frombuffer(bytes.fromhex(got), dtype)).any()
    got = world[rank]["kinds"][kind]["leaves"]["bfloat16"]["bits"]
    halves = np.frombuffer(bytes.fromhex(got), np.uint16)
    assert not ((halves & 0x7FFF) > 0x7F80).any()


@pytest.mark.parametrize("rank", [0, 1])
@pytest.mark.parametrize("kind", ["numpy", "device"])
def test_both_spans_say_what_moved_and_what_came_from_the_host(
        world, kind, rank):
    spans = world[rank]["kinds"][kind]["spans"]
    assert sorted(spans) == ["broadcast.one_to_all", "broadcast.replicate"]
    nbytes = sum(world[rank]["nbytes"].values())
    assert spans["broadcast.one_to_all"] == {
        "leaves": len(LEAVES), "bytes": nbytes,
        "host_bytes": nbytes if kind == "numpy" else 0}


@pytest.mark.parametrize("rank", [0, 1])
def test_jaxs_host_side_helpers_are_never_called(world, rank):
    """`broadcast_one_to_all`, `process_allgather` and `assert_equal` raise
    in the agent: the device_put of a host value onto a sharding that spans
    processes would have drawn the last two."""
    assert world[rank]["called"] == []


def test_the_program_is_one_text_on_both_ranks(world):
    assert world[0]["program"] == world[1]["program"]
    assert len(world[0]["program"]) == 64


@pytest.mark.parametrize("rank", [0, 1])
def test_without_a_mesh_the_caller_reads_rank_0s_number(world, rank):
    assert world[rank]["no_mesh"] == {"int": 7, "asarray": 7, "dtype": "int32"}
