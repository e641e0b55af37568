"""Multi-process localhost integration: kfrun x strategy x np matrix.

Parity: scripts/tests/run-integration-tests.sh — every strategy must give
correct collectives on real multi-process clusters.
"""

import os
import subprocess
import sys

import pytest

from ports import kfrun_ports

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AGENT = os.path.join(REPO, "tests", "integration", "host_agent.py")


def run_kfrun(np_, strategy, extra_env=None, timeout=120):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if extra_env:
        env.update(extra_env)
    return subprocess.run(
        [
            sys.executable, "-m", "kungfu_tpu.runner.cli",
            *kfrun_ports().args,  # this xdist worker's block
            "-np", str(np_),
            "-H", f"127.0.0.1:{np_}",
            "-strategy", strategy,
            "-q",
            "--", sys.executable, AGENT,
        ],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
        cwd=REPO,
    )


@pytest.mark.parametrize("np_", [1, 2, 4])
def test_kfrun_matrix_default(np_):
    r = run_kfrun(np_, "AUTO")
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"


@pytest.mark.parametrize(
    "strategy",
    ["STAR", "RING", "CLIQUE", "BINARY_TREE", "BINARY_TREE_STAR", "TREE",
     "MULTI_STAR", "MULTI_BINARY_TREE_STAR"],
)
def test_kfrun_all_strategies_np4(strategy):
    r = run_kfrun(4, strategy)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"


def test_kfrun_monitoring_counts_bytes():
    """Parity: monitoring CI test (ci.yaml:36-41) — egress counters must be
    nonzero after real collectives and /metrics must serve them."""
    r = run_kfrun(2, "AUTO", extra_env={"KF_CONFIG_ENABLE_MONITORING": "1"})
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"


def test_kfrun_propagates_worker_failure():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [
            sys.executable, "-m", "kungfu_tpu.runner.cli",
            "-np", "2", "-q",
            "--", sys.executable, "-c", "import sys; sys.exit(3)",
        ],
        env=env, capture_output=True, text=True, timeout=60, cwd=REPO,
    )
    assert r.returncode == 1


def test_kfrun_debug_port_dumps_stages():
    """Parity: -debug-port (runner/handler.go:118-124) — the runner serves
    a JSON dump of the Stages it has seen."""
    import json
    import re
    import time
    import urllib.request

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.Popen(
        [
            sys.executable, "-m", "kungfu_tpu.runner.cli",
            "-np", "2", "-w", "-debug-port", "0", "-q",
            "-runner-port", "38085",  # private port: don't race other tests
            "--", sys.executable, "-c", "import time; time.sleep(8)",
        ],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=REPO,
    )
    try:
        port = None
        seen = []
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            line = p.stderr.readline()
            if not line:
                if p.poll() is not None:
                    break
                time.sleep(0.1)
                continue
            seen.append(line)
            m = re.search(r"debug endpoint on :(\d+)", line)
            if m:
                port = int(m.group(1))
                break
        assert port, f"no debug endpoint line; stderr so far:\n{''.join(seen)}"
        # the endpoint comes up before the watcher spawns workers: poll
        dump = None
        while time.monotonic() < deadline:
            with urllib.request.urlopen(f"http://127.0.0.1:{port}/", timeout=5) as r:
                dump = json.loads(r.read().decode())
            if len(dump["workers"]) == 2:
                break
            time.sleep(0.2)
        assert dump and dump["stages"] and dump["stages"][0]["version"] == 0
        assert len(dump["stages"][0]["workers"]) == 2
        assert len(dump["workers"]) == 2, dump
        # ISSUE 2: the same endpoint serves the cluster plane; the
        # aggregator tracks every worker from the Stage (these sleep(8)
        # workers run no telemetry server, so scrapes error — but the
        # membership and health shape must be there)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/cluster/health", timeout=5
        ) as r:
            health = json.loads(r.read().decode())
        assert set(health["peers"]) == set(dump["workers"])
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/cluster/metrics", timeout=5
        ) as r:
            assert r.status == 200
    finally:
        p.kill()
        p.wait(10)
