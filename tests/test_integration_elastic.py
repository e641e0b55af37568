"""Elastic resize end-to-end: kfrun -w + builtin config server.

Parity: scripts/tests/run-elastic-test.sh — a schedule of cluster sizes is
driven through the config server while training progresses; the run must
finish with progress complete and all procs exited cleanly.
"""

import os
import subprocess
import sys

import pytest

from ports import kfrun_ports

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AGENT = os.path.join(REPO, "tests", "integration", "elastic_agent.py")
JOINER_FIRST_AGENT = os.path.join(
    REPO, "tests", "integration", "joiner_first_agent.py"
)


def test_elastic_resize_schedule():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [
            sys.executable, "-m", "kungfu_tpu.runner.cli",
            *kfrun_ports().args,  # this xdist worker's block
            "-np", "2",
            "-H", "127.0.0.1:4",
            "-w",
            "-builtin-config-port", "0",
            "-q",
            "--", sys.executable, AGENT,
        ],
        env=env,
        capture_output=True,
        text=True,
        timeout=220,
        cwd=REPO,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"


def test_joiner_listed_first_cannot_reset_survivor_state():
    """A config PUT that puts the joiner at rank 0 must not let its fresh
    weights overwrite the survivors' (state re-sync roots at a survivor)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [
            sys.executable, "-m", "kungfu_tpu.runner.cli",
            *kfrun_ports().args,  # this xdist worker's block
            "-np", "2",
            "-H", "127.0.0.1:4",
            "-w",
            "-builtin-config-port", "0",
            "--", sys.executable, JOINER_FIRST_AGENT,
        ],
        env=env, capture_output=True, text=True, timeout=220, cwd=REPO,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    oks = [l for l in r.stdout.splitlines() if "OK joiner-first" in l]
    assert len(oks) == 3, r.stdout
