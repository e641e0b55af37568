"""Multi-host device plane: kfrun-launched workers form ONE JAX world.

Parity: VERDICT r1 #1 / SURVEY §7 stages 4+6 — the control plane must
bootstrap the device data plane across processes (the reference does this
for NCCL via unique-id broadcast over its CPU collective).
"""

import os
import subprocess
import sys

import pytest

from ports import kfrun_ports

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AGENT = os.path.join(REPO, "tests", "integration", "device_agent.py")


def run_device_agent(np_, timeout=240):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # workers must see the CPU backend, not the test session's settings
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [
            sys.executable, "-m", "kungfu_tpu.runner.cli",
            *kfrun_ports().args,  # this xdist worker's block
            "-np", str(np_),
            "-H", f"127.0.0.1:{np_}",
            "--", sys.executable, AGENT,
        ],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
        cwd=REPO,
    )


@pytest.mark.parametrize("np_", [2, 3])
def test_kfrun_forms_one_jax_world(np_):
    r = run_device_agent(np_)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    oks = [l for l in r.stdout.splitlines() if "OK device-plane" in l]
    assert len(oks) == np_, r.stdout
