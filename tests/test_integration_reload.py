"""Reload-mode elastic e2e: kfrun -w -elastic-mode reload restarts the
whole cluster from the carried progress, and each incarnation forms a
fresh multi-process JAX world.

Parity: test-elastic-reload.sh + test_elastic_reload.py:17-47; VERDICT r1
items #1 (device plane survives resize) and #4 (reload e2e).
"""

import os
import re
import subprocess
import sys

from ports import kfrun_ports

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AGENT = os.path.join(REPO, "tests", "integration", "reload_agent.py")


def test_reload_mode_restarts_with_progress_and_fresh_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [
            sys.executable, "-m", "kungfu_tpu.runner.cli",
            *kfrun_ports().args,  # this xdist worker's block
            "-np", "2",
            "-H", "127.0.0.1:4",
            "-w",
            "-elastic-mode", "reload",
            "-builtin-config-port", "0",
            "--", sys.executable, AGENT,
        ],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"

    # three incarnations: start at 0 (np=2), reload ~10 (np=3), reload ~20 (np=2)
    starts = re.findall(r"incarnation rank=\d+/(\d+) start_progress=(\d+)", r.stdout)
    progresses = sorted({int(p) for _, p in starts})
    assert len(progresses) >= 3, f"expected >=3 incarnations: {starts}"
    assert progresses[0] == 0
    sizes_by_progress = {}
    for s, p in starts:
        sizes_by_progress.setdefault(int(p), set()).add(int(s))
    mid = [p for p in progresses if 10 <= p < 20]
    assert mid and sizes_by_progress[mid[0]] == {3}, sizes_by_progress
    # final incarnation finishes with full progress on every worker
    finished = re.findall(r"stopped reason=finished progress=30", r.stdout)
    assert len(finished) == 2, r.stdout
