"""-w + -auto-recover composition: a SIGKILLed worker mid-train shrinks
out of the cluster and training completes at the smaller size with
carried progress (VERDICT r3 #5 — the preemptible-TPU-VM story)."""

import os
import re
import subprocess
import sys

from ports import kfrun_ports

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AGENT = os.path.join(REPO, "tests", "integration", "dying_elastic_agent.py")


def test_watch_autorecover_sigkilled_worker():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [
            sys.executable, "-m", "kungfu_tpu.runner.cli",
            *kfrun_ports().args,  # this xdist worker's block
            "-np", "3", "-H", "127.0.0.1:4",
            "-w", "-auto-recover", "30s",
            "-warm-spares", "0",
            "-builtin-config-port", "0",
            sys.executable, AGENT,
        ],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO,
    )
    out, err = r.stdout, r.stderr
    assert r.returncode == 0, f"stdout:\n{out}\nstderr:\n{err}"
    # the failure was detected and recovery happened
    assert "dying (SIGKILL)" in out, out
    assert re.search(r"died; reloading at size 2", err), err
    # training finished at the shrunk size on every survivor
    done = [l for l in out.splitlines() if l.startswith("agent done") or "agent done" in l]
    assert len(done) == 2, out
    for l in done:
        assert "size=2" in l, l
        assert "progress=24" in l, l
    # progress was carried: the respawned workers started at the min
    # completed step (8), not 0
    restarts = [
        l for l in out.splitlines()
        if "agent up" in l and "size=3" not in l
    ]
    assert restarts, out
    for l in restarts:
        m = re.search(r"progress=(\d+)", l)
        assert m and int(m.group(1)) >= 8, l
