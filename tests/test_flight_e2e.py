"""Flight-recorder e2e (ISSUE 3 acceptance): SIGKILL a worker mid-run
under `kfrun -w -auto-recover` and assert the black box exists at every
surface — a `worker_postmortem` audit event on the runner, a non-empty
live /cluster/postmortem entry for the dead peer, the durable
postmortems.jsonl in the run dir, and an `info postmortem` timeline
rendered from both the URL and the directory."""

import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

from ports import kfrun_ports

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AGENT = os.path.join(REPO, "tests", "integration", "dying_elastic_agent.py")
PORTS = kfrun_ports()  # this xdist worker's block, not kfrun's defaults
DEBUG_PORT = PORTS.spare(0)


def _poll_postmortem(base_url, proc, timeout_s=240.0):
    deadline = time.time() + timeout_s
    last_err = None
    while time.time() < deadline:
        if proc.poll() is not None:
            return None, f"runner exited early (rc={proc.returncode})"
        try:
            with urllib.request.urlopen(
                base_url + "/cluster/postmortem", timeout=2
            ) as r:
                doc = json.loads(r.read().decode())
            if doc.get("deaths", 0) >= 1:
                return doc, None
        except (OSError, ValueError) as e:
            last_err = e
        time.sleep(0.3)
    return None, f"timed out; last error: {last_err}"


def test_sigkilled_worker_leaves_a_black_box(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["KF_TELEMETRY_DIR"] = str(tmp_path)
    env["KF_FLIGHT_INTERVAL"] = "0.2"  # snapshot faster than the agent dies
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "kungfu_tpu.runner.cli",
            *PORTS.args,
            "-np", "3", "-H", "127.0.0.1:4",
            "-w", "-auto-recover", "30s",
            "-warm-spares", "0",
            "-builtin-config-port", "0",
            "-debug-port", str(DEBUG_PORT),
            sys.executable, AGENT,
        ],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, cwd=REPO,
    )
    base_url = f"http://127.0.0.1:{DEBUG_PORT}"
    try:
        # -- live surface: /cluster/postmortem fills in while running --
        doc, err = _poll_postmortem(base_url, proc)
        if doc is None and proc.poll() is None:
            proc.kill()
        if doc is None:
            out, errout = proc.communicate(timeout=30)
            pytest.fail(
                f"no postmortem appeared: {err}\nstdout:\n{out}\nstderr:\n{errout}"
            )
        dead_peer = PORTS.worker(2)  # rank 2 of 3, in rank order from the base
        assert dead_peer in doc["peers"], doc
        pm = doc["peers"][dead_peer][-1]
        assert pm["death"] == "signal SIGKILL (-9)"
        assert pm["clean_exit"] is False
        # the runner-captured output ring carries the agent's last words
        assert any("dying (SIGKILL)" in l for l in pm.get("output_tail", [])), pm

        # -- info postmortem straight off the live endpoint --
        r = subprocess.run(
            [sys.executable, "-m", "kungfu_tpu.info", "postmortem", base_url],
            env=env, capture_output=True, text=True, timeout=60, cwd=REPO,
        )
        assert r.returncode == 0, r.stderr
        assert f"== postmortem: {dead_peer} ==" in r.stdout
        assert "SIGKILL" in r.stdout

        out, errout = proc.communicate(timeout=240)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate(timeout=30)
    # the run itself still recovers and completes (size 2, progress carried)
    assert proc.returncode == 0, f"stdout:\n{out}\nstderr:\n{errout}"
    # the worker_postmortem audit event was recorded on the runner
    assert f"worker_postmortem recorded for {PORTS.worker(2)}" in errout, errout

    # -- durable surface: the run dir outlives the runner --
    pm_file = tmp_path / "postmortems.jsonl"
    assert pm_file.exists()
    records = [
        json.loads(l) for l in pm_file.read_text().splitlines() if l.strip()
    ]
    dead = [r for r in records if r["peer"] == dead_peer]
    assert dead and dead[-1]["death"] == "signal SIGKILL (-9)"
    # the dead worker's journal is on disk and readable (snapshots made
    # it out before the SIGKILL thanks to the fast flight interval)
    from kungfu_tpu.telemetry import flight

    recs, _ = flight.read_journal(flight.peer_dir(str(tmp_path), dead_peer))
    assert any(r.get("kind") in ("snapshot", "start") for r in recs)

    r = subprocess.run(
        [sys.executable, "-m", "kungfu_tpu.info", "postmortem", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=60, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr
    assert f"== postmortem: {dead_peer} ==" in r.stdout
    assert "SIGKILL" in r.stdout
    assert "output tail" in r.stdout
