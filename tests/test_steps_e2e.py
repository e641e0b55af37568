"""Step-plane e2e (ISSUE 13 acceptance): a real np=4 run under
`kfrun -w -debug-port` with a shaped slow edge (KF_SHAPE_LINKS — the
ISSUE 14 shaped-link harness — delays one peer's sends toward its ring
successor) serves merged per-step critical-path records on
/cluster/steps that NAME that (peer, edge) within a few steps, `info
steps` renders the lanes, and /cluster/health carries the compact steps
summary the info-top columns read. The agents assert the worker-side
plane (recorded timelines, step/* PolicyContext signals) themselves and
exit nonzero otherwise. (Migrated off the deprecated KF_TEST_SLOW_EDGE
alias, whose parse-compat is covered by tests/test_shaping.py.)"""

import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

from ports import kfrun_ports

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AGENT = os.path.join(REPO, "tests", "integration", "steps_agent.py")
PORTS = kfrun_ports()  # this xdist worker's block, not kfrun's defaults
DEBUG_PORT = PORTS.spare(0)

# kfrun's slot assignment: first-fit over the port range, so np=4 on one
# host is base..base+3 in rank order. The injected edge is rank 1 ->
# rank 2 — a real ring edge of the segmented walk.
SLOW_SRC = PORTS.worker(1)
SLOW_DST = PORTS.worker(2)


def _poll_steps(base_url, proc, timeout_s=120.0):
    """Wait until /cluster/steps carries merged steps whose recent
    critical elections name the injected (peer, edge)."""
    deadline = time.time() + timeout_s
    last = None
    while time.time() < deadline:
        if proc.poll() is not None:
            return None, f"runner exited early (rc={proc.returncode})"
        try:
            with urllib.request.urlopen(
                base_url + "/cluster/steps", timeout=2
            ) as r:
                doc = json.loads(r.read().decode())
            last = doc
            steps = doc.get("steps", [])
            # acceptance: the slow edge is named within 5 steps — look
            # at the latest window of elections
            recent = steps[-5:]
            if recent and any(
                (s.get("critical") or {}).get("peer") == SLOW_SRC
                and (s.get("critical") or {}).get("edge") == SLOW_DST
                for s in recent
            ):
                return doc, None
        except (OSError, ValueError):
            pass
        time.sleep(0.3)
    return None, f"timed out; last doc: {json.dumps(last)[:2000]}"


def test_np4_steps_end_to_end(tmp_path):
    np_ = 4
    done_file = str(tmp_path / "steps-e2e-done")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["KF_TELEMETRY"] = "metrics"
    env["KF_CONFIG_ASYNC"] = "on"
    env["KF_CONFIG_ALGO"] = "segmented"  # deterministic ring successor
    env["KF_CLUSTER_SCRAPE_INTERVAL"] = "0.5"
    env["KF_SHAPE_LINKS"] = f"{SLOW_SRC}>{SLOW_DST}=lat:30"
    env["KF_TEST_DONE_FILE"] = done_file
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "kungfu_tpu.runner.cli",
            *PORTS.args,
            "-np", str(np_), "-H", f"127.0.0.1:{np_}",
            "-w", "-debug-port", str(DEBUG_PORT), "-q",
            sys.executable, AGENT,
        ],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, cwd=REPO,
    )
    base_url = f"http://127.0.0.1:{DEBUG_PORT}"
    try:
        doc, err = _poll_steps(base_url, proc)
        if doc is None:
            if proc.poll() is None:
                proc.kill()
            out, errout = proc.communicate(timeout=30)
            pytest.fail(
                f"/cluster/steps never named the slow edge: {err}\n"
                f"stdout:\n{out}\nstderr:\n{errout}"
            )
        steps = doc["steps"]
        named = [
            s for s in steps
            if (s.get("critical") or {}).get("peer") == SLOW_SRC
        ]
        assert named, steps
        s = named[-1]
        # the election carries the full attribution: bucket, edge,
        # blocking time, overlap and queue fractions
        crit = s["critical"]
        assert crit["edge"] == SLOW_DST
        assert crit["self_us"] > 0
        assert crit["bucket"] is not None
        assert s["overlap_frac"] is None or 0.0 <= s["overlap_frac"] <= 1.0
        assert s["peer_count"] >= 2  # cross-peer merge, not one lane

        # -- compact summary rides /cluster/health (info top's source) --
        with urllib.request.urlopen(
            base_url + "/cluster/health", timeout=5
        ) as r:
            health = json.loads(r.read().decode())
        summary = health.get("steps")
        assert summary and summary["steps"] > 0, health.get("steps")
        assert SLOW_SRC in (summary.get("crit_frac") or {}), summary

        # -- operator view: info steps one-shot against the live runner --
        r = subprocess.run(
            [sys.executable, "-m", "kungfu_tpu.info", "steps", base_url],
            env=env, capture_output=True, text=True, timeout=60, cwd=REPO,
        )
        assert r.returncode == 0, r.stderr
        assert "critical" in r.stdout
        assert SLOW_SRC in r.stdout
        assert "overlap" in r.stdout
        # the live path renders actual per-peer lanes with the critical
        # peer starred (recent /cluster/steps records keep their lanes)
        lanes = [
            l for l in r.stdout.splitlines()
            if "|" in l and l.lstrip().startswith(("*", "1"))
        ]
        assert any(l.lstrip().startswith("*") for l in lanes), r.stdout

        # release the agents; the run must complete cleanly (they assert
        # the worker-side plane and step/* signals themselves)
        with open(done_file, "w") as f:
            f.write("ok")
        out, errout = proc.communicate(timeout=180)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate(timeout=30)
        try:
            os.unlink(done_file)
        except OSError:
            pass
    assert proc.returncode == 0, f"stdout:\n{out}\nstderr:\n{errout}"
