"""Memory-plane e2e (ISSUE 17 acceptance): a real np=4 run under
`kfrun -w -debug-port` serves every peer's bucket decomposition on
/cluster/memory with `untracked` under 50% of RSS, an injected
per-beat pool leak on the last rank fires `memory_leak_suspect`
naming `pool` within the patience window while the clean peers stay
silent, and a worker SIGKILLed near a tight fake cgroup limit
(KF_MEMORY_LIMIT) harvests an `oom_suspected` postmortem rendering
its final attribution."""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

import pytest

from ports import kfrun_ports

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MEM_AGENT = os.path.join(REPO, "tests", "integration", "memory_agent.py")
OOM_AGENT = os.path.join(REPO, "tests", "integration", "oom_agent.py")
PORTS = kfrun_ports()  # this xdist worker's block, not kfrun's defaults
DEBUG_PORT = PORTS.spare(0)
OOM_DEBUG_PORT = PORTS.spare(1)


def _fetch(base_url, path):
    with urllib.request.urlopen(base_url + path, timeout=2) as r:
        return json.loads(r.read().decode())


def _poll(proc, fn, timeout_s=120.0):
    deadline = time.time() + timeout_s
    last = None
    while time.time() < deadline:
        if proc.poll() is not None:
            return None, f"runner exited early (rc={proc.returncode})"
        try:
            got = fn()
            last = got
            if got:
                return got, None
        except (OSError, ValueError):
            pass
        time.sleep(0.3)
    return None, f"timed out; last: {last}"


def test_np4_memory_plane_and_leak_watchdog(tmp_path):
    np_ = 4
    done_file = str(tmp_path / "memory-e2e-done")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["KF_TELEMETRY"] = "metrics"
    env["KF_TEST_DONE_FILE"] = done_file
    env["KF_CLUSTER_SCRAPE_INTERVAL"] = "0.5"
    env["KF_MEMORY_INTERVAL"] = "0.3"
    env["KF_MEMORY_WINDOWS"] = "5"
    # arm the watchdog only after the boot transient: a loaded box can
    # stretch agent startup (monotone untracked growth) past the
    # patience window and fake a leak on a clean peer
    env["KF_MEMORY_WARMUP"] = "12"
    env["KF_MEM_AGENT_LEAK"] = "1"
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "kungfu_tpu.runner.cli",
            *PORTS.args,
            "-np", str(np_), "-H", f"127.0.0.1:{np_}",
            "-w", "-debug-port", str(DEBUG_PORT), "-q",
            sys.executable, MEM_AGENT,
        ],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, cwd=REPO,
    )
    base_url = f"http://127.0.0.1:{DEBUG_PORT}"
    leaker = PORTS.worker(np_ - 1)
    try:
        # -- every peer's decomposition, untracked honest and < 50% --
        def full_matrix():
            doc = _fetch(base_url, "/cluster/memory")
            peers = doc.get("peers") or {}
            # wait until every agent's parked pool buffer is on the
            # books — early scrapes land while the agents still boot
            if len(peers) == np_ and all(
                r.get("rss_bytes")
                and r.get("sweeps", 0) >= 2
                and (r["buckets"]["pool"]["bytes"] >= 200 << 20)
                for r in peers.values()
            ):
                return doc
            return None

        doc, err = _poll(proc, full_matrix)
        if doc is None:
            if proc.poll() is None:
                proc.kill()
            out, errout = proc.communicate(timeout=30)
            pytest.fail(
                f"/cluster/memory never populated: {err}\n"
                f"stdout:\n{out}\nstderr:\n{errout}"
            )
        for peer, row in doc["peers"].items():
            buckets = row["buckets"]
            assert set(buckets) == {
                "arena", "pool", "zero_state", "sched_inflight",
                "telemetry", "untracked",
            }, (peer, buckets)
            # the parked pool buffer dominates: tracked > untracked
            assert buckets["untracked"]["frac"] < 0.5, (peer, buckets)
            assert buckets["pool"]["bytes"] >= 200 << 20, (peer, buckets)
            # the decomposition adds back up to RSS exactly
            total = sum(b["bytes"] for b in buckets.values())
            assert total == row["rss_bytes"], (peer, total, row["rss_bytes"])

        # -- injected leak: the watchdog names the right bucket on the
        # right peer; every clean peer stays silent --
        def leak_event():
            events = [
                e for e in _fetch(base_url, "/cluster/audit")
                if e.get("kind") == "memory_leak_suspect"
            ]
            return events or None

        events, err = _poll(proc, leak_event)
        if events is None:
            if proc.poll() is None:
                proc.kill()
            out, errout = proc.communicate(timeout=30)
            pytest.fail(
                f"memory_leak_suspect never fired: {err}\n"
                f"stdout:\n{out}\nstderr:\n{errout}"
            )
        assert any(
            e["peer"] == leaker and e["detail"]["bucket"] == "pool"
            for e in events
        ), events
        clean = [e for e in events if e["peer"] != leaker]
        assert not clean, f"clean peers fired the watchdog: {clean}"

        # -- operator view: info memory one-shot off the live runner --
        r = subprocess.run(
            [sys.executable, "-m", "kungfu_tpu.info", "memory", base_url],
            env=env, capture_output=True, text=True, timeout=60, cwd=REPO,
        )
        assert r.returncode == 0, r.stderr
        for peer in doc["peers"]:
            assert peer in r.stdout
        assert "leak:pool" in r.stdout, r.stdout

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


def test_oom_near_fake_limit_harvests_suspected_postmortem(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["KF_TELEMETRY_DIR"] = str(tmp_path)
    env["KF_FLIGHT_INTERVAL"] = "0.2"
    env["KF_MEMORY_INTERVAL"] = "0.1"
    env["KF_MEMORY_LIMIT"] = str(384 << 20)  # tight FAKE cgroup limit
    env["KF_MEMORY_OOM_MARGIN"] = "0.15"
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "kungfu_tpu.runner.cli",
            *PORTS.args,
            "-np", "3", "-H", "127.0.0.1:4",
            "-w", "-auto-recover", "30s",
            "-warm-spares", "0",
            "-builtin-config-port", "0",
            "-debug-port", str(OOM_DEBUG_PORT),
            sys.executable, OOM_AGENT,
        ],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, cwd=REPO,
    )
    base_url = f"http://127.0.0.1:{OOM_DEBUG_PORT}"
    dead_peer = PORTS.worker(2)
    try:
        def harvested():
            doc = _fetch(base_url, "/cluster/postmortem")
            return doc if doc.get("deaths", 0) >= 1 else None

        doc, err = _poll(proc, harvested, timeout_s=240.0)
        if doc is None:
            if proc.poll() is None:
                proc.kill()
            out, errout = proc.communicate(timeout=30)
            pytest.fail(
                f"no postmortem appeared: {err}\n"
                f"stdout:\n{out}\nstderr:\n{errout}"
            )
        pm = doc["peers"][dead_peer][-1]
        assert pm["death"] == "signal SIGKILL (-9)"
        # the verdict and its evidence: the journaled memory tail says
        # RSS died at the fake limit
        assert pm["oom_suspected"] is True, pm
        mem = pm["last_memory"]
        assert mem["limit_bytes"] == 384 << 20, mem
        assert mem["rss_bytes"] >= 0.85 * (384 << 20), mem
        assert mem["buckets"]["untracked"]["bytes"] > 0, mem

        # -- info postmortem renders the attribution and the verdict --
        r = subprocess.run(
            [sys.executable, "-m", "kungfu_tpu.info", "postmortem", base_url],
            env=env, capture_output=True, text=True, timeout=60, cwd=REPO,
        )
        assert r.returncode == 0, r.stderr
        assert f"== postmortem: {dead_peer} ==" in r.stdout
        assert "final memory attribution" in r.stdout, r.stdout
        assert "OOM suspected" in r.stdout, r.stdout

        out, errout = proc.communicate(timeout=240)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate(timeout=30)
    # the run itself recovers at the shrunk size and completes
    assert proc.returncode == 0, f"stdout:\n{out}\nstderr:\n{errout}"

    # durable surface: the verdict survives the runner
    records = [
        json.loads(l)
        for l in (tmp_path / "postmortems.jsonl").read_text().splitlines()
        if l.strip()
    ]
    dead = [r for r in records if r["peer"] == dead_peer]
    assert dead and dead[-1]["oom_suspected"] is True
