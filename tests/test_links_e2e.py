"""Link-plane e2e (ISSUE 6 acceptance): a real np=4 run under
`kfrun -w -debug-port` serves a POPULATED k×k matrix on /cluster/links
(every source row present, bandwidth estimated from the passive
collective traffic alone), `info links` renders it, and the agent
asserts worker-side that PolicyContext.metrics carries links/* +
collective/* signals (it exits nonzero otherwise, failing the run)."""

import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

from ports import kfrun_ports

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AGENT = os.path.join(REPO, "tests", "integration", "links_agent.py")
PORTS = kfrun_ports()  # this xdist worker's block, not kfrun's defaults
DEBUG_PORT = PORTS.spare(0)


def _poll_links(base_url, proc, np_, timeout_s=240.0):
    """Wait until the matrix is the one the test asserts: every peer's
    source row there with an edge that carried bytes, a bandwidth estimate
    overall and every peer's clock offset. The rows arrive a scrape at a
    time, later on a loaded machine, and the agents keep them warm until
    the harness has seen them all."""
    deadline = time.time() + timeout_s
    last = None
    while time.time() < deadline:
        if proc.poll() is not None:
            return None, f"runner exited early (rc={proc.returncode})"
        try:
            with urllib.request.urlopen(
                base_url + "/cluster/links", timeout=2
            ) as r:
                doc = json.loads(r.read().decode())
            last = doc
            edges = doc.get("edges", {})
            if (
                len(doc.get("peers", [])) == np_
                and len(edges) == np_
                and doc.get("min_bw")
                and all(row and all(e["tx_bytes"] > 0 for e in row.values())
                        for row in edges.values())
                and len(doc.get("clock_offset_us", {})) == np_
            ):
                return doc, None
        except (OSError, ValueError):
            pass
        time.sleep(0.3)
    return None, f"timed out; last doc: {last}"


def test_np4_link_matrix_end_to_end(tmp_path):
    np_ = 4
    done_file = str(tmp_path / "links-e2e-done")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["KF_TELEMETRY"] = "metrics"
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
        doc, err = _poll_links(base_url, proc, np_)
        if doc is None:
            if proc.poll() is None:
                proc.kill()
            out, errout = proc.communicate(timeout=30)
            pytest.fail(
                f"/cluster/links never populated: {err}\n"
                f"stdout:\n{out}\nstderr:\n{errout}"
            )
        # the matrix is k x k: all four peers, all four source rows, and
        # the slowest edge was elected from real measured traffic
        assert len(doc["peers"]) == np_
        assert set(doc["edges"]) == set(doc["peers"])
        assert doc["min_bw"] > 0
        src, dst = doc["slowest_edge"]
        assert src in doc["peers"] and dst in doc["peers"]
        for srow in doc["edges"].values():
            assert srow, doc["edges"]  # every peer measured someone
            for e in srow.values():
                assert e["tx_bytes"] > 0
        # clock offsets ride along for offline alignment
        assert set(doc["clock_offset_us"]) == set(doc["peers"])

        # -- operator view: info links one-shot against the live runner --
        r = subprocess.run(
            [sys.executable, "-m", "kungfu_tpu.info", "links", base_url],
            env=env, capture_output=True, text=True, timeout=60, cwd=REPO,
        )
        assert r.returncode == 0, r.stderr
        assert f"{np_} peers" in r.stdout
        assert "slowest edge" in r.stdout
        for peer in doc["peers"]:
            assert peer in r.stdout  # the legend names every peer

        # release the agents; the run must complete cleanly (the agents
        # assert the PolicyContext links/collective signals themselves)
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
