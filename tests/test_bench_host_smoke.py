"""Tier-1 smoke for the HOST bench A/B flag (ISSUE 4 satellite): the
tree/segmented paths must both run end-to-end under kfrun at tiny sizes
and report throughput + per-peer wire bytes, so the A/B tooling (and the
segmented engine behind it) can't silently rot."""

import os
import subprocess
import sys

import pytest

from ports import kfrun_ports

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AGENT = os.path.join(REPO, "tests", "integration", "bench_host_agent.py")


@pytest.mark.parametrize("algo,wire", [
    ("tree", ""),
    ("segmented", ""),
    ("segmented", "bf16"),
])
def test_bench_host_ab_smoke(algo, wire):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    # tiny payloads sit below the segmentation + codec thresholds; drop
    # them so the segmented/compressed legs actually exercise their
    # paths (cluster-agreed via the runner env)
    env["KF_CONFIG_SEGMENT_MIN_BYTES"] = "0"
    env["KF_CONFIG_WIRE_MIN_BYTES"] = "0"
    env["KF_BENCH_ALGO"] = algo
    env["KF_BENCH_MODEL"] = "tiny"
    env["KF_BENCH_ITERS"] = "2"
    if wire:
        env["KF_BENCH_WIRE"] = wire
    r = subprocess.run(
        [
            sys.executable, "-m", "kungfu_tpu.runner.cli",
            *kfrun_ports().args,  # this xdist worker's block
            "-np", "2", "-H", "127.0.0.1:2",
            sys.executable, AGENT,
        ],
        env=env, capture_output=True, text=True, timeout=180, cwd=REPO,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "RESULT:" in r.stdout, r.stdout
    # the A/B must report per-peer wire bytes, labelled with the forced
    # strategy family and the codec dimension
    want_label = "RING_SEGMENTED" if algo == "segmented" else "BINARY_TREE"
    want_codec = f'codec="{wire or "off"}"'
    # worker stdout arrives prefixed with the runner's [rank/np] tag
    wire_lines = [l for l in r.stdout.splitlines() if "WIRE " in l]
    assert wire_lines, r.stdout
    assert any(want_label in l and want_codec in l for l in wire_lines), (
        r.stdout
    )
    if wire:
        # compressed leg must also report the bytes the codec saved
        assert any("saved by codec" in l for l in wire_lines), r.stdout
    # ISSUE 6: utilization, not just bytes — the EFF report attributes
    # walk time (wait/compute/send) and names the strategy that ran
    eff_lines = [l for l in r.stdout.splitlines() if "EFF " in l]
    assert eff_lines, r.stdout
    assert any(want_label in l and "wait" in l and "walks)" in l
               for l in eff_lines), r.stdout


def _run_bench(np_, env_extra, timeout=240):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["KF_CONFIG_SEGMENT_MIN_BYTES"] = "0"
    env["KF_BENCH_MODEL"] = "tiny"
    env["KF_BENCH_ITERS"] = "2"
    env.update(env_extra)
    return subprocess.run(
        [
            sys.executable, "-m", "kungfu_tpu.runner.cli",
            *kfrun_ports().args,  # this xdist worker's block
            "-np", str(np_), "-H", f"127.0.0.1:{np_}",
            sys.executable, AGENT,
        ],
        env=env, capture_output=True, text=True, timeout=timeout, cwd=REPO,
    )


def test_bench_survives_lockwatch_np4():
    """ISSUE 7 bench guard: the KF_DEBUG_LOCKS runtime detector rides
    the REAL segmented + pipelined walk at np=4 — it must neither break
    the engine nor cry wolf (no lock_order_violation, no long-held at
    the default 1s threshold) on a deadlock-free workload."""
    # 10s long-held threshold: worker STARTUP legitimately holds the
    # singleton-init lock across the whole cluster rendezvous and the
    # per-peer send lock across a first dial's retry backoff (seconds on
    # a loaded 2-core box) — the walk itself must stay clean far below it
    r = _run_bench(4, {
        "KF_DEBUG_LOCKS": "1",
        "KF_DEBUG_LOCKS_HELD_MS": "10000",
        "KF_BENCH_ALGO": "segmented",
    })
    out = r.stdout + r.stderr
    assert r.returncode == 0, out
    assert "RESULT:" in r.stdout, out
    assert "lock_order_violation" not in out, out
    assert "lock_long_held" not in out, out


def test_lockwatch_live_in_workers_positive_control():
    """Prove the detector is actually running inside bench workers (so
    the clean np=4 run above is meaningful): a microscopic long-held
    threshold must make every worker report — end-to-end through
    install, instrumentation and the telemetry log."""
    r = _run_bench(2, {
        "KF_DEBUG_LOCKS": "1",
        "KF_DEBUG_LOCKS_HELD_MS": "0.000001",
        "KF_BENCH_ALGO": "segmented",
    })
    out = r.stdout + r.stderr
    assert r.returncode == 0, out
    assert "lock_long_held" in out, out
    assert "lock_order_violation" not in out, out
