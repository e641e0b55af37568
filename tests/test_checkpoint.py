"""Checkpoint/resume subsystem (orbax-backed; parity+: SURVEY §5.4)."""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from ports import kfrun_ports

from kungfu_tpu.elastic.checkpoint import (
    Checkpointer,
    dump_final_variables,
    load_final_variables,
)


def _state(v):
    return {
        "params": {"w": jnp.full((3, 2), float(v)), "b": jnp.ones(2) * v},
        "opt": {"momentum": jnp.zeros(2)},
    }


def test_save_restore_roundtrip(tmp_path):
    ckpt = Checkpointer(str(tmp_path / "ck"), save_rank=None)
    assert ckpt.latest_step() is None
    state, start = ckpt.restore_or(_state(0))
    assert start == 0
    for step in (1, 2, 3):
        assert ckpt.save(step, _state(step))
    out, start = ckpt.restore_or(_state(0))
    assert start == 3
    np.testing.assert_array_equal(np.asarray(out["params"]["w"]), np.full((3, 2), 3.0))
    ckpt.close()


def test_window_bounds_old_steps(tmp_path):
    ckpt = Checkpointer(str(tmp_path / "ck"), max_to_keep=2, save_rank=None)
    for step in range(1, 5):
        ckpt.save(step, _state(step))
    steps = sorted(ckpt.mgr.all_steps())
    assert steps == [3, 4], steps
    ckpt.close()


def test_recover_epoch_caps_restore(tmp_path, monkeypatch):
    """A checkpoint ahead of the cluster-wide safe epoch must be skipped
    (KF_RECOVER_EPOCH contract of the monitored runner)."""
    ckpt = Checkpointer(str(tmp_path / "ck"), save_rank=None)
    for step in (1, 2, 3):
        ckpt.save(step, _state(step))
    monkeypatch.setenv("KF_RECOVER_EPOCH", "2")
    assert ckpt.latest_step() == 2
    out, start = ckpt.restore_or(_state(0))
    assert start == 2
    np.testing.assert_array_equal(np.asarray(out["params"]["b"]), [2.0, 2.0])
    ckpt.close()


def test_rank_gating(tmp_path, monkeypatch):
    ckpt = Checkpointer(str(tmp_path / "ck"), save_rank=0)
    monkeypatch.setattr(Checkpointer, "_my_rank", lambda self: 1)
    assert not ckpt.save(1, _state(1))
    assert ckpt.latest_step() is None
    monkeypatch.setattr(Checkpointer, "_my_rank", lambda self: 0)
    assert ckpt.save(1, _state(1))
    ckpt.close()


def test_open_save_and_restore_leave_one_span_each(tmp_path, monkeypatch):
    """What a checkpoint costs a step or a resize is read from the ring:
    one span a call, with the step and the bytes of the tree."""
    from kungfu_tpu.telemetry import tracing

    tree_bytes = 3 * 2 * 4 + 2 * 4 + 2 * 4  # w, b, momentum: float32
    tracing.clear()
    ckpt = Checkpointer(str(tmp_path / "ck"), save_rank=0)
    assert ckpt.restore_or(_state(0))[1] == 0  # nothing to restore
    assert not tracing.full_events("checkpoint.restore")
    assert ckpt.save(5, _state(5))
    monkeypatch.setattr(Checkpointer, "_my_rank", lambda self: 1)
    assert not ckpt.save(6, _state(6))  # not this rank's to write: no wait either
    out, start = ckpt.restore_or(_state(0))
    assert start == 5
    events = {}
    for e in tracing.full_events("checkpoint."):
        events.setdefault(e.name, []).append(e.args)
    (opened,) = events["checkpoint.open"]
    assert opened["import_s"] >= 0
    assert events["checkpoint.save"] == [
        {"step": 5, "bytes": tree_bytes, "rank": 0, "written": True},
        {"step": 6, "bytes": tree_bytes, "rank": 1, "written": False},
    ]
    assert events["checkpoint.restore"] == [{"step": 5, "bytes": tree_bytes}]
    ckpt.close()
    tracing.clear()


def test_dump_final_variables_bf16(tmp_path):
    tree = {"w": jnp.arange(6, dtype=jnp.bfloat16) / 3, "s": jnp.float32(2.5)}
    path = str(tmp_path / "variables-final.kf")
    dump_final_variables(path, tree)
    out = load_final_variables(path, tree)
    assert np.asarray(out["w"]).dtype == np.asarray(tree["w"]).dtype
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))
    assert float(out["s"]) == 2.5


def test_checkpoint_resume_under_auto_recover(tmp_path):
    """kfrun -auto-recover: a worker crashes after the epoch-3 checkpoint;
    the relaunch restores from it (capped by KF_RECOVER_EPOCH) and the
    final accumulated state is exactly the no-crash result."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    agent = os.path.join(repo, "tests", "integration", "ckpt_agent.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [
            sys.executable, "-m", "kungfu_tpu.runner.cli",
            *kfrun_ports().args,  # this xdist worker's block
            "-np", "2", "-H", "127.0.0.1:2",
            "-auto-recover", "30s",
            sys.executable, agent, str(tmp_path / "ck"),
        ],
        env=env, capture_output=True, text=True, timeout=540, cwd=repo,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "crash after epoch 3 checkpoint" in r.stdout
    done = [l for l in r.stdout.splitlines() if "agent done" in l]
    assert len(done) == 2, r.stdout
    for l in done:
        assert "acc=10.0" in l, l
    # the relaunch really resumed (start>=2), it didn't redo everything
    resumed = [l for l in r.stdout.splitlines() if "restart=True" in l]
    assert len(resumed) == 2, r.stdout
