"""Embedded runner API (parity: kungfu/cmd/__init__.py)."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = """
import sys
sys.path.insert(0, {repo!r})


def worker(rank):
    import numpy as np
    from kungfu_tpu import api

    size = api.cluster_size()
    assert api.current_rank() == rank
    out = api.all_reduce_array(np.array([rank + 1.0]))
    assert out[0] == size * (size + 1) / 2, out
    print(f"MP {{rank}}/{{size}} ok", flush=True)


if __name__ == "__main__":
    from kungfu_tpu.cmd import launch_multiprocess

    launch_multiprocess(worker, 3)
    print("DONE", flush=True)
"""


def _run_script(tmp_path, body):
    # a real file, not -c: mp spawn workers re-import __main__ by path
    p = tmp_path / "mp_main.py"
    p.write_text(body)
    return subprocess.run(
        [sys.executable, str(p)],
        capture_output=True, text=True, timeout=240,
    )


def test_launch_multiprocess(tmp_path):
    r = _run_script(tmp_path, SCRIPT.format(repo=REPO))
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert r.stdout.count("ok") == 3, r.stdout
    assert "DONE" in r.stdout


def test_launch_multiprocess_propagates_failure(tmp_path):
    script = SCRIPT.format(repo=REPO).replace(
        "assert out[0] == size * (size + 1) / 2, out",
        "raise SystemExit(3)",
    )
    r = _run_script(tmp_path, script)
    assert r.returncode != 0
    assert "workers failed" in (r.stdout + r.stderr)


def test_monitor_signal_helpers_no_monitor():
    """Best-effort: with no monitor running these are silent no-ops."""
    from kungfu_tpu import cmd

    cmd.monitor_batch_begin(0)
    cmd.monitor_batch_end(0)
    cmd.monitor_epoch_end(0)
    cmd.monitor_train_end(0)


def test_launch_multiprocess_refuses_when_parent_holds_chips(monkeypatch):
    """The children get no device slots: started from a process whose JAX
    backend already owns the accelerators they would hang opening them."""
    import pytest

    from kungfu_tpu import cmd

    monkeypatch.setattr(cmd, "_holds_chips", lambda: True)
    with pytest.raises(RuntimeError, match="holds the accelerators"):
        cmd.launch_multiprocess(lambda rank: None, 2)
    monkeypatch.undo()
    assert cmd._holds_chips() is False  # the CPU backend holds nothing
