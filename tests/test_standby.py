"""Warm-spare standby workers + scoped tracer."""

import os
import sys
import time

import pytest

from kungfu_tpu.runner.standby import StandbyPool


def test_standby_activate_runs_command():
    pool = StandbyPool(1, quiet=True)
    env = dict(os.environ)
    try:
        pool.refill()
        assert len(pool.slots) == 1
        slot = pool.take()
        assert slot is not None and slot.alive
        deadline = time.time() + 30
        ok = False
        while not ok and time.time() < deadline:
            ok = slot.activate(
                {"KF_TEST_GREETING": "warm"},
                [sys.executable, "-c",
                 "import os, sys; sys.exit(0 if os.environ['KF_TEST_GREETING'] == 'warm' else 3)"],
                "w0", 0,
            )
            if not ok:
                time.sleep(0.1)  # fifo not open yet (python still exec'ing)
        assert ok
        assert slot.proc.wait(60) == 0
        assert slot.proc.name == "w0"
    finally:
        pool.kill_all()


def test_standby_activation_can_precede_warmup():
    """Activation written immediately after spawn must still be consumed
    (the standby opens its FIFO before warming)."""
    pool = StandbyPool(1, quiet=True)
    try:
        pool.refill()
        slot = pool.take()
        deadline = time.time() + 30
        ok = False
        while time.time() < deadline:
            ok = slot.activate(
                {}, [sys.executable, "-c", "print('fast path')"], "w1", 1
            )
            if ok:
                break
            time.sleep(0.1)  # python still exec'ing; fifo not open yet
        assert ok, "standby never opened its fifo"
        assert slot.proc.wait(60) == 0
    finally:
        pool.kill_all()


def test_standby_dead_slot_detected():
    pool = StandbyPool(1, quiet=True)
    try:
        pool.refill()
        slot = pool.take()
        slot.proc.kill()
        slot.proc.wait(10)
        # fifo has no reader anymore -> activation reports failure
        deadline = time.time() + 10
        while slot.activate({}, ["true"], "w", 0, wait=0):
            # a race where the fifo still had the dying reader attached:
            # retry until the kernel drops it
            assert time.time() < deadline
            time.sleep(0.2)
    finally:
        pool.kill_all()


def test_run_activated_python_script(tmp_path, capfd):
    from kungfu_tpu.runner.standby import run_activated

    script = tmp_path / "agent.py"
    script.write_text("import sys, os\nprint('AGENT', sys.argv[1:], os.environ['KF_X'])\n")
    old_env = os.environ.get("KF_X")
    old_argv = sys.argv
    try:
        run_activated({
            "env": {"KF_X": "42"},
            "argv": [sys.executable, str(script), "--flag", "v"],
        })
    finally:
        sys.argv = old_argv
        if old_env is None:
            os.environ.pop("KF_X", None)
    out = capfd.readouterr().out
    assert "AGENT ['--flag', 'v'] 42" in out


def test_tracer_spans():
    from kungfu_tpu.telemetry import tracing as trace

    trace.clear()
    with trace.span("t.a"):
        time.sleep(0.01)
    trace.record("t.b", 0.5)
    evs = trace.full_events("t.")
    assert [e.name for e in evs] == ["t.a", "t.b"]
    s = trace.summary_ms("t.")
    assert s["t.a"] >= 10.0
    assert s["t.b"] == 500.0


@pytest.mark.skipif(sys.platform != "linux", reason="PR_SET_PDEATHSIG is Linux-only")
def test_standby_dies_with_its_runner(tmp_path):
    """A hard-killed runner must not leave orphaned standbys
    (PR_SET_PDEATHSIG): spawn a 'runner' that creates one standby and
    idles; SIGKILL the runner; the standby must exit on its own."""
    import signal
    import subprocess

    script = tmp_path / "runner.py"
    script.write_text(
        "import sys, time\n"
        f"sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})\n"
        "from kungfu_tpu.runner.standby import StandbyPool\n"
        "pool = StandbyPool(1, quiet=True)\n"
        "pool.refill()\n"
        "print(pool.slots[0].proc.proc.pid, flush=True)\n"
        "time.sleep(600)\n"
    )
    runner = subprocess.Popen(
        [sys.executable, str(script)], stdout=subprocess.PIPE, text=True
    )
    try:
        standby_pid = int(runner.stdout.readline())
        # the standby is alive while the runner lives
        os.kill(standby_pid, 0)
        runner.kill()  # SIGKILL: no cleanup runs in the runner
        runner.wait(10)
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                os.kill(standby_pid, 0)
            except ProcessLookupError:
                return  # orphan reaped itself
            time.sleep(0.2)
        os.kill(standby_pid, signal.SIGKILL)
        raise AssertionError("standby outlived its killed runner")
    finally:
        if runner.poll() is None:
            runner.kill()
