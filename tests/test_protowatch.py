"""Runtime collective-order sentinel (ISSUE 12): a live np=4 cluster
runs clean under KF_DEBUG_PROTOCOL=1 (no false divergences from real
overlapped traffic), an injected divergence — one peer submits an extra
tensor — is reported with the exact tensor and call site on EVERY peer
BEFORE any rendezvous hang, and with the knob unset the module is never
imported and the session's methods stay the plain class functions
(zero overhead, subprocess-asserted like lockwatch).
"""

import os
import subprocess
import sys

import pytest

from ports import kfrun_ports

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AGENT = os.path.join(REPO, "tests", "integration", "protowatch_agent.py")


def _run(np_, extra_env=None, timeout=150):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["KF_DEBUG_PROTOCOL"] = "1"
    if extra_env:
        env.update(extra_env)
    return subprocess.run(
        [
            sys.executable, "-m", "kungfu_tpu.runner.cli",
            *kfrun_ports().args,
            "-np", str(np_), "-H", f"127.0.0.1:{np_}",
            sys.executable, AGENT,
        ],
        env=env, capture_output=True, text=True, timeout=timeout, cwd=REPO,
    )


def test_unset_knob_imports_nothing_hot_path_untouched():
    """KF_DEBUG_PROTOCOL unset: protowatch is never imported and the
    session's collective entry points are the plain class functions —
    the sentinel costs literally zero when off."""
    env = dict(os.environ)
    env.pop("KF_DEBUG_PROTOCOL", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys\n"
         "import numpy as np\n"
         "from kungfu_tpu import api\n"
         "from kungfu_tpu.peer import get_default_peer\n"
         "api.all_reduce_array(np.ones(4, np.float32))\n"
         "sess = get_default_peer().current_session()\n"
         "assert sess._protowatch is None\n"
         "assert 'all_reduce' not in vars(sess), 'entry point wrapped'\n"
         "assert not any('protowatch' in m for m in sys.modules), \\\n"
         "    'protowatch imported without the knob'\n"
         "print('clean')"],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "clean" in r.stdout


def test_np4_live_bench_clean_under_sentinel():
    """Acceptance: a healthy np=4 workload — sync rounds with explicit
    boundary checks plus async scheduler rounds whose flushes
    auto-check — must come back agreed on every peer, zero divergence
    events (the sentinel must not cry wolf on real overlapped traffic).
    Runs SHAPED with a lockstep re-plan round (ISSUE 14): the shaped
    harness + vote/exchange/adopt collectives must stay silent too."""
    r = _run(4, extra_env={
        "KF_SHAPE_LINKS": f"{kfrun_ports().worker(1)}>{kfrun_ports().worker(2)}=lat:5",
        "KF_CONFIG_REPLAN": "auto",
    })
    out = r.stdout + r.stderr
    assert r.returncode == 0, out
    assert out.count("CLEAN-OK") == 4, out
    assert "protocol_divergence" not in out, out


def test_injected_divergence_named_on_every_peer_before_hang():
    """Acceptance: rank 0 submits an extra tensor into the scheduler's
    registration round. Every peer must (a) get the engine's named
    RuntimeError instead of a hang, and (b) carry a protocol_divergence
    audit event naming the extra tensor AND the submitting call site —
    the run completes in seconds, far inside any walk timeout."""
    r = _run(4, extra_env={"PROTOWATCH_INJECT": "1"}, timeout=150)
    out = r.stdout + r.stderr
    assert r.returncode == 0, out
    assert out.count("INJECT-RAISED") == 4, out
    assert out.count("INJECT-REPORT") == 4, out
    assert "pw-extra-tensor" in out, out
    assert "protowatch_agent.py" in out, out


def test_single_process_record_check_cycle():
    """In-process smoke on a cluster of one: entries record, the check
    is a local no-op that still advances the round, stats expose the
    window."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["KF_DEBUG_PROTOCOL"] = "1"
    r = subprocess.run(
        [sys.executable, "-c",
         "import numpy as np\n"
         "from kungfu_tpu import api\n"
         "from kungfu_tpu.peer import get_default_peer\n"
         "from kungfu_tpu.devtools import protowatch\n"
         "api.all_reduce_array(np.ones(8, np.float32))\n"
         "sess = get_default_peer().current_session()\n"
         "st = protowatch.stats(sess)\n"
         "assert st['window'] >= 1, st\n"
         "assert protowatch.check(sess)\n"
         "st = protowatch.stats(sess)\n"
         "assert st['window'] == 0 and st['round'] == 1, st\n"
         "assert st['divergences'] == 0, st\n"
         "print('ok', st)"],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ok" in r.stdout
