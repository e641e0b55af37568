"""Worker process spawning and log streaming.

Capability parity: srcs/go/proc/proc.go + srcs/go/utils/runner/local
(parallel local exec with colored per-proc log prefixes and per-worker log
files) and srcs/go/kungfu/job/job.go (env construction).
"""

from __future__ import annotations

import collections
import os
import subprocess
import sys
import threading
from typing import Dict, List, Optional

# last-words ring per worker: postmortems include output even when the
# flight journal is missing or empty (ISSUE 3 satellite)
OUTPUT_TAIL_LINES = 200

from kungfu_tpu.telemetry import log

_COLORS = [31, 32, 33, 34, 35, 36, 91, 92, 93, 94, 95, 96]

# Orphan protection: children get SIGTERM when the runner dies
# (PR_SET_PDEATHSIG), so a hard-killed runner (SIGKILL, OOM) cannot leave
# workers or warm standbys lingering (an orphan that touched the backend
# keeps its chips). The arming must NOT happen via preexec_fn — calling
# into ctypes between fork and exec in a threaded runner deadlocks
# intermittently on locks held by threads that don't exist in the child
# (observed ~1/3 of spawns under a jax-threaded parent). Instead a tiny
# exec shim (native/pdeathsig.c, built by native/build.sh) arms the
# signal in a fresh single-threaded process and execvp's the real
# command; python -m kungfu_tpu.runner.standby additionally arms itself
# in-process, covering standbys even without the shim.
_PDEATHSIG_SHIM = os.path.join(os.path.dirname(__file__), "kf-pdeathsig")
_warned_no_shim = False
_shim_broken = False  # set after the first exec failure: skip doomed retries


def _shim_argv(argv: List[str]) -> List[str]:
    if not _shim_broken and os.access(_PDEATHSIG_SHIM, os.X_OK):
        return [_PDEATHSIG_SHIM] + list(argv)
    global _warned_no_shim
    if not _warned_no_shim and os.name == "posix":
        _warned_no_shim = True
        log.warn(
            "kfrun: kf-pdeathsig shim not built (native/build.sh); workers "
            "will not be reaped if this runner is hard-killed"
        )
    return list(argv)


def _color(i: int, s: str) -> str:
    if not sys.stdout.isatty():
        return s
    return f"\x1b[{_COLORS[i % len(_COLORS)]}m{s}\x1b[0m"


class WorkerProc:
    def __init__(
        self,
        name: str,
        argv: List[str],
        env: Dict[str, str],
        rank: int = 0,
        logdir: Optional[str] = None,
        quiet: bool = False,
        cpus: Optional[List[int]] = None,
    ):
        self.name = name
        self.argv = argv
        self.env = env
        self.rank = rank
        self.logdir = logdir
        self.quiet = quiet
        self.cpus = cpus  # CPU affinity mask (runner/affinity.py plan)
        self.proc: Optional[subprocess.Popen] = None
        self._threads: List[threading.Thread] = []
        self._tail: "collections.deque[str]" = collections.deque(
            maxlen=OUTPUT_TAIL_LINES
        )
        self._tail_lock = threading.Lock()

    def start(self) -> None:
        full_env = dict(os.environ)
        full_env.update(self.env)
        # explicit runner pid for the shim/standby died-before-arm check
        full_env["KF_RUNNER_PID"] = str(os.getpid())
        argv = _shim_argv(self.argv)
        try:
            self.proc = subprocess.Popen(
                argv,
                env=full_env,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
                bufsize=1,
            )
        except OSError as e:
            import errno as _errno

            if argv is self.argv or argv == list(self.argv):
                raise
            if e.errno not in (_errno.ENOEXEC, _errno.EACCES, _errno.ENOENT):
                # transient spawn failure (EMFILE/ENOMEM/EAGAIN): NOT the
                # shim's fault — surface it, don't latch protection off
                raise
            # the committed shim binary doesn't run on this platform/arch:
            # degrade to unprotected spawns — loudly, and only once
            global _shim_broken
            if not _shim_broken:
                _shim_broken = True
                log.warn(
                    "kfrun: kf-pdeathsig unusable (%s); spawning workers "
                    "WITHOUT orphan protection (rebuild via native/build.sh)",
                    e,
                )
            self.proc = subprocess.Popen(
                list(self.argv),
                env=full_env,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
                bufsize=1,
            )
        if self.cpus:
            from kungfu_tpu.runner.affinity import apply_affinity

            if apply_affinity(self.proc.pid, self.cpus) and not self.quiet:
                log.info("[%s] pinned to cpus %s", self.name, self.cpus)
        logfile = None
        if self.logdir:
            os.makedirs(self.logdir, exist_ok=True)
            logfile = open(os.path.join(self.logdir, f"{self.name}.log"), "w")
        for stream, tag in ((self.proc.stdout, ""), (self.proc.stderr, "!")):
            t = threading.Thread(
                target=self._pump, args=(stream, tag, logfile), daemon=True
            )
            t.start()
            self._threads.append(t)

    def _pump(self, stream, tag: str, logfile) -> None:
        for line in stream:
            # prefix computed per line: a standby proc is renamed to its
            # worker identity on activation
            prefix = _color(self.rank, f"[{self.name}{tag}] ")
            with self._tail_lock:
                self._tail.append(f"[{tag or ' '}] {line.rstrip()}")
            if logfile:
                logfile.write(f"[{tag or ' '}] {line}")
                logfile.flush()
            if not self.quiet:
                sys.stdout.write(prefix + line)
                sys.stdout.flush()

    def output_tail(self) -> List[str]:
        """The worker's last ~200 stdout/stderr lines ('[ ]'/'[!]'
        prefixed), for postmortems."""
        with self._tail_lock:
            return list(self._tail)

    def wait(self, timeout: Optional[float] = None) -> int:
        rc = self.proc.wait(timeout)
        self.drain()
        return rc

    def drain(self) -> None:
        """Let the pumps echo what an ended process wrote last: its
        traceback is there, and a runner that exits first loses it."""
        for t in self._threads:
            t.join(1)

    def kill(self) -> bool:
        """Stop the process and reap it; True if its 5 s after `terminate`
        ran out and it took a `kill` (the runner's `runner.kill` span
        says so as `escalated`)."""
        if self.proc and self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(5)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                try:
                    # reap, so returncode reads -SIGKILL instead of a
                    # stale None in the postmortem that follows
                    self.proc.wait(5)
                except subprocess.TimeoutExpired:
                    pass
                return True
        return False

    @property
    def running(self) -> bool:
        return self.proc is not None and self.proc.poll() is None


def run_all(procs: List[WorkerProc]) -> List[int]:
    """Start all procs and wait; on first failure kill the rest (parity:
    local.RunAll semantics)."""
    for p in procs:
        p.start()
    codes = [None] * len(procs)
    try:
        for i, p in enumerate(procs):
            # kfcheck: disable=KF301 — a training worker legitimately
            # runs unboundedly; KeyboardInterrupt kills the batch below
            codes[i] = p.wait()
    except KeyboardInterrupt:
        for p in procs:
            p.kill()
        raise
    if any(c != 0 for c in codes):
        for p in procs:
            p.kill()
    return [c if c is not None else -1 for c in codes]
