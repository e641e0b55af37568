"""Embedded runner API: launch a single-machine multi-process cluster
from Python, plus the failure-monitor signal helpers.

Capability parity: srcs/python/kungfu/cmd/__init__.py —
``launch_multiprocess(f, np)`` (cmd/__init__.py:45-49) and the
``monitor_batch_begin/end`` / ``monitor_epoch_end`` / ``monitor_train_end``
signal functions (:18-31) that feed the -auto-recover heartbeat monitor.
"""

from __future__ import annotations

import os
import socket
import sys
from typing import Callable, List

from kungfu_tpu.runner.monitored import send_heartbeat


def _reserve_ports(n: int) -> List[int]:
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _run_worker(f: Callable[[int], None], rank: int, env: dict) -> None:
    os.environ.update(env)
    f(rank)
    # deterministic teardown before the process exits (atexit also covers
    # it, but multiprocessing's exit path is less forgiving)
    from kungfu_tpu.peer import finalize_default_peer

    finalize_default_peer()


def _holds_chips() -> bool:
    """Has this process started a JAX backend that owns accelerators?"""
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized() and jax.default_backend() != "cpu"


def launch_multiprocess(f: Callable[[int], None], np_: int) -> None:
    """Run ``f(rank)`` in ``np_`` local worker processes wired into one
    host-plane cluster (parity: launch_multiprocess). Inside ``f`` the
    normal API works: ``kungfu_tpu.api.current_rank()``, collectives,
    optimizers. Raises RuntimeError if any worker exits nonzero.

    The workers get no device slots (kfrun's ``-devices-per-host`` does
    that), so this refuses to start them from a process that already
    holds the chips: a chip belongs to one process, and the children
    would hang or fail opening it."""
    import multiprocessing as mp

    if _holds_chips():
        raise RuntimeError(
            "launch_multiprocess: this process has started a JAX backend "
            "that holds the accelerators, so its children could not open "
            "them; launch before touching jax, or use kfrun "
            "-devices-per-host"
        )

    from kungfu_tpu.plan.peer import PeerID, PeerList
    from kungfu_tpu.runner import env as kfenv

    peers = PeerList(
        [PeerID("127.0.0.1", p) for p in _reserve_ports(np_)]
    )
    envs = [
        kfenv.worker_env(
            self_id=peers[r],
            peers=peers,
            runners=PeerList(),
            parent=None,
        )
        for r in range(np_)
    ]
    ctx = mp.get_context("spawn")
    procs = [
        ctx.Process(target=_run_worker, args=(f, r, envs[r]), daemon=False)
        for r in range(np_)
    ]
    for p in procs:
        p.start()
    for p in procs:
        # kfcheck: disable=KF302 — the workers ARE the foreground job; the
        # launcher's contract is to block for their whole (unbounded)
        # training run, and Ctrl-C interrupts the join
        p.join()
    bad = [(i, p.exitcode) for i, p in enumerate(procs) if p.exitcode != 0]
    if bad:
        raise RuntimeError(f"launch_multiprocess: workers failed: {bad}")


def monitor_batch_begin(rank: int = -1) -> None:
    """Heartbeat: a batch started (parity: monitor_batch_begin)."""
    send_heartbeat("begin", _rank(rank))


def monitor_batch_end(rank: int = -1) -> None:
    send_heartbeat("end", _rank(rank))


def monitor_epoch_end(rank: int = -1) -> None:
    send_heartbeat("epoch", _rank(rank))


def monitor_train_end(rank: int = -1) -> None:
    send_heartbeat("trainend", _rank(rank))


def _rank(rank: int) -> int:
    if rank >= 0:
        return rank
    try:
        from kungfu_tpu import api

        return api.current_rank()
    # kfcheck: disable=KF400 — heartbeats are best-effort: outside a
    # cluster api.current_rank() has no peer and rank 0 is the contract
    except Exception:  # noqa: BLE001
        return 0
