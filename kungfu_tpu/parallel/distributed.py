"""Multi-host device-plane bootstrap over the host control plane.

The reference bootstraps its GPU data plane by having rank 0 create an
NCCL unique id and broadcasting it over the CPU collective
(srcs/cpp/src/nccl/gpu_collective.cpp:190-243). The TPU-native analog:
rank 0 picks a JAX coordination-service address, broadcasts it over the
HOST plane (kfrun's TCP collectives), and every worker calls
`jax.distributed.initialize` with its host-plane rank — after which
`jax.devices()` spans ALL workers' chips and one `jax.sharding.Mesh` /
compiled program covers the whole cluster (SURVEY §7 stages 4+6).

Elastic semantics:
- reload mode (PRIMARY on TPU — the ICI mesh shape is fixed per slice):
  workers exit on resize, runners respawn them, and the fresh processes
  bootstrap a fresh device plane here. Nothing to tear down.
- delta mode: `reinitialize_device_plane()` tears the XLA backend down
  in-process (distributed shutdown + backend clear) and bootstraps again
  over the NEW host session. Works on CPU clusters; on real TPU pods
  prefer reload mode — the TPU runtime does not always release chips
  cleanly for in-process re-init.
"""

from __future__ import annotations

import os
import socket
import threading
from typing import Optional

from kungfu_tpu.telemetry import tracing
from kungfu_tpu.utils import log
from kungfu_tpu.utils.stall import stall_detect

_state = {"initialized": False, "local_only": False, "version": -1}
_lock = threading.Lock()


def _free_port(host: str) -> int:
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    try:
        s.bind((host if host not in ("localhost",) else "127.0.0.1", 0))
        return s.getsockname()[1]
    finally:
        s.close()


_MAX_CHIP_ID = 64


def _chip_coords(sess, slots, version: int) -> dict:
    """{chip id: [x, y, z]} of every one-chip worker of this world, each
    telling where in the ICI grid its chip sits, summed over the host
    plane. Which chip ids are neighbours differs between hosts, and the
    runner that makes the next world's process grid cannot ask libtpu
    (runner/env.py). {} where the devices have no coordinates (the CPU)."""
    import jax
    import numpy as np

    from kungfu_tpu.base.ops import ReduceOp
    from kungfu_tpu.base.workspace import Workspace

    mine = np.zeros((_MAX_CHIP_ID, 4), np.int64)
    coords = getattr(jax.local_devices()[0], "coords", None)
    if len(slots) == 1 and slots[0] < _MAX_CHIP_ID and coords is not None:
        mine[slots[0]] = (1, *coords)
    table = np.zeros_like(mine)
    sess.all_reduce(Workspace(
        mine.reshape(-1), table.reshape(-1), ReduceOp.SUM,
        f"kungfu::chipcoords:v{version}",
    ))
    return {str(chip): [int(v) for v in row[1:]]
            for chip, row in enumerate(table) if row[0] == 1}


def device_plane_initialized() -> bool:
    return _state["initialized"]


def initialize_device_plane(platform: Optional[str] = None) -> None:
    """Stand up ONE JAX world across all workers of the current cluster.

    Must run before any other JAX API touches the backend (jax.devices()
    etc.) — the same constraint the reference's NCCL init has. Single
    process (no kfrun): no-op, local devices only.

    Workers that kfrun pinned to chips of one host (`-devices-per-host`)
    are each a device world of their own until they call this: it applies
    the libtpu variables the runner derived for the joined world
    (`KF_DEVICE_WORLD`, runner/env.py) before the backend starts.
    """
    import jax

    from kungfu_tpu.parallel.chip import enable_compile_cache
    from kungfu_tpu.peer import get_default_peer

    with _lock:
        if _state["initialized"]:
            return
        peer = get_default_peer()
        if platform:
            jax.config.update("jax_platforms", platform)
        with tracing.span("device_plane.compile_cache"):
            enable_compile_cache()
        sess = peer.current_session()
        if peer.config.single_process or sess.size == 1:
            _state["local_only"] = True
            _state["initialized"] = True
            log.debug("device plane: single-process, local devices only")
            return
        if peer.config.device_slots:
            if not peer.config.device_world:
                raise RuntimeError(
                    "device plane: this worker is pinned to chips "
                    f"{peer.config.device_slots} but kfrun described no joined "
                    "world for the layout (workers must be on one host and "
                    "hold its chips in rank order; see runner/env.py)"
                )
            os.environ.update(peer.config.device_world)
        if sess.rank == 0:
            host = peer.self_id.host
            addr = f"{host}:{_free_port(host)}".encode()
        else:
            addr = b""
        with stall_detect("device_plane_bootstrap"):
            with tracing.span("device_plane.bootstrap"):
                addr = sess.broadcast_bytes(
                    addr, f"kungfu::devplane:v{peer.cluster_version}")
            coordinator = addr.decode()
            log.info(
                "device plane: initializing process %d/%d, coordinator %s",
                sess.rank, sess.size, coordinator,
            )
            with tracing.span("device_plane.distributed_initialize"):
                jax.distributed.initialize(
                    coordinator_address=coordinator,
                    num_processes=sess.size,
                    process_id=sess.rank,
                )
            # the backend starts at the first call that needs it; taken here
            # so that its seconds are timed where they are spent
            with tracing.span("device_plane.backend_start"):
                jax.devices()
            if peer.config.device_slots:
                with tracing.span("device_plane.chip_coords"):
                    peer.chip_coords = _chip_coords(
                        sess, peer.config.device_slots, peer.cluster_version)
        _state["initialized"] = True
        _state["local_only"] = False
        _state["version"] = peer.cluster_version


def shutdown_device_plane() -> None:
    """Tear down the distributed JAX backend so a new world can form."""
    import jax

    with _lock:
        if not _state["initialized"]:
            return
        if not _state["local_only"]:
            jax.distributed.shutdown()
        # Drop live backends + compiled programs so the next JAX call (after
        # re-initialize) builds a client for the NEW process set. JAX has no
        # public backend-reset API, so this leans on a private one and
        # fails loudly, pointing at reload mode, where it is missing.
        try:
            from jax._src import xla_bridge

            xla_bridge._clear_backends()
        except (ImportError, AttributeError) as e:
            _state["initialized"] = False
            _state["local_only"] = False
            raise RuntimeError(
                "cannot reset the XLA backend in-process with this JAX "
                "version; use elastic reload mode (process restart) instead"
            ) from e
        jax.clear_caches()
        _state["initialized"] = False
        _state["local_only"] = False


def reinitialize_device_plane(platform: Optional[str] = None) -> None:
    """Delta-mode elastic rebuild: new host session -> new JAX world.

    The caller must drop references to arrays/compiled functions from the
    old world first (they hold the old backend alive). Parity: NCCL
    ReInit per new cluster version (nccl/controller.hpp:14-44).

    Under `device_plane.reinitialize` (`from_version`: the world torn
    down), with `device_plane.shutdown` and the bootstrap's own spans
    inside it: this has not met the chip, and whoever tries it first
    reads from the ring, or from the open spans, where it stopped.
    """
    with tracing.span("device_plane.reinitialize", from_version=_state["version"]):
        with tracing.span("device_plane.shutdown"):
            shutdown_device_plane()
        initialize_device_plane(platform)


def current_device_plane_version() -> int:
    return _state["version"]
