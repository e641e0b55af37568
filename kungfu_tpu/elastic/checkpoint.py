"""Checkpoint/resume for elastic and failure-recovered training.

The reference has no general checkpoint subsystem (SURVEY §5.4): resume
relies on live state broadcast across survivors plus user-managed Keras
checkpoints reloaded on ``--restart 1``. The TPU-native build keeps the
live-broadcast path (elastic/state.py) for in-flight membership changes
and adds a real checkpointer for the cases live state cannot cover — a
full-cluster restart (kfrun -auto-recover relaunch, preemption of every
host) — built on orbax, the JAX-ecosystem checkpoint library.

Also provides ``dump_final_variables`` (parity: hooks/elastic.py:80-87,
the ad-hoc ``variables-final.npz`` dump), dtype-faithful for bf16 via
base/serialize.

Usage with the auto-recover contract::

    ckpt = Checkpointer(logdir)            # every rank; saves on rank 0
    state, start = ckpt.restore_or((params, opt_state))
    for epoch in range(start, n_epochs):
        ...
        state = (params, opt_state)
        ckpt.save(epoch + 1, state)        # after the epoch completes
        cmd.monitor_epoch_end()

On relaunch, KF_RECOVER_EPOCH (set by the monitored runner from the
heartbeat min-epoch) caps the restore step: a checkpoint AHEAD of the
cluster-wide safe epoch is skipped so every worker resumes from the same
step.
"""

from __future__ import annotations

import os
import time
from typing import Any, Optional, Tuple

from kungfu_tpu.runner.monitored import RECOVER_EPOCH_ENV
from kungfu_tpu.telemetry import tracing as trace


def _tree_bytes(state: Any) -> int:
    """The bytes of a pytree's leaves, a global array's whole."""
    import jax
    import numpy as np

    return sum(
        int(getattr(leaf, "nbytes", None) or np.asarray(leaf).nbytes)
        for leaf in jax.tree.leaves(state)
    )


class Checkpointer:
    """Orbax-backed (step, pytree) checkpoints with a bounded window.

    Saving is rank-0-only by default (synchronous data parallelism keeps
    state replicated); every rank restores from the same directory —
    colocated workers share the local FS, multi-host clusters need a
    shared path (e.g. GCS, which orbax speaks natively). Workers that
    are one JAX world (`initialize_device_plane()`) hold global arrays,
    which orbax saves and restores with every process taking part:
    `save_rank=None` there.

    Spans: `checkpoint.open` (orbax's import and the manager),
    `checkpoint.save` (`step`, `bytes`, `rank`, `written`; on every rank,
    a rank that does not write shows that it did not wait) and
    `checkpoint.restore` (`step`, `bytes`), each around the orbax calls
    and awaited inside."""

    def __init__(
        self,
        directory: str,
        max_to_keep: int = 3,
        save_rank: Optional[int] = 0,
    ):
        self.directory = os.path.abspath(directory)
        self.save_rank = save_rank
        with trace.span("checkpoint.open", import_s=0.0) as sp:
            import orbax.checkpoint as ocp

            # what is left of the span is the manager, which in one jax
            # world waits for every process to have made its own
            sp.args["import_s"] = round(time.perf_counter() - sp.t0, 3)
            self._ocp = ocp
            self.mgr = ocp.CheckpointManager(
                self.directory,
                options=ocp.CheckpointManagerOptions(max_to_keep=max_to_keep),
            )

    def _my_rank(self) -> int:
        try:
            from kungfu_tpu import api

            return api.current_rank()
        # kfcheck: disable=KF400 — checkpointing is usable without a
        # cluster; no api/peer means single-process rank 0 by contract
        except Exception:  # noqa: BLE001
            return 0

    def save(self, step: int, state: Any, force: bool = False) -> bool:
        """Save `state` at `step`; returns True if written (rank-gated)."""
        rank = self._my_rank()
        written = self.save_rank is None or rank == self.save_rank
        with trace.span("checkpoint.save", step=int(step),
                        bytes=_tree_bytes(state), rank=rank, written=written):
            if written:
                self.mgr.save(
                    step, args=self._ocp.args.StandardSave(state), force=force
                )
                self.mgr.wait_until_finished()
        return written

    def latest_step(self) -> Optional[int]:
        """Newest step not beyond the cluster-wide safe resume epoch
        (KF_RECOVER_EPOCH, when the monitored runner provides one)."""
        steps = sorted(self.mgr.all_steps())
        from kungfu_tpu import knobs

        cap = knobs.raw(RECOVER_EPOCH_ENV)
        if cap:
            steps = [s for s in steps if s <= int(cap)]
        return steps[-1] if steps else None

    def restore(self, abstract_state: Any, step: Optional[int] = None) -> Any:
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {self.directory}")
        import jax

        with trace.span("checkpoint.restore", step=int(step)) as sp:
            state = jax.block_until_ready(self.mgr.restore(
                step, args=self._ocp.args.StandardRestore(abstract_state)
            ))
            sp.args["bytes"] = _tree_bytes(state)
        return state

    def restore_or(self, default_state: Any) -> Tuple[Any, int]:
        """(state, start_step): the newest safe checkpoint, or the given
        initial state at step 0."""
        step = self.latest_step()
        if step is None:
            return default_state, 0
        return self.restore(default_state, step), step

    def close(self) -> None:
        self.mgr.close()


def dump_final_variables(path: str, tree: Any) -> None:
    """Dump a pytree's leaves to one file at end of training (parity:
    variables-final.npz, hooks/elastic.py:80-87). Uses the dtype-faithful
    pack format — np.savez cannot round-trip bf16."""
    import jax

    from kungfu_tpu.base.serialize import pack_leaves

    leaves = jax.tree.leaves(jax.device_get(tree))
    with open(path, "wb") as f:
        f.write(pack_leaves(leaves))


def load_final_variables(path: str, like: Any) -> Any:
    """Inverse of dump_final_variables, re-shaped onto `like`'s treedef."""
    import jax

    from kungfu_tpu.base.serialize import unpack_leaves

    leaves, treedef = jax.tree.flatten(like)
    with open(path, "rb") as f:
        out = unpack_leaves(f.read(), len(leaves))
    return jax.tree.unflatten(treedef, out)
