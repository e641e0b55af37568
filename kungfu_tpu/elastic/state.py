"""ElasticState: progress-based elastic training loop driver.

Capability parity: srcs/python/kungfu/python/elastic_state.py:4-79 +
KungFuElasticTrainHook's state re-sync (hooks/elastic.py:46-57) —
  es = ElasticState(max_progress)
  es.register_state(get_state, set_state)   # joiner weight re-sync
  while not es.stopped():
      with es.scope():          # begin(): sync progress + state after resize
          train_one_batch()
          es.end(batch_size)    # progress += n, maybe resize
                                # (es.advance is an alias for es.end)
Stop reasons: 'finished' | 'detached' | 'reload'.

After every membership change begin() (a) adopts the cluster-max progress
via an int-max allreduce and (b) if state callbacks are registered,
broadcasts rank-0's training state over the host plane so joining workers
inherit live weights instead of fresh-initialized ones (the reference
re-broadcasts variables + re-syncs progress in its elastic hook).
"""

from __future__ import annotations

import contextlib
from typing import Callable, Optional

import numpy as np

from kungfu_tpu import api
from kungfu_tpu.base.serialize import pack_leaves as _pack_leaves
from kungfu_tpu.base.serialize import unpack_leaves as _unpack_leaves


class ElasticState:
    def __init__(self, max_progress: Optional[int] = None, reload_mode: bool = False):
        from kungfu_tpu.peer import get_default_peer

        self.max_progress = max_progress
        self.reload_mode = reload_mode
        self._peer = get_default_peer()
        self.progress = self._peer.config.init_progress
        self._synced = False
        self._stop_reason: Optional[str] = None
        self._get_state: Optional[Callable] = None
        self._set_state: Optional[Callable] = None
        # last checkpoint version this driver saved/restored (stamped
        # onto resize audit records); None until note_checkpoint()
        self._checkpoint_version: Optional[int] = None

    def note_checkpoint(self, version: int) -> None:
        """Tell the elastic driver which checkpoint version now covers
        `progress` — recorded on the next resize's audit entry."""
        self._checkpoint_version = int(version)

    def register_state(self, get_state: Callable, set_state: Callable) -> None:
        """Register training-state callbacks for joiner re-sync.

        get_state() -> pytree of arrays (params + optimizer state);
        set_state(pytree) installs the received values. Called only after
        membership changes, never in the steady-state step path.
        """
        self._get_state = get_state
        self._set_state = set_state

    def _sync_state(self) -> None:
        if self._get_state is None:
            return
        from kungfu_tpu.telemetry import tracing as trace

        with trace.span("elastic.sync_state"):
            self._sync_state_traced()

    def _sync_state_traced(self) -> None:
        import jax

        from kungfu_tpu.base.ops import ReduceOp
        from kungfu_tpu.base.workspace import Workspace

        sess = self._peer.current_session()
        if sess.size == 1:
            return
        # Pick a provably SURVIVING broadcast root: the new cluster's order
        # comes verbatim from the user's config PUT, so rank 0 may be a
        # fresh joiner whose state must never overwrite the survivors'.
        # Each peer votes (its rank if it lived through a previous epoch);
        # the min survivor rank becomes the root. Two more scalars ride the
        # same vote: the joiner count (a pure shrink has none -> skip the
        # broadcast entirely) gated by the MIN below.
        big = np.int64(1 << 30)
        survivor = self._peer.epoch_count > 1
        v = f"v{self._peer.cluster_version}"
        root_in = np.array([sess.rank if survivor else big], np.int64)
        root_out = np.zeros(1, np.int64)
        sess.all_reduce(
            Workspace(root_in, root_out, ReduceOp.MIN, f"kungfu::syncroot:{v}")
        )
        fresh_in = np.array([0 if survivor else 1], np.int64)
        fresh_out = np.zeros(1, np.int64)
        sess.all_reduce(
            Workspace(fresh_in, fresh_out, ReduceOp.SUM, f"kungfu::syncfresh:{v}")
        )
        n_fresh = int(fresh_out[0])
        if n_fresh == 0:
            return  # pure shrink: survivors are already in sync
        # fresh world (startup / reload): root 0 = initializer broadcast
        root = int(root_out[0]) if root_out[0] < big else 0
        tree = self._get_state()
        leaves, treedef = jax.tree.flatten(tree)
        blob = _pack_leaves(leaves) if sess.rank == root else b""
        got = sess.broadcast_bytes(blob, f"kungfu::statesync:{v}", root=root)
        if sess.rank != root and self._set_state is not None:
            new_leaves = _unpack_leaves(got, len(leaves))
            new_leaves = [
                np.asarray(nl).astype(np.asarray(ol).dtype).reshape(np.shape(ol))
                for nl, ol in zip(new_leaves, leaves)
            ]
            self._set_state(jax.tree.unflatten(treedef, new_leaves))

    def begin(self) -> None:
        if not self._synced:
            # after a membership change, everyone adopts the max progress
            # and rank-0's live training state
            self.progress = api.all_reduce_int_max(self.progress)
            self._sync_state()
            self._synced = True

    def end(self, delta: int = 1) -> None:
        self.progress += delta
        if self.max_progress is not None and self.progress >= self.max_progress:
            self._stop_reason = "finished"
            return
        if self.reload_mode:
            changed, _ = api.change_cluster(self.progress)
            if changed:
                self._stop_reason = "reload"
            return
        changed, detached = api.resize()
        if changed:
            # the resize audit record was written deep in the peer
            # protocol; only the elastic driver knows the training
            # progress (and checkpoint version) it happened at
            from kungfu_tpu.telemetry import audit

            audit.annotate_last(
                peer=str(self._peer.self_id),
                progress=self.progress,
                checkpoint_version=self._checkpoint_version,
            )
        if detached:
            self._stop_reason = "detached"
        elif changed:
            self._synced = False

    advance = end  # documented alias

    @contextlib.contextmanager
    def scope(self):
        self.begin()
        yield

    def stopped(self) -> bool:
        return self._stop_reason is not None

    @property
    def stop_reason(self) -> Optional[str]:
        return self._stop_reason
