"""PyTorch frontend: distributed data parallelism over the host plane.

Capability parity: srcs/python/kungfu/torch/__init__.py +
srcs/cpp/src/torch/module_cpu.cpp — the reference serves TensorFlow AND
PyTorch from one runtime. Here the same host collective engine (graph-walk
allreduce over the kfrun cluster) backs torch tensors: gradients cross the
numpy bridge zero-copy (torch CPU tensors share memory with numpy views).

JAX remains the TPU compute path; this frontend covers the reference's
second-framework contract for CPU torch and torch/XLA hosts:

    from kungfu_tpu import torch as kf_torch
    kf_torch.broadcast_parameters(model)
    opt = kf_torch.SynchronousSGDOptimizer(torch.optim.SGD(model.parameters(), lr=0.1))
    ...
    loss.backward(); opt.step()
"""

from __future__ import annotations

from typing import Iterable, List, Optional

import numpy as np

from kungfu_tpu import api
from kungfu_tpu.base.ops import ReduceOp
from kungfu_tpu.base.serialize import pack_leaves, unpack_leaves
from kungfu_tpu.base.workspace import Workspace


def _params_of(module_or_params) -> List:
    if hasattr(module_or_params, "parameters"):
        return list(module_or_params.parameters())
    return list(module_or_params)


def _flat_view(t) -> np.ndarray:
    """Flat numpy view of a tensor: zero-copy for contiguous CPU tensors
    (.cpu() is a no-op there); a host copy for XLA/CUDA tensors, whose
    callers write the result back explicitly. bfloat16 crosses the bridge
    by bit-reinterpretation (torch refuses .numpy() on bf16) and comes out
    as an ml_dtypes.bfloat16 array, which the host engine reduces
    natively."""
    import torch

    t = t.detach().cpu().contiguous().view(-1)
    if t.dtype == torch.bfloat16:
        import ml_dtypes

        return t.view(torch.int16).numpy().view(ml_dtypes.bfloat16)
    return t.numpy()


def _to_torch(arr: np.ndarray):
    """numpy -> torch, inverting _flat_view's bf16 reinterpretation."""
    import torch

    arr = np.ascontiguousarray(arr)
    if arr.dtype.kind == "V" and arr.dtype.itemsize == 2:  # ml_dtypes bf16
        return torch.from_numpy(arr.view(np.int16)).view(torch.bfloat16)
    return torch.from_numpy(arr)


_sync_round = [0]


def sync_gradients(module_or_params, name: str = "torch-grad",
                   _force_sync_engine: bool = False) -> None:
    """Average .grad across the cluster in-place (parity:
    _synchronize_grads, kungfu/torch/optimizers.py). One windowed group
    allreduce over the host plane; no-op for a cluster of one. Wire names
    carry a per-process round counter: a peer that finishes round k and
    immediately starts k+1 must not have its sends consumed by a slower
    peer still waiting on round k.

    With the async scheduler enabled (``KF_CONFIG_ASYNC``) the group is
    routed through it instead (submit-all + flush — grads are already
    ready here, so there is no backprop overlap; the hook path in
    SynchronousSGDOptimizer is the overlapped one). Scheduler tensor
    names must be STABLE across steps, so the trailing ``:<suffix>`` of
    `name` (the sync path's round counter) is stripped — the scheduler
    stamps its own round counter into wire names."""
    size = api.cluster_size()
    if size <= 1:
        return
    params = [p for p in _params_of(module_or_params) if p.grad is not None]
    if not params:
        return
    rnd = _sync_round[0]
    _sync_round[0] += 1
    views = [_flat_view(p.grad) for p in params]
    sess = api.get_default_peer().current_session()
    if sess.async_enabled() and not _force_sync_engine:
        # async scheduler path (ISSUE 10): stable per-tensor names (the
        # scheduler stamps its own round counter into wire names, which
        # is what the :{rnd}: component below exists for on the sync
        # path), submitted in parameter order, one flush per step
        sched = sess.scheduler()
        for i, v in enumerate(views):
            sched.submit(Workspace(
                send=v, recv=v, op=ReduceOp.SUM,
                name=f"kungfu::torch:{name.rsplit(':', 1)[0]}:{i}",
            ))
        sched.flush()
    else:
        ws = [
            Workspace(send=v, recv=v, op=ReduceOp.SUM,
                      name=f"kungfu::torch:{name}:{rnd}:{i}")
            for i, v in enumerate(views)
        ]
        sess.group_all_reduce(ws)
    inv = 1.0 / size
    for p, v in zip(params, views):
        v *= v.dtype.type(inv)
        # v aliases p.grad's storage for CPU tensors; if torch had to
        # copy (non-CPU / non-contiguous), write the result back
        if p.grad.device.type != "cpu" or not p.grad.is_contiguous():
            p.grad.copy_(_to_torch(v).view_as(p.grad))


def broadcast_parameters(module_or_params, root: int = 0,
                         name: str = "torch-init") -> None:
    """Replace every param with root's values (parity:
    broadcast_parameters, kungfu/torch/__init__.py)."""
    import torch

    if api.cluster_size() <= 1:
        return
    params = _params_of(module_or_params)
    sess = api.get_default_peer().current_session()
    blob = pack_leaves([_flat_view(p) for p in params])
    out = sess.broadcast_bytes(blob, f"kungfu::torch:{name}", root=root)
    if sess.rank == root:
        return
    leaves = unpack_leaves(out, len(params))
    with torch.no_grad():
        for p, l in zip(params, leaves):
            p.copy_(_to_torch(l).view_as(p))


def all_reduce(tensor, op: ReduceOp = ReduceOp.SUM, name: str = "torch-ar"):
    """AllReduce a single tensor, returning a new tensor on the input's
    device (parity: all_reduce_fn). all_reduce_array never mutates its
    input and returns a fresh buffer, so no defensive copy is needed."""
    out = api.all_reduce_array(_flat_view(tensor), op=op, name=name)
    return _to_torch(out).view_as(tensor).to(tensor.device)


class SynchronousSGDOptimizer:
    """S-SGD wrapper over any torch optimizer (parity:
    SynchronousSGDOptimizer, kungfu/torch/optimizers.py): averages
    gradients across the cluster, then applies the base step.

    With the async collective scheduler enabled (``KF_CONFIG_ASYNC``,
    ISSUE 10) each parameter's gradient is SUBMITTED the moment autograd
    finishes accumulating it (post-accumulate-grad hooks), so buckets
    pack and walk while backward is still producing later gradients;
    ``step()`` then only flushes the tail. Falls back to the step-end
    group op when the scheduler is off, the cluster is size 1, or torch
    predates the hook API (<2.1). Results are bit-identical either way
    (same buckets, same engine — only launch time moves).

    Hook-path contract: exactly ONE backward per ``step()``. Gradient
    accumulation (several ``backward()`` calls before a step) would
    submit partially-accumulated gradients, so pass
    ``async_hooks=False`` to keep the step-end path for such loops (a
    second backward otherwise fails fast with the scheduler's
    "submitted twice in round" error rather than reducing partial
    data)."""

    def __init__(self, base, name: str = "ssgd",
                 async_hooks: Optional[bool] = None):
        self.base = base
        self.name = name
        self._step = 0
        self._async_grads: dict = {}  # param index -> (param, flat view)
        # None: follow the session's KF_CONFIG_ASYNC; False: never hook
        # (gradient-accumulation loops); True: require hooks or fall
        # back silently like None
        self._async_opt_in = async_hooks
        self._hooks_installed: Optional[bool] = None  # None: undecided

    def _params_list(self) -> List:
        return [
            p for group in self.base.param_groups for p in group["params"]
        ]

    def _install_hooks(self) -> bool:
        """Register per-param submission hooks when the async scheduler
        can take them; decided once, at the first step (the session
        exists by then). Hook firing order is autograd order — identical
        across data-parallel replicas of the same model, which is what
        the scheduler's registration consensus verifies."""
        if self._async_opt_in is False:
            return False
        if api.cluster_size() <= 1:
            return False
        sess = api.get_default_peer().current_session()
        if not sess.async_enabled():
            return False
        params = self._params_list()
        if not all(
            hasattr(p, "register_post_accumulate_grad_hook") for p in params
        ):
            return False

        def make_hook(i):
            def hook(param):
                s = api.get_default_peer().current_session()
                if not s.async_enabled():
                    # an elastic resize landed on an async-off session
                    # (e.g. KF_CONFIG_ASYNC=auto shrunk to 1 peer):
                    # hooks must go dormant, NOT buffer into a scheduler
                    # nobody will ever flush — step() falls back to the
                    # step-end path when _async_grads stays empty
                    return
                v = _flat_view(param.grad)
                self._async_grads[i] = (param, v)
                s.scheduler().submit(Workspace(
                    send=v, recv=v, op=ReduceOp.SUM,
                    name=f"kungfu::torch:{self.name}:{i}",
                ))
            return hook

        for i, p in enumerate(params):
            if p.requires_grad:
                p.register_post_accumulate_grad_hook(make_hook(i))
        return True

    def step(self, closure=None):
        if self._hooks_installed is None:
            # decided AFTER the first backward: grads of step 0 already
            # exist, so step 0 always takes the sync path below and the
            # hooks start feeding the scheduler from step 1
            self._hooks_installed = self._install_hooks()
        if self._async_grads:
            sess = api.get_default_peer().current_session()
            if not sess.async_enabled():
                # a resize landed BETWEEN backward and step: this
                # step's submissions died with the old epoch and some
                # in-place gradient views may already be partially
                # reduced — scaling them would corrupt silently, and
                # re-reducing could double-sum completed buckets. Fail
                # loudly; the elastic loop re-runs the step.
                self._async_grads.clear()
                raise RuntimeError(
                    "cluster resized mid-step onto an async-off "
                    "session; gradients of this step are indeterminate "
                    "— zero_grad() and re-run the backward"
                )
            api.flush_async()
            inv = 1.0 / api.cluster_size()
            for _, (p, v) in sorted(self._async_grads.items()):
                v *= v.dtype.type(inv)
                # v aliases p.grad's storage for CPU tensors; if torch
                # had to copy (non-CPU / non-contiguous), write back
                if p.grad.device.type != "cpu" or not p.grad.is_contiguous():
                    p.grad.copy_(_to_torch(v).view_as(p.grad))
            self._async_grads.clear()
        else:
            # step-end path (step 0, hooks unavailable, or opted out):
            # force the classic group engine even when the scheduler is
            # on — routing THIS call through the scheduler would
            # register grad-filtered indices while the hooks submit
            # full-param-list indices, desynchronizing the registered
            # identity set for any model with frozen params
            sync_gradients(self._params_list(),
                           name=f"{self.name}:{self._step}",
                           _force_sync_engine=True)
        self._step += 1
        return self.base.step(closure)

    def __getattr__(self, item):
        return getattr(self.base, item)


class ZeroSGDOptimizer:
    """ZeRO-1 sharded S-SGD for torch over the host plane (ISSUE 11):
    gradients are reduce-scattered around the ring, ``step()`` runs SGD
    on — and holds momentum state plus f32 master weights for — ONLY
    this rank's 1/k shard, and an all-gather of updated weights (bf16 on
    the wire when ``KF_CONFIG_WIRE`` is active) lands the result back in
    the param tensors in place. Optimizer state and update FLOPs drop
    k-fold vs :class:`SynchronousSGDOptimizer`.

    This optimizer OWNS the SGD math (``lr``/``momentum``, the torch-SGD
    formula ``buf = m·buf + g; p -= lr·buf``) rather than wrapping a
    base ``torch.optim`` instance — a base optimizer would allocate
    full-size state, which is exactly what sharding removes.

    With ``KF_CONFIG_ZERO`` resolving off — or a cluster of one — it
    falls back to the replicated path (``sync_gradients`` + the same
    formula on full params, full-size state), so ``zero`` A/Bs by knob;
    for plain SGD on exact payloads the two paths are bit-identical.
    With the async scheduler on, gradients are submitted per tensor and
    the weight all-gathers pipeline across buckets; ``step()`` returns
    with params fully updated (the forward that follows needs them).

    Elastic resize: shard ownership is a function of k — call
    ``export_state()`` BEFORE the resize and ``rebuild(blob)`` after
    (see ShardedUpdateSession). CPU-tensor first like the rest of the
    frontend: param/grad views cross the numpy bridge zero-copy there;
    non-CPU params are copied back after each step."""

    def __init__(self, module_or_params, lr: float, momentum: float = 0.0,
                 name: str = "zsgd"):
        self.lr = float(lr)
        self.momentum = float(momentum)
        self.name = name
        self._params = [
            p for p in _params_of(module_or_params) if p.requires_grad
        ]
        if not self._params:
            raise ValueError("ZeroSGDOptimizer needs at least one param")
        self._mode: Optional[str] = None  # decided at first step
        self._views: List[np.ndarray] = []
        self._zs = None  # ShardedUpdateSession (sharded mode)
        self._repl_opt = None  # ShardedSGD over FULL params (fallback)
        self._repl_state: List[dict] = []
        self._step = 0

    def _build(self) -> None:
        self.rebuild(None)

    def state_bytes(self) -> int:
        """Optimizer-held bytes on this peer: ~1/k of the replicated
        path in sharded mode (the `kungfu_sharded_update_state_bytes`
        gauge)."""
        if self._mode is None:
            self._build()
        if self._zs is not None:
            return self._zs.state_bytes()
        return sum(
            a.nbytes for st in self._repl_state for a in st.values()
        )

    def _bucket_layout(self, sess):
        from kungfu_tpu.collective.zero import bucket_layout

        return bucket_layout(
            [v.size for v in self._views], sess.GROUP_BUCKET_BYTES
        )

    def export_state(self) -> bytes:
        """Full optimizer state as one exact blob (every peer gets the
        identical bytes) — run BEFORE a resize, then `rebuild(blob)` on
        the new epoch. BOTH modes serialize the same canonical
        bucket-shaped layout (per bucket: full f32 masters, then each
        state leaf — the `bucket_layout` of the param sizes under the
        cluster-agreed byte cap), so a resize that flips the resolved
        KF_CONFIG_ZERO mode (e.g. `auto` shrinking to one peer) can
        still restore the other mode's blob."""
        if self._mode is None:
            self._build()
        if self._zs is not None:
            return self._zs.export_state()
        from kungfu_tpu.base.serialize import pack_leaves

        sess = api.get_default_peer().current_session()
        names = self._repl_opt.state_names()
        leaves = []
        for idxs in self._bucket_layout(sess):
            # replicated mode's masters ARE the current params
            leaves.append(np.concatenate([self._views[i] for i in idxs]))
            for k in names:
                leaves.append(np.concatenate(
                    [self._repl_state[i][k] for i in idxs]
                ))
        return pack_leaves(leaves)

    def rebuild(self, restore_state: Optional[bytes] = None) -> None:
        """(Re-)bind to the CURRENT session epoch — called lazily at the
        first step, and explicitly after an elastic resize with an
        `export_state` blob from before it, re-sharding (or
        de-sharding: the resolved mode may flip across the resize)
        optimizer state so zero-step-loss resizes hold."""
        from kungfu_tpu.collective.zero import ShardedSGD, ShardedUpdateSession

        sess = api.get_default_peer().current_session()
        self._views = [_flat_view(p) for p in self._params]
        if sess.zero_enabled():
            self._mode = "sharded"
            self._zs = ShardedUpdateSession(
                self._views, ShardedSGD(self.lr, self.momentum),
                name=self.name, session=sess, restore_state=restore_state,
            )
            self._repl_opt = None
            self._repl_state = []
            self._writeback()
            return
        self._mode = "replicated"
        self._zs = None
        self._repl_opt = ShardedSGD(self.lr, self.momentum)
        self._repl_state = [self._repl_opt.init(v.size) for v in self._views]
        if restore_state is not None:
            from kungfu_tpu.base.serialize import unpack_leaves

            names = self._repl_opt.state_names()
            layout = self._bucket_layout(sess)
            leaves = unpack_leaves(restore_state, (1 + len(names)) * len(layout))
            it = iter(leaves)
            for idxs in layout:
                # canonical layout (see export_state): masters refresh
                # the params, state leaves split back per param
                master = np.asarray(next(it), np.float32).reshape(-1)
                off = 0
                for i in idxs:
                    np.copyto(self._views[i], master[off:off + self._views[i].size])
                    off += self._views[i].size
                for k in names:
                    full = np.asarray(next(it), np.float32).reshape(-1)
                    off = 0
                    for i in idxs:
                        np.copyto(self._repl_state[i][k],
                                  full[off:off + self._views[i].size])
                        off += self._views[i].size
            self._writeback()

    def _writeback(self) -> None:
        """Non-CPU / non-contiguous params: the numpy views are copies,
        push the updated values back into the tensors."""
        for p, v in zip(self._params, self._views):
            if p.device.type != "cpu" or not p.data.is_contiguous():
                with_no_grad_copy(p, v)

    def zero_grad(self) -> None:
        for p in self._params:
            if p.grad is not None:
                p.grad.detach_()
                p.grad.zero_()

    def step(self, closure=None):
        loss = closure() if closure is not None else None
        if self._mode is None:
            self._build()
        grads = []
        for i, p in enumerate(self._params):
            if p.grad is None:
                raise RuntimeError(
                    f"param {i} has no gradient — ZeroSGDOptimizer "
                    "requires every registered param to receive a grad "
                    "each step (the sharded bucket layout is fixed)"
                )
            grads.append(_flat_view(p.grad))
        if self._zs is not None:
            sess = api.get_default_peer().current_session()
            if sess.async_enabled():
                for i, g in enumerate(grads):
                    self._zs.submit_grad(i, g)
                self._zs.flush()
                # params feed the forward right after step() returns:
                # wait for the tail all-gathers here (the pipelining
                # already overlapped them with later buckets' updates)
                self._zs.wait_params()
            else:
                self._zs.step(grads)
        else:
            # replicated fallback: averaged grads (in place), then the
            # identical SGD formula on full params with full-size state
            if api.cluster_size() > 1:
                sync_gradients(self._params, name=f"{self.name}:{self._step}",
                               _force_sync_engine=True)
                # non-CPU grads: sync_gradients wrote the averages back
                # into p.grad, so the pre-sync copies above are stale
                grads = [_flat_view(p.grad) for p in self._params]
            for v, g, st in zip(self._views, grads, self._repl_state):
                self._repl_opt.apply(v, g, st, 1.0)
        self._writeback()
        self._step += 1
        return loss


def with_no_grad_copy(p, arr: np.ndarray) -> None:
    """p.copy_(arr) under no_grad, inverting the bf16 bridge."""
    import torch

    with torch.no_grad():
        p.copy_(_to_torch(arr).view_as(p))


class PairAveragingOptimizer:
    """AD-PSGD for torch (parity: PairAveragingOptimizer): apply the local
    step, then average parameters 0.5/0.5 with a random peer's published
    model via the versioned p2p store."""

    def __init__(self, base, name: str = "torch-pair", rng=None):
        import random

        self.base = base
        self.blob = f"pair-avg-torch:{name}"
        self.rng = rng or random.Random(api.current_rank() * 6007 + 13)
        self._version = 0
        self._published = False

    def _params(self) -> List:
        return [p for g in self.base.param_groups for p in g["params"]]

    def _publish(self) -> None:
        p2p = api.get_default_peer().p2p
        blob = pack_leaves([_flat_view(p) for p in self._params()])
        p2p.save_version(self._version, self.blob, blob)
        self._version += 1

    def _random_peer(self) -> Optional[int]:
        size = api.cluster_size()
        if size <= 1:
            return None
        r = self.rng.randrange(size - 1)
        me = api.current_rank()
        return r + 1 if r >= me else r

    def step(self, closure=None):
        import torch

        if not self._published:
            # first step: publish + fence so every peer has a model to serve
            self._publish()
            api.run_barrier()
            self._published = True
        out = self.base.step(closure)
        target = self._random_peer()
        if target is not None:
            sess = api.get_default_peer().current_session()
            p2p = api.get_default_peer().p2p
            try:
                data = p2p.request(
                    sess.peers[target], self.blob, timeout=30, version="latest"
                )
            except (ConnectionError, TimeoutError, OSError):
                data = None
            params = self._params()
            if data is not None:
                try:
                    leaves = unpack_leaves(bytes(data), len(params))
                except (ValueError, KeyError):
                    leaves = None
                if leaves is not None:
                    with torch.no_grad():
                        for p, l in zip(params, leaves):
                            p.mul_(0.5).add_(_to_torch(l).view_as(p), alpha=0.5)
        self._publish()
        return out

    def __getattr__(self, item):
        return getattr(self.base, item)
