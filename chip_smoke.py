#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the trainer still starts on the chip.

    python chip_smoke.py

drives the training path once through the entry points a user calls, at
the full width and depth of `TransformerConfig.bert_base()`, on every chip
JAX finds: `make_mesh` -> `synchronous_sgd` -> `make_train_step`, the
tensor-parallel `make_sharded_train_step`, `kfrun` workers joined by
`initialize_device_plane()`, a job that `kfrun` resizes 4 -> 2 -> 4 in
reload mode with its state carried by a checkpoint, two worlds bridged by
`make_hier_train_step`, and the Pallas flash-attention kernels compiled by
Mosaic. It exits 0 only
if every phase passed, and then ends its output with two lines: the report
(`CHIP_SMOKE_REPORT ` and one JSON object: versions, and per phase its wall
seconds, seconds to the first step and the checks' values) and, last, the
result, `{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`
with the device as JAX reports it and no other key. On the first failure it
names the phase and exits non-zero with neither line. There is no flag:
without a TPU the first phase fails.

This process never imports jax. A chip belongs to one process at a time,
so every phase runs in a child (or a `kfrun` tree) that is gone before the
next one starts. The phase bodies below are plain functions of the model
configuration and step count; `tests/test_chip_smoke.py` drives them at
`tiny()` size on the CPU mesh. What they time is printed as smoke timings
and is no benchmark figure.
"""

from __future__ import annotations

import hashlib
import json
import os
import signal
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
RESULT_TAG = "CHIP_SMOKE_RESULT "  # a child's line, read by the parent
REPORT_TAG = "CHIP_SMOKE_REPORT "  # the parent's account of every phase

# -- what the chip run uses (the bodies take these as arguments) ----------
PER_CHIP_BATCH = 16  # largest power of two that leaves headroom in 16 GB
TRAIN_STEPS = 8  # after the compiling one
WORKER_STEPS = 3  # launcher and hierarchical workers
# the resize phase: rank 0 asks for this many workers before that step,
# and the job ends after RESIZE_STEPS
RESIZES = {20: 2, 40: 4}
RESIZE_STEPS = 60
LEARNING_RATE = 3e-4
# train-sharded against train, first three losses. Both compute in bf16
# (8-bit mantissa, 2^-8 = 3.9e-3 per rounding) and reduce in different
# orders: tp splits the contractions of wo/w_out and the vocabulary sum
# of the loss, dp=2 averages two half-batches where dp=4 averages four
# quarters. One part in a hundred is about two and a half roundings.
LOSS_RTOL = 1e-2
# kernels against the float32 dense reference, as a share of the largest
# reference value: outputs and gradients are rounded to bf16 once more
# than the reference's, and the backward's delta term is built from the
# bf16-rounded output.
KERNEL_TOL = 2e-2
KERNEL_SEQ = 2048
KERNEL_HEAD_DIMS = (64, 128)


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# -- phase bodies (run in children; import jax lazily) ---------------------


def _device_report() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _memory(devices) -> dict:
    """bytes_in_use / peak_bytes_in_use per device (None where the
    backend keeps no statistics, as the CPU's does not)."""
    stats = [d.memory_stats() or {} for d in devices]
    return {
        "bytes_in_use": [s.get("bytes_in_use") for s in stats],
        "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
    }


def _seeded_batch(cfg, global_batch: int):
    """A fixed batch of token ids, (B, S+1): the loss shifts it by one."""
    import numpy as np

    rng = np.random.RandomState(0)
    return rng.randint(
        0, cfg.vocab_size, (global_batch, cfg.max_seq + 1)
    ).astype(np.int32)


def _init_params(cfg):
    import jax

    from kungfu_tpu.models.transformer import init_transformer

    return jax.jit(lambda key: init_transformer(key, cfg))(jax.random.PRNGKey(0))


def _loss_fn(cfg):
    from kungfu_tpu.models.transformer import transformer_loss

    return lambda params, batch: transformer_loss(params, batch, cfg)


def _run_steps(step, params, opt_state, batch, n_steps: int,
               devices) -> dict:
    """One compiling step and n_steps more; every step closed by
    block_until_ready, compile requests (an XLA compile or a load from the
    persistent cache each) counted per step by the program's own counter,
    which `enable_compile_cache()` started, `devices`' memory read while
    the parameters and optimizer state are still alive."""
    import jax

    from kungfu_tpu.telemetry import device

    def requests() -> int:
        return sum(device.compile_requests().values())

    losses, seconds, compiles = [], [], []
    for _ in range(n_steps + 1):
        c0 = requests()
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, batch)
        jax.block_until_ready((params, opt_state, loss))
        seconds.append(time.perf_counter() - t0)
        compiles.append(requests() - c0)
        losses.append(float(loss))
    _check(all(l == l and abs(l) != float("inf") for l in losses),
           f"non-finite loss: {losses}")
    _check(losses[-1] < losses[0],
           f"loss did not fall on the fixed batch: {losses}")
    _check(sum(compiles[1:]) == 0,
           f"compilations after the first step: {compiles}")
    by_cache = device.compile_requests()
    return {
        "params": params,
        "losses": [round(l, 6) for l in losses],
        "first_step_s": round(seconds[0], 3),
        "step_s": [round(s, 4) for s in seconds[1:]],
        "compiles_first_step": compiles[0],
        "compiles_after_first_step": sum(compiles[1:]),
        "cache_hits": by_cache["hit"],
        "cache_misses": by_cache["miss"],
        **_memory(devices),
    }


def devices_phase() -> dict:
    """Every device is a TPU of a kind the repo's one table names."""
    import importlib.metadata as md

    import jax
    import jaxlib

    from kungfu_tpu.parallel.chip import require_tpu

    require_tpu()
    return {
        "device": _device_report(),
        "versions": {
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "libtpu": md.version("libtpu"),
        },
    }


def train_phase(cfg, steps: int, per_chip_batch: int) -> dict:
    """The README quick-start on all chips of one process: data-parallel
    S-SGD over AdamW through make_train_step."""
    import jax
    import optax

    from kungfu_tpu.optimizers import synchronous_sgd
    from kungfu_tpu.parallel import make_mesh, make_train_step
    from kungfu_tpu.parallel.chip import enable_compile_cache
    from kungfu_tpu.parallel.dp import replicate, shard_batch

    t_start = time.perf_counter()
    enable_compile_cache()
    n = jax.device_count()
    mesh = make_mesh({"dp": n})
    opt = synchronous_sgd(optax.adamw(LEARNING_RATE), "dp")
    params = replicate(_init_params(cfg), mesh)
    opt_state = replicate(opt.init(params), mesh)
    batch = shard_batch(_seeded_batch(cfg, per_chip_batch * n), mesh)
    step = make_train_step(_loss_fn(cfg), opt, mesh)
    setup_s = time.perf_counter() - t_start

    out = _run_steps(step, params, opt_state, batch, steps, jax.devices())
    params = out.pop("params")
    leaves = jax.tree.leaves(params)
    _check(all(len(l.sharding.device_set) == n for l in leaves),
           f"a parameter leaf does not span all {n} devices")
    batch_devices = {s.device for s in batch.addressable_shards}
    _check(len(batch_devices) == n,
           f"batch shards sit on {len(batch_devices)} devices, not {n}")
    return {
        "device": _device_report(),
        "mesh": {"dp": n},
        "global_batch": per_chip_batch * n,
        "param_count": sum(l.size for l in leaves),
        "setup_s": round(setup_s, 3),
        **out,
    }


def train_sharded_phase(cfg, steps: int, global_batch: int,
                        reference_losses) -> dict:
    """The same model and batch over dp=2 x tp: param_pspecs ->
    shard_params -> make_sharded_train_step; its first losses must agree
    with the data-parallel run's."""
    import jax
    import optax

    from kungfu_tpu.models.transformer import param_pspecs
    from kungfu_tpu.parallel import make_mesh
    from kungfu_tpu.parallel.chip import enable_compile_cache
    from kungfu_tpu.parallel.dp import shard_batch
    from kungfu_tpu.parallel.sharded import (
        init_opt_state,
        make_sharded_train_step,
        shard_params,
    )

    t_start = time.perf_counter()
    enable_compile_cache()
    n = jax.device_count()
    tp = n // 2
    mesh = make_mesh({"dp": 2, "tp": tp})
    specs = param_pspecs(cfg, "tp")
    opt = optax.adamw(LEARNING_RATE)
    params = shard_params(_init_params(cfg), mesh, specs)
    opt_state = init_opt_state(opt, params, mesh)
    batch = shard_batch(_seeded_batch(cfg, global_batch), mesh)
    step = make_sharded_train_step(_loss_fn(cfg), opt, mesh, specs)
    setup_s = time.perf_counter() - t_start

    out = _run_steps(step, params, opt_state, batch, steps, jax.devices())
    params = out.pop("params")
    for name in ("wqkv", "w_in"):
        leaf = params["layers"][name]
        widths = {s.data.shape[-1] for s in leaf.addressable_shards}
        _check(widths == {leaf.shape[-1] // tp},
               f"{name} is not split {tp} ways over tp: shard widths {widths}")
        _check(len(leaf.sharding.device_set) == n,
               f"{name} does not span all {n} devices")
    k = len(reference_losses)
    drift = [abs(a - b) / abs(b)
             for a, b in zip(out["losses"][:k], reference_losses)]
    _check(max(drift) <= LOSS_RTOL,
           f"losses {out['losses'][:k]} differ from the data-parallel run's "
           f"{list(reference_losses)} by more than {LOSS_RTOL}: {drift}")
    return {
        "device": _device_report(),
        "mesh": {"dp": 2, "tp": tp},
        "mesh_devices": [[d.id for d in row] for row in mesh.devices],
        "mesh_coords": [[getattr(d, "coords", None) for d in row]
                        for row in mesh.devices],
        "global_batch": global_batch,
        "setup_s": round(setup_s, 3),
        **out,
        "loss_drift": [round(d, 6) for d in drift],
    }


def kernels_phase(seq: int, head_dims, interpret: bool,
                  blk: int = 512) -> dict:
    """flash_attention forward and backward against the float32 dense
    reference and its jax.grad, bf16, causal."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kungfu_tpu.ops.flash_attention import (
        _dense_reference,
        flash_attention,
    )
    from kungfu_tpu.parallel.chip import enable_compile_cache

    enable_compile_cache()
    out = {"device": _device_report(), "seq": seq, "errors": {}}
    t_start = time.perf_counter()

    def value_and_grads(f):
        return jax.jit(
            jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)
        )

    for hd in head_dims:
        # drawn on the host: no program besides the two under test
        rng = np.random.RandomState(hd)
        q, k, v, w = (
            jnp.asarray(rng.standard_normal((1, 4, seq, hd)), jnp.bfloat16)
            for _ in range(4)
        )
        scale = 1.0 / hd ** 0.5

        def flash(q, k, v):
            o = flash_attention(q, k, v, True, None, blk, blk, interpret)
            return jnp.sum(o.astype(jnp.float32) * w), o

        def dense(q, k, v):
            o = _dense_reference(q, k, v, True, scale)
            return jnp.sum(o.astype(jnp.float32) * w), o

        # the kernels as a model traces them, the reference at the highest
        # precision: under that setting Mosaic refuses the kernels' bf16
        # products since PR 53 ("Bad lhs type": the smoke failed here at
        # PR 54's parent)
        (_, o_f), g_f = value_and_grads(flash)(q, k, v)
        with jax.default_matmul_precision("highest"):
            (_, o_d), g_d = value_and_grads(dense)(q, k, v)
        for name, a, b in zip(
            ("out", "dq", "dk", "dv"), (o_f, *g_f), (o_d, *g_d)
        ):
            a = a.astype(jnp.float32)
            b = b.astype(jnp.float32)
            _check(bool(jnp.all(jnp.isfinite(a))),
                   f"hd={hd} {name}: non-finite values")
            err = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
            out["errors"][f"hd{hd}_{name}"] = round(err, 5)
            _check(err <= KERNEL_TOL,
                   f"hd={hd} {name}: error {err:.4f} of the reference's "
                   f"scale exceeds {KERNEL_TOL}")
    out["check_s"] = round(time.perf_counter() - t_start, 3)
    return out


def _params_digest(params) -> bytes:
    import jax
    import numpy as np

    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        h.update(np.asarray(leaf).tobytes())
    return h.digest()


def _span_timeline(spawn_ts: float, prefixes):
    """(ms by span name, [[name, seconds since spawn, seconds, args]] in
    order of start) of this process's ring under `prefixes`: each span
    where it fell, because a sum hides that a name ran twice, and what
    waited between two spans."""
    from kungfu_tpu.telemetry import tracing

    spans_ms, timeline = {}, []
    to_wall = time.time() - time.perf_counter()  # the ring's clock -> wall
    for prefix in prefixes:
        spans_ms.update(tracing.summary_ms(prefix))
        timeline += [[e.name, round(e.start + to_wall - spawn_ts, 3),
                      round(e.duration, 3), e.args]
                     for e in tracing.full_events(prefix)]
    timeline.sort(key=lambda e: e[1])
    return spans_ms, timeline


def _native_loaded() -> bool:
    from kungfu_tpu.base import ops

    return bool(ops._load_native())


def launcher_worker(cfg, steps: int, per_chip_batch: int,
                    chips_per_worker: int = 1) -> dict:
    """One kfrun worker of the launcher phase: join the one device world,
    take a few S-SGD steps over it, agree on the parameters."""
    from kungfu_tpu import api, knobs
    from kungfu_tpu.parallel import initialize_device_plane
    from kungfu_tpu.telemetry import tracing

    rank, size = api.current_rank(), api.cluster_size()
    initialize_device_plane()
    # kfrun's clock for this worker starts where the runner spawned it
    spawn_ts = float(knobs.raw("KF_SPAWN_TS") or time.time())
    since_spawn = {"world": time.time() - spawn_ts}

    import jax
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from kungfu_tpu.initializer import broadcast_variables
    from kungfu_tpu.optimizers import synchronous_sgd
    from kungfu_tpu.parallel import make_mesh, make_train_step
    from kungfu_tpu.parallel.dp import replicate

    t_start = time.perf_counter()
    n = jax.device_count()
    _check(jax.local_device_count() == chips_per_worker,
           f"worker {rank} opened {jax.local_device_count()} devices, "
           f"not {chips_per_worker}")
    _check(jax.process_count() == size,
           f"jax.process_count() {jax.process_count()} != {size} workers")
    _check(n == size * chips_per_worker,
           f"the world has {n} devices, not {size * chips_per_worker}")

    # rank 0 is the source of truth, wherever libtpu put its chip
    source = int(broadcast_variables({"rank": np.asarray(rank, np.int32)})["rank"])
    _check(source == 0,
           f"broadcast_variables delivered rank {source}'s values, not rank 0's")

    mesh = make_mesh({"dp": n})
    opt = synchronous_sgd(optax.adamw(LEARNING_RATE), "dp")
    # the smoke's own phases between the program's spans, so that the
    # timeline below has no unnamed interval
    with tracing.span("smoke.init_params"):
        params = jax.block_until_ready(_init_params(cfg))
    params = broadcast_variables(params, mesh)
    with tracing.span("smoke.opt_state"):
        opt_state = jax.block_until_ready(replicate(opt.init(params), mesh))
    with tracing.span("smoke.batch"):
        tokens = _seeded_batch(cfg, per_chip_batch * n)
        batch = jax.make_array_from_callback(
            tokens.shape, NamedSharding(mesh, P("dp")), lambda idx: tokens[idx]
        )
    step = make_train_step(_loss_fn(cfg), opt, mesh)
    setup_s = time.perf_counter() - t_start
    since_spawn["state_placed"] = time.time() - spawn_ts

    out = _run_steps(step, params, opt_state, batch, steps,
                     jax.local_devices())
    since_spawn["first_step"] = since_spawn["state_placed"] + out["first_step_s"]
    # the launcher and placement phases by span, once a process (PERF.md)
    spans_ms, timeline = _span_timeline(
        spawn_ts, ("worker.", "device_plane.", "broadcast.", "smoke."))
    if rank == 0:
        print("launch and placement spans (ms): " + json.dumps(spans_ms),
              flush=True)
        print("[name, seconds since spawn, seconds, args]: "
              + json.dumps(timeline), flush=True)
    agreed = api.consensus(_params_digest(out.pop("params")), "chip-smoke")
    _check(agreed, "workers disagree on the parameters after the last step")
    api.run_barrier()
    return {
        "rank": rank,
        "workers": size,
        "device": _device_report(),
        "local_devices": [d.id for d in jax.local_devices()],
        "process_index": jax.process_index(),
        "global_batch": per_chip_batch * n,
        "setup_s": round(setup_s, 3),
        "spawn_ts": spawn_ts,
        "since_spawn_s": {k: round(v, 3) for k, v in since_spawn.items()},
        "spans_ms": spans_ms,
        "span_timeline": timeline,
        **out,
        "params_agree": agreed,
        "native_kernels": _native_loaded(),
    }


def resize_worker(cfg, per_chip_batch: int, resizes: dict, max_steps: int,
                  ckpt_dir: str) -> None:
    """One kfrun worker of one incarnation of the resize phase: join the
    device world of this membership, restore what the last incarnation
    saved, train; rank 0 asks for `resizes[step]` workers before that
    step, and every worker saves when the reload is agreed. Prints its
    tagged result itself, before the runner hears of the reload: the
    runner stops a worker as soon as it does."""
    from kungfu_tpu import api, knobs
    from kungfu_tpu.parallel import initialize_device_plane
    from kungfu_tpu.telemetry import device, tracing

    initialize_device_plane()
    rank, size = api.current_rank(), api.cluster_size()

    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from kungfu_tpu.elastic.checkpoint import Checkpointer
    from kungfu_tpu.elastic.state import ElasticState
    from kungfu_tpu.initializer import broadcast_variables
    from kungfu_tpu.optimizers import synchronous_sgd
    from kungfu_tpu.parallel import make_mesh, make_train_step
    from kungfu_tpu.parallel.dp import replicate
    from kungfu_tpu.peer import get_default_peer

    n = jax.device_count()
    _check(jax.process_count() == size,
           f"jax.process_count() {jax.process_count()} != {size} workers")
    mesh = make_mesh({"dp": n})
    opt = synchronous_sgd(optax.adamw(LEARNING_RATE), "dp")
    with tracing.span("smoke.init_params"):
        params = jax.block_until_ready(_init_params(cfg))
    params = broadcast_variables(params, mesh)
    with tracing.span("smoke.opt_state"):
        opt_state = jax.block_until_ready(replicate(opt.init(params), mesh))

    es = ElasticState(max_progress=max_steps, reload_mode=True)
    # one jax world, global arrays: every worker takes part in a save
    ckpt = Checkpointer(ckpt_dir, save_rank=None)
    leaves, treedef = jax.tree.flatten((params, opt_state))
    del params, opt_state

    def as_saved(leaves) -> dict:  # a flat tree orbax takes as it is
        return {f"{i:03d}": leaf for i, leaf in enumerate(leaves)}

    restored, start = ckpt.restore_or(as_saved(leaves))
    leaves = [restored[k] for k in sorted(restored)]
    live = dict(zip(("params", "opt_state"), jax.tree.unflatten(treedef, leaves)))
    del leaves, restored
    _check(start == es.progress,
           f"restored step {start}, the runner carried {es.progress}")
    handed = {}
    if start:
        with tracing.span("smoke.digest"):
            digest = _params_digest(live["params"])
        with open(os.path.join(ckpt_dir, f"saved-{start}.json")) as f:
            handed = json.load(f)  # handed_digest, loss_before
        handed["restored_digest"] = digest.hex()
        handed["ranks_agree"] = api.consensus(digest, f"chip-smoke-resize:{start}")

    with tracing.span("smoke.batch"):
        tokens = _seeded_batch(cfg, per_chip_batch * n)
        batch = jax.make_array_from_callback(
            tokens.shape, NamedSharding(mesh, P("dp")), lambda idx: tokens[idx]
        )
    step = make_train_step(_loss_fn(cfg), opt, mesh)
    first_step, losses, leaving = es.progress, [], {}

    def report(reason: str) -> None:
        by_cache = device.compile_requests()
        print(RESULT_TAG + json.dumps({
            "rank": rank,
            "workers": size,
            "version": get_default_peer().cluster_version,
            "device": _device_report(),
            "local_devices": [d.id for d in jax.local_devices()],
            "coords": [getattr(d, "coords", None) for d in jax.local_devices()],
            "slots": list(get_default_peer().config.device_slots),
            "process_bounds": get_default_peer().config.device_world.get(
                "TPU_PROCESS_BOUNDS"),
            "first_step": first_step,
            "last_step": es.progress,
            "stop_reason": reason,
            "resize_phases": api.last_resize_phases() if start else {},
            **handed,
            "first_loss": losses[0],
            "last_loss": losses[-1],
            "compile_requests": by_cache,
            # the compiles worth a name: what missed the cache for a second or more
            "slow_misses": [
                [e.args.get("fun_name"), round(e.duration, 1)]
                for e in tracing.full_events("device_plane.compile.backend")
                if e.args.get("cache") == "miss" and e.duration >= 1.0],
            "smoke_spans_ms": tracing.summary_ms("smoke."),
            "checkpoint_spans_ms": tracing.summary_ms("checkpoint."),
            **leaving,
        }), flush=True)

    def save(progress: int) -> None:
        if leaving.get("digest_step") != progress:  # agreed a step late
            with tracing.span("smoke.digest"):
                leaving["saved_digest"] = _params_digest(live["params"]).hex()
        ckpt.save(progress, as_saved(jax.tree.leaves((live["params"], live["opt_state"]))))
        if rank == 0:
            with open(os.path.join(ckpt_dir, f"saved-{progress}.json"), "w") as f:
                json.dump({"handed_digest": leaving["saved_digest"],
                           "loss_before": losses[-1]}, f)
        report("reload")

    es.on_reload(save)
    while not es.stopped():
        with es.scope():
            target = resizes.get(es.progress)
            asked = target is not None and target != size
            if asked and rank == 0:
                api.propose_new_size(target)
            live["params"], live["opt_state"], loss = step(
                live["params"], live["opt_state"], batch)
            # the step is awaited before end(): the pause ends there
            losses.append(float(jax.block_until_ready(loss)))
            if start and rank == 0 and len(losses) == 2:
                # a step late, so that the print is outside the pause
                print("[name, seconds since spawn, seconds, args]: " + json.dumps(
                    _span_timeline(float(knobs.raw("KF_SPAWN_TS")), (
                        "worker.", "device_plane.", "broadcast.", "smoke.",
                        "checkpoint.", "elastic.", "resize."))[1]), flush=True)
            if asked:
                # before the pause begins: the smoke's own check is not
                # the resize's cost
                leaving["saved_digest"] = _params_digest(live["params"]).hex()
                leaving["digest_step"] = es.progress + 1
            es.end(1)
    _check(all(l == l and abs(l) != float("inf") for l in losses),
           f"non-finite loss: {losses}")
    if es.stop_reason == "finished":
        report("finished")
        api.run_barrier()


def hier_worker(cfg, steps: int, per_chip_batch: int) -> dict:
    """One kfrun worker of the two-world launch: its chips are a world of
    their own; gradients cross worlds over the host plane from inside the
    jitted step (make_hier_train_step's io_callback)."""
    from kungfu_tpu import api

    rank, size = api.current_rank(), api.cluster_size()

    import jax
    import optax

    from kungfu_tpu.ops.hierarchical import make_hier_train_step
    from kungfu_tpu.parallel import make_mesh
    from kungfu_tpu.parallel.chip import enable_compile_cache
    from kungfu_tpu.parallel.dp import replicate, shard_batch

    t_start = time.perf_counter()
    enable_compile_cache()
    n = jax.device_count()
    _check(jax.process_count() == 1, "a hierarchical worker is its own world")
    mesh = make_mesh({"dp": n})
    opt = optax.adamw(LEARNING_RATE)
    params = replicate(_init_params(cfg), mesh)  # same seed in every world
    opt_state = replicate(opt.init(params), mesh)
    rows = per_chip_batch * n
    tokens = _seeded_batch(cfg, rows * size)[rank * rows:(rank + 1) * rows]
    batch = shard_batch(tokens, mesh)
    step = make_hier_train_step(_loss_fn(cfg), opt, mesh)
    setup_s = time.perf_counter() - t_start

    out = _run_steps(step, params, opt_state, batch, steps,
                     jax.local_devices())
    agreed = api.consensus(_params_digest(out.pop("params")), "chip-smoke-hier")
    _check(agreed, "the worlds hold different parameters after the last step")
    api.run_barrier()
    return {
        "rank": rank,
        "worlds": size,
        "device": _device_report(),
        "local_devices": [d.id for d in jax.local_devices()],
        "global_batch": rows * size,
        "setup_s": round(setup_s, 3),
        **out,
        "params_agree": agreed,
        "native_kernels": _native_loaded(),
    }


# -- the children, on the chip ---------------------------------------------


def _require_memory_in_use(result: dict, at_least: int) -> None:
    used = result["bytes_in_use"]
    _check(all(b is not None and b >= at_least for b in used),
           f"a device reports less than {at_least} bytes in use: {used}")


def _child(phase: str, arg: str) -> dict:
    """Run one phase in this process at the chip's sizes. Every branch
    establishes that the devices are TPUs before it compiles anything."""
    if phase == "devices":
        return devices_phase()

    from kungfu_tpu.models.transformer import TransformerConfig
    from kungfu_tpu.parallel.chip import require_tpu

    cfg = TransformerConfig.bert_base()
    if phase in ("launcher-worker", "resize-worker"):
        from kungfu_tpu.parallel import initialize_device_plane

        initialize_device_plane()  # before the backend starts
        require_tpu()
        if phase == "resize-worker":
            resize_worker(cfg, PER_CHIP_BATCH, RESIZES, RESIZE_STEPS, arg)
            sys.exit(0)  # it printed its own result, before the reload
        return launcher_worker(cfg, WORKER_STEPS, PER_CHIP_BATCH)
    require_tpu()
    if phase == "train":
        result = train_phase(cfg, TRAIN_STEPS, PER_CHIP_BATCH)
        # replicated f32 parameters and both AdamW moments, at the least
        _require_memory_in_use(result, 3 * 4 * result["param_count"])
        return result
    if phase == "train-sharded":
        spec = json.loads(arg)
        result = train_sharded_phase(
            cfg, TRAIN_STEPS, spec["global_batch"], spec["losses"]
        )
        # make_mesh reshapes jax.devices() in enumeration order: the
        # chips of one tp group must be ICI neighbours on the 2x2 host
        for row in result["mesh_coords"]:
            for a, b in zip(row, row[1:]):
                hops = sum(abs(x - y) for x, y in zip(a, b))
                _check(hops == 1, f"tp neighbours {a} and {b} are {hops} "
                                  "hops apart")
        return result
    if phase == "hier-worker":
        return hier_worker(cfg, WORKER_STEPS, PER_CHIP_BATCH)
    if phase == "kernels":
        return kernels_phase(KERNEL_SEQ, KERNEL_HEAD_DIMS, interpret=False)
    raise SystemExit(f"chip_smoke: unknown phase {phase!r}")


# -- the parent: no jax here -----------------------------------------------


def _run(name: str, argv, timeout: float):
    """Run one child (or kfrun tree) in a session of its own, echo its
    output, collect its tagged result lines, and leave nothing of it
    behind. Returns (exit code, results, wall seconds)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    t0 = time.monotonic()
    proc = subprocess.Popen(
        argv, cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, start_new_session=True,
    )

    def kill_tree():
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    timer = threading.Timer(timeout, kill_tree)
    timer.start()
    results = []
    try:
        for line in proc.stdout:
            sys.stdout.write(f"[{name}] {line}")
            sys.stdout.flush()
            if RESULT_TAG in line:
                results.append(json.loads(line.split(RESULT_TAG, 1)[1]))
        code = proc.wait()
    finally:
        timed_out = not timer.is_alive()
        timer.cancel()
        kill_tree()  # stragglers of the session, if any
    if timed_out:
        print(f"[{name}] killed after {timeout:.0f} s", flush=True)
        code = code or 124
    return code, results, round(time.monotonic() - t0, 1)


def _kfrun(np_: int, host_chips: int, worker_phase: str, *worker_args,
           elastic: bool = False):
    return [
        sys.executable, "-m", "kungfu_tpu.runner.cli",
        "-np", str(np_), "-H", f"127.0.0.1:{np_}",
        "-devices-per-host", str(host_chips),
        # a job the runner may resize: it watches for Stages, restarts
        # every worker on one, and serves the membership itself
        *(("-w", "-elastic-mode", "reload", "-builtin-config-port", "0")
          if elastic else ()),
        "--", sys.executable, os.path.join(REPO, "chip_smoke.py"),
        worker_phase, *worker_args,
    ]


# a pause's parts, which with `unaccounted_ms` are the pause
PAUSE_PARTS = ("agree_ms", "kill_ms", "spawn_ms", "import_ms", "startup_ms",
               "device_plane_ms", "restore_ms", "broadcast_ms", "compile_ms",
               "first_step_ms")


def resize_report(results, sizes) -> dict:
    """The resize phase's account from its workers' results: one entry an
    incarnation, rank 0's pause with its parts and the slowest rank's.
    Raises SmokeFailure where the job did not run as `sizes` says, lost
    its state on the way, or left a part of a pause unmeasured."""
    versions = sorted({r["version"] for r in results})
    _check(len(versions) == len(sizes),
           f"{len(versions)} incarnations ran, not {len(sizes)}")
    out = []
    for version, size in zip(versions, sizes):
        ranks = sorted((r for r in results if r["version"] == version),
                       key=lambda r: r["rank"])
        _check([r["rank"] for r in ranks] == list(range(size))
               and all(r["workers"] == size for r in ranks),
               f"incarnation {version}: ranks {[r['rank'] for r in ranks]} of "
               f"{[r['workers'] for r in ranks]} workers, not {size}")
        first = ranks[0]
        entry = {
            "version": version, "workers": size,
            "slots": [r["slots"] for r in ranks],
            "local_devices": [r["local_devices"] for r in ranks],
            "coords": [r["coords"] for r in ranks],
            "process_bounds": first["process_bounds"],
            **{k: first[k] for k in (
                "first_step", "last_step", "stop_reason", "first_loss",
                "last_loss", "compile_requests", "smoke_spans_ms",
                "checkpoint_spans_ms")},
        }
        if version != versions[0]:
            for r in ranks:
                _check(r["ranks_agree"]
                       and r["restored_digest"] == r["handed_digest"],
                       f"incarnation {version}, rank {r['rank']}: restored "
                       f"{r['restored_digest']}, saved {r['handed_digest']}")
                parts = r["resize_phases"]
                missing = [k for k in PAUSE_PARTS if parts.get(k) is None]
                _check(not missing, f"incarnation {version}, rank "
                       f"{r['rank']}: no {missing} in {parts}")
                total = sum(parts[k] for k in PAUSE_PARTS) + parts["unaccounted_ms"]
                _check(abs(total - parts["pause_ms"]) < 1.0
                       and parts["unaccounted_ms"] >= 0,
                       f"incarnation {version}, rank {r['rank']}: parts sum to "
                       f"{total}, pause {parts['pause_ms']}")
            entry.update(
                loss_before=first["loss_before"],
                digest=first["restored_digest"][:16],
                pause=first["resize_phases"],
                # a rank that compiles what the others load shows here, and
                # in the others' first step, which waits for it
                parts_by_rank={
                    k: [r["resize_phases"][k] for r in ranks]
                    for k in (*PAUSE_PARTS, "unaccounted_ms", "pause_ms",
                              "compile_hits", "compile_misses")},
                slow_misses_by_rank=[r["slow_misses"] for r in ranks],
            )
        out.append(entry)
    return {"incarnations": out}


def result_line(device: dict) -> str:
    """The last line of a passing run: these keys and no others."""
    return json.dumps({
        "ok": True,
        "device": {
            "platform": str(device["platform"]),
            "kind": str(device["kind"]),
            "count": int(device["count"]),
        },
    })


def main() -> int:
    deadline = time.monotonic() + 1150  # the whole run, compilation included
    phases = {}

    def fail(name: str, why: str, code: int = 1):
        print(f"chip_smoke: phase '{name}' FAILED ({why})", file=sys.stderr)
        sys.exit(code or 1)

    def phase(name: str, argv, want: int = 1, cap: float = 420):
        """Run a phase; exit on its failure, naming it."""
        budget = min(cap, deadline - time.monotonic())
        code, results, wall = (
            _run(name, argv, budget) if budget > 0 else (124, [], 0.0)
        )
        if code != 0 or len(results) != want:
            fail(name, f"exit code {code}, {len(results)} of {want} results",
                 code)
        phases[name] = {"ok": True, "wall_s": wall}
        return results

    me = [sys.executable, os.path.join(REPO, "chip_smoke.py")]
    (found,) = phase("devices", me + ["devices"], cap=180)
    n = found["device"]["count"]

    (train,) = phase("train", me + ["train"])
    phases["train"].update(train)

    if n >= 4:
        spec = {"global_batch": train["global_batch"],
                "losses": train["losses"][:3]}
        (sharded,) = phase(
            "train-sharded", me + ["train-sharded", json.dumps(spec)]
        )
        phases["train-sharded"].update(sharded)

    # the host plane's native kernels are not tracked: build them here,
    # on the CPU that will run them, from the tracked sources
    build = subprocess.run(
        ["sh", os.path.join(REPO, "native", "build.sh")],
        capture_output=True, text=True,
    )
    if build.returncode != 0:
        print(build.stdout + build.stderr, flush=True)
        fail("launcher", f"native/build.sh exit code {build.returncode}")

    t_kfrun = time.time()
    workers = phase("launcher", _kfrun(n, n, "launcher-worker"), want=n)
    workers.sort(key=lambda w: w["rank"])
    # command start -> the runner spawned rank 0: the runner's own start
    workers[0]["command_to_spawn_s"] = round(
        workers[0].pop("spawn_ts") - t_kfrun, 3)
    phases["launcher"].update(workers[0], workers=n, local_devices=[
        w["local_devices"] for w in workers
    ])
    native = all(w["native_kernels"] for w in workers)

    if n >= 4:
        import shutil
        import tempfile

        scratch = tempfile.mkdtemp(prefix="chip_smoke_resize_")
        try:
            sizes = [n, *RESIZES.values()]
            # 175 s with the compile cache warm, 275 s with it cold (PR 54)
            incarnations = phase(
                "resize", _kfrun(n, n, "resize-worker", scratch, elastic=True),
                want=sum(sizes),
            )
        finally:
            shutil.rmtree(scratch, ignore_errors=True)
        try:
            phases["resize"].update(resize_report(incarnations, sizes))
        except SmokeFailure as e:
            fail("resize", str(e))

    if n >= 4:
        worlds = phase("launcher-hier", _kfrun(2, n, "hier-worker"), want=2)
        worlds.sort(key=lambda w: w["rank"])
        phases["launcher-hier"].update(worlds[0], local_devices=[
            w["local_devices"] for w in worlds
        ])
        native = native and all(w["native_kernels"] for w in worlds)

    (kernels,) = phase("kernels", me + ["kernels"])
    phases["kernels"].update(kernels)

    for p in phases.values():
        p.pop("device", None)
    print(REPORT_TAG + json.dumps({
        "versions": found["versions"],
        "model": "TransformerConfig.bert_base(), uncut",
        "per_chip_batch": PER_CHIP_BATCH,
        "native_kernels": native,
        "phases": phases,
    }))
    print(result_line(found["device"]), flush=True)
    return 0


if __name__ == "__main__":
    if len(sys.argv) == 1:
        sys.exit(main())
    # a child of the run above: one phase, one tagged result line
    outcome = _child(sys.argv[1], sys.argv[2] if len(sys.argv) > 2 else "")
    print(RESULT_TAG + json.dumps(outcome), flush=True)
