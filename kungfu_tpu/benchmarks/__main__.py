"""Allreduce throughput benchmark.

Capability parity: python -m kungfu.tensorflow.v1.benchmarks
(srcs/python/kungfu/tensorflow/v1/benchmarks/__main__.py) — measure
allreduce bus throughput over a fake model's gradient set and print
``RESULT: <v> +-<e> (GiB/s)``. Methods:
  XLA   — on-device psum over the local mesh (the ICI data plane)
  HOST  — the host-side graph-walk engine (DCN plane; run under kfrun)
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from kungfu_tpu.telemetry import log


def bench_xla(model: str, iters: int, warmup: int = 3) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from kungfu_tpu.models.fake import FAKE_MODELS
    from kungfu_tpu.ops.collective import group_all_reduce
    from kungfu_tpu.parallel import make_mesh, DeviceSession

    sizes = FAKE_MODELS[model]
    sess = DeviceSession(make_mesh())
    n = sess.size
    xs = [jnp.ones((n, s), jnp.float32) for s in sizes]
    fn = sess.spmd(
        lambda t: group_all_reduce(t, sess.axis_names[0]),
        in_specs=P(sess.axis_names[0]),
        out_specs=P(),
    )
    for _ in range(warmup):
        out = fn(xs)
    float(jax.device_get(out[0][0, 0]))  # warm-up done before timing

    samples = []
    total_bytes = sum(s * 4 for s in sizes)
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(xs)
        float(jax.device_get(out[-1][0, 0]))
        dt = time.perf_counter() - t0
        # algorithm bandwidth: 2(n-1)/n factors omitted — report bus data rate
        samples.append(total_bytes / dt / (1 << 30))
    mean, err = float(np.mean(samples)), float(1.96 * np.std(samples))
    log.echo(f"RESULT: {mean:.3f} +-{err:.3f} (GiB/s) [XLA x{n} devices, {model}]")


def _wire_samples() -> dict:
    """Per-(collective, strategy, codec) wire-byte counter values for
    THIS worker process (each worker owns its registry, so these are
    true per-peer numbers — the in-process test suite only sees
    aggregates)."""
    from kungfu_tpu.telemetry import metrics as tmetrics

    ctr = tmetrics.counter(
        "kungfu_collective_wire_bytes_total",
        "Host-plane collective payload bytes sent by this peer",
        ("collective", "strategy", "codec"),
    )
    return {labels: value for _, labels, value in ctr.samples()}


def _wire_saved() -> float:
    """Total bytes the codec kept off the wire (this peer)."""
    from kungfu_tpu.telemetry import metrics as tmetrics

    ctr = tmetrics.counter(
        "kungfu_collective_wire_saved_bytes_total",
        "Wire bytes saved by the collective codec on this peer",
        ("collective", "codec"),
    )
    return sum(value for _, _, value in ctr.samples())


def _simulated_backprop(grads, scratch, passes: int = 16) -> None:
    """Deterministic per-tensor FLOP load standing in for backward-pass
    compute (the bench has no real model). 16 passes of elementwise
    work per parameter is a LOW bound on a real backward pass's
    FLOP-to-gradient-bytes ratio (a conv/matmul backward touches each
    weight far more than 16 times), so the overlap this measures is the
    conservative end of what a real step offers the scheduler. Both
    legs pay the identical load, so the A/B ratio stays drift-free, and
    it never mutates the gradients — the bit-identity claim depends on
    both legs reducing the same bytes."""
    for g, s in zip(grads, scratch):
        for _ in range(passes):
            np.multiply(g, np.float32(1.0000001), out=s)


def bench_host_async_ab(model: str, iters: int, warmup: int = 4,
                        passes: int = 16) -> None:
    """Paired same-process async-scheduler A/B (ISSUE 10): the SYNC leg
    runs the serial step loop — simulate every tensor's backward
    compute, then one step-end `group_all_reduce_arrays` — while the
    ASYNC leg submits each tensor to the background scheduler the moment
    its compute finishes (readiness order: last layer first, like real
    backprop) and only flushes the tail. Legs interleave in alternating
    rounds within one process/session, so box drift cancels out of the
    ratio. The OVERLAP line reports the measured flush-wait vs
    engine-busy time — flush-wait ≪ walk time is the overlap actually
    happening, not inferred."""
    from kungfu_tpu import api
    from kungfu_tpu.models.fake import fake_gradients
    from kungfu_tpu.peer import get_default_peer

    grads = fake_gradients(model)
    outs = [np.empty_like(g) for g in grads]
    scratch = [np.empty_like(g) for g in grads]
    total_bytes = sum(g.nbytes for g in grads)
    sess = get_default_peer().current_session()
    if not sess.async_enabled():
        raise SystemExit(
            "--async A/B needs the scheduler: KF_CONFIG_ASYNC=on|auto "
            "must reach every worker before the session comes up (the "
            "--async flag sets it process-wide; under kfrun use "
            "KF_BENCH_ASYNC with the bench agent)"
        )
    sched = sess.scheduler()
    n = len(grads)
    legs: dict = {"sync": [], "async": []}
    rounds = 8  # 4 alternating rounds per mode
    # allow per=1: the async A/B pays a simulated backward per sample,
    # so bert-size sets at 16 steps blow through any reasonable harness
    # timeout — --iters controls the budget
    per = max(1, iters // 4)

    def run_sync(tag: str) -> None:
        _simulated_backprop(grads, scratch, passes)
        api.group_all_reduce_arrays(grads, name=tag, outs=outs)

    def run_async() -> None:
        # readiness order: reversed (the last layer's gradient exists
        # first); registration pins the launch order from round one, so
        # every peer walks identical bucket sequences regardless
        for i in reversed(range(n)):
            _simulated_backprop(grads[i : i + 1], scratch[i : i + 1], passes)
            api.group_all_reduce_async(
                [grads[i]], name=f"b{i}", outs=[outs[i]]
            )
        api.flush_async()

    api.run_barrier()
    for i in range(warmup):
        run_sync(f"wu:{i}")
    run_async()  # registration round + async staging warmup
    api.run_barrier()
    stats0 = sched.stats()
    for rnd in range(rounds):
        mode = "sync" if rnd % 2 == 0 else "async"
        samples = legs[mode]
        for it in range(per):
            t0 = time.perf_counter()
            if mode == "sync":
                # per-iteration names: a fast worker's next-iteration
                # sends must not be consumed by a slow worker still in
                # this one
                run_sync(f"ab:{rnd}:{it}")
            else:
                run_async()
            samples.append(
                total_bytes / (time.perf_counter() - t0) / (1 << 30)
            )
        api.run_barrier()
    stats1 = sched.stats()
    if api.current_rank() != 0:
        return
    meds = {m: float(np.median(s)) for m, s in legs.items()}
    for m, s in legs.items():
        log.echo(
            f"RESULT: {float(np.mean(s)):.3f} "
            f"+-{float(1.96 * np.std(s)):.3f} (GiB/s) "
            f"median {meds[m]:.3f} [HOST-AB async={m}, "
            f"x{api.cluster_size()} workers, {model}, "
            f"{len(s)} interleaved samples]"
        )
    log.echo(
        f"RESULT: async / sync median speedup: "
        f"{meds['async'] / meds['sync']:.2f}x [interleaved paired, "
        f"{model}, simulated backprop]"
    )
    a_rounds = max(1, stats1["rounds"] - stats0["rounds"])
    flush_wait = (stats1["flush_wait_s"] - stats0["flush_wait_s"]) / a_rounds
    busy = (stats1["busy_s"] - stats0["busy_s"]) / a_rounds
    overlap = (stats1["overlap_s"] - stats0["overlap_s"]) / a_rounds
    frac = overlap / busy if busy > 0 else 0.0
    ratio = flush_wait / busy if busy > 0 else float("inf")
    log.echo(
        f"OVERLAP {model}: flush-wait {flush_wait * 1e3:.1f} ms vs walk "
        f"{busy * 1e3:.1f} ms per step — {frac:.0%} of engine time "
        f"overlapped with backprop (flush-wait/walk {ratio:.2f})"
    )


def bench_host(model: str, iters: int, warmup: int = 4) -> None:
    from kungfu_tpu import api
    from kungfu_tpu.models.fake import fake_gradients

    from kungfu_tpu.collective.host_session import get_walk_profiler

    grads = fake_gradients(model)
    outs = [np.empty_like(g) for g in grads]
    total_bytes = sum(g.nbytes for g in grads)
    api.run_barrier()
    # warmup: connection + shm-arena setup and first-touch page faults
    # belong to session bring-up, not steady-state bandwidth (the XLA
    # bench warms up identically). 4 rounds, not 2: the wire codec's
    # pooled staging buffers (wire + encode scratches) are new exact-
    # size pool bins whose first-touch ramp measurably lasts past 2
    # iterations on the bench box
    for i in range(warmup):
        api.group_all_reduce_arrays(grads, name=f"warmup:{i}", outs=outs)
    wire_before = _wire_samples()
    saved_before = _wire_saved()
    # the EFF report below must describe the measured iterations only:
    # warmup walks run on cold pools and would drag the attribution
    get_walk_profiler().reset()
    samples = []
    for i in range(iters):
        t0 = time.perf_counter()
        api.group_all_reduce_arrays(grads, name=f"bench:{i}", outs=outs)
        dt = time.perf_counter() - t0
        samples.append(total_bytes / dt / (1 << 30))
    wire_after = _wire_samples()
    saved = _wire_saved() - saved_before
    mean, err = float(np.mean(samples)), float(1.96 * np.std(samples))
    if api.current_rank() == 0:
        med = float(np.median(samples))
        log.echo(
            f"RESULT: {mean:.3f} +-{err:.3f} (GiB/s) median {med:.3f} "
            f"[HOST x{api.cluster_size()} workers, {model}]"
        )
        # per-peer wire bytes (this rank): the A/B numbers behind the
        # segmented engine (2(k-1)/k x payload vs full-payload relays)
        # and the wire codec (a further /2 on compressed series); labels
        # are (collective, strategy, codec)
        for labels, after in sorted(wire_after.items()):
            delta = after - wire_before.get(labels, 0.0)
            if delta <= 0:
                continue
            per_iter = delta / iters
            log.echo(
                f"WIRE {labels}: {per_iter / (1 << 20):.1f} MiB/iter "
                f"({per_iter / total_bytes:.2f}x payload)"
            )
        if saved > 0:
            log.echo(
                f"WIRE saved by codec: {saved / iters / (1 << 20):.1f} "
                f"MiB/iter ({saved / iters / total_bytes:.2f}x payload)"
            )
        # utilization, not just bytes (ISSUE 6): per walk family the
        # achieved throughput at the 2(k-1)/k*N bandwidth-optimal byte
        # volume, the efficiency ratio against the measured link speed
        # when the link plane has an estimate, and where the walk time
        # went (wait-on-recv / reduce+codec compute / send-blocked)
        for key, s in sorted(get_walk_profiler().snapshot().items()):
            eff = s.get("efficiency")
            eff_s = f", {eff:.2f} of link bw" if eff is not None else ""
            log.echo(
                f"EFF {key}: {s['achieved_gib_s']:.3f} GiB/s at the "
                f"2(k-1)/k bound{eff_s} "
                f"(wait {s['wait_frac']:.0%} compute {s['compute_frac']:.0%} "
                f"send {s['send_frac']:.0%}, {s['walks']} walks)"
            )
        # where the time went (hot-path spans, this process only)
        summary = api.trace_summary()
        top = sorted(summary.items(), key=lambda kv: -kv[1])[:10]
        for name, ms in top:
            log.echo(f"TRACE {name}: {ms:.0f} ms")


def bench_p2p(model: str, iters: int) -> None:
    """p2p model-request throughput (parity: kungfu-bench-p2p,
    tests/go/cmd/ — each worker fetches its ring neighbour's published
    model from the versioned store)."""
    from kungfu_tpu import api
    from kungfu_tpu.models.fake import fake_gradients

    blob = b"".join(g.tobytes() for g in fake_gradients(model))
    rank, size = api.current_rank(), api.cluster_size()
    api.save("bench-model", blob, version=0)
    api.run_barrier()
    peer = (rank + 1) % size
    samples = []
    for i in range(iters):
        t0 = time.perf_counter()
        got = api.request(peer, "bench-model", version="latest")
        dt = time.perf_counter() - t0
        assert got is not None and len(got) == len(blob)
        samples.append(len(blob) / dt / (1 << 30))
    api.run_barrier()
    mean, err = float(np.mean(samples)), float(1.96 * np.std(samples))
    if rank == 0:
        log.echo(
            f"RESULT: {mean:.3f} +-{err:.3f} (GiB/s) "
            f"[P2P x{size} workers, {model}]"
        )


def bench_gns(iters: int) -> None:
    """GNS monitoring overhead: train-step time with the plain S-SGD
    optimizer vs monitor_gradient_noise_scale wrapping the same base.

    Parity: the reference ships the harness but publishes no number
    (benchmarks/monitoring/benchmark.py, BASELINE.md row 'GNS monitoring
    overhead'). Runs a small MLP over the local device mesh."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax import lax

    from kungfu_tpu.models.mlp import init_mlp, mlp_loss
    from kungfu_tpu.monitor import monitor_gradient_noise_scale
    from kungfu_tpu.optimizers import synchronous_sgd
    from kungfu_tpu.parallel import DeviceSession, make_mesh
    from jax.sharding import PartitionSpec as P

    sess = DeviceSession(make_mesh())
    axis = sess.axis_names[0]
    params = init_mlp(jax.random.PRNGKey(0))
    x = jnp.ones((64 * sess.size, 784), jnp.float32)
    y = jnp.zeros((64 * sess.size,), jnp.int32)

    def make_step(opt):
        state = opt.init(params)

        def local(params, state, x, y):
            loss, grads = jax.value_and_grad(mlp_loss)(params, (x, y))
            updates, state = opt.update(grads, state, params)
            return optax.apply_updates(params, updates), state, lax.pmean(loss, axis)

        step = sess.spmd(
            local,
            in_specs=(P(), P(), P(axis), P(axis)),
            out_specs=(P(), P(), P()),
        )
        return step, state

    def timeit(opt):
        step, state = make_step(opt)
        p = params
        for _ in range(3):
            p, state, loss = step(p, state, x, y)
        float(jax.device_get(loss))
        best = float("inf")
        for _ in range(max(3, iters // 3)):
            t0 = time.perf_counter()
            for _ in range(10):
                p, state, loss = step(p, state, x, y)
            float(jax.device_get(loss))
            best = min(best, (time.perf_counter() - t0) / 10)
        return best * 1e3

    base = optax.sgd(0.1)
    t_plain = timeit(synchronous_sgd(base, axis))
    t_gns = timeit(monitor_gradient_noise_scale(base, batch_small=64, axis_name=axis))
    log.echo(
        f"RESULT: plain {t_plain:.3f} ms/step, +GNS {t_gns:.3f} ms/step, "
        f"overhead {100 * (t_gns - t_plain) / t_plain:+.1f}% "
        f"[GNS x{sess.size} devices]"
    )


def main() -> None:
    p = argparse.ArgumentParser("kungfu_tpu.benchmarks")
    p.add_argument("--method", choices=["XLA", "HOST", "P2P", "GNS"], default="XLA")
    p.add_argument("--model", default="resnet50-imagenet")
    p.add_argument("--iters", type=int, default=10)
    p.add_argument(
        "--algo", choices=["auto", "tree", "segmented"], default="",
        help="HOST engine A/B: force the collective algorithm family "
        "(sets KF_CONFIG_ALGO before the session comes up; every worker "
        "runs the same argv so the override is cluster-agreed)",
    )
    p.add_argument(
        "--wire", choices=["off", "bf16", "f16", "auto", "int8", "int4"],
        default="",
        help="HOST engine A/B: wire codec for f32 payloads (sets "
        "KF_CONFIG_WIRE before the session comes up; cluster-agreed the "
        "same way as --algo). int8/int4 are the block-scaled quantized "
        "codecs (ISSUE 20) with error-feedback on the segmented paths",
    )
    p.add_argument(
        "--async", action="store_true", dest="async_ab",
        help="HOST only: paired same-process async-scheduler A/B — "
        "alternate the serial step loop (compute all, then one step-end "
        "group allreduce) with readiness-ordered submission to the "
        "background scheduler (KF_CONFIG_ASYNC=on, set before the "
        "session comes up), report both medians, the drift-free speedup "
        "and the OVERLAP line (flush-wait vs walk time)",
    )
    p.add_argument(
        "--passes", type=int, default=16,
        help="HOST --async only: simulated-backprop passes per tensor "
        "(compute:comm ratio of the A/B; 16 is a conservative LOW bound "
        "for real backward passes — raise it to model matmul-heavy "
        "layers, e.g. when a shaped link makes comm sleep-dominated)",
    )
    args = p.parse_args()
    if args.method != "HOST" and (args.algo or args.wire or args.async_ab):
        # the default method is XLA: silently measuring the wrong plane
        # is worse than an error
        p.error("--algo/--wire/--async only apply to --method HOST")
    if args.method == "HOST":
        import os

        if args.algo:
            os.environ["KF_CONFIG_ALGO"] = args.algo
        if args.wire:
            os.environ["KF_CONFIG_WIRE"] = args.wire
        if args.async_ab:
            os.environ["KF_CONFIG_ASYNC"] = "on"
        # wire-byte accounting rides the metrics gate; the bench wants it
        # on regardless so the A/B always reports bytes per peer
        from kungfu_tpu.telemetry import config as tconfig

        tconfig.enable("metrics")
    if args.method == "XLA":
        bench_xla(args.model, args.iters)
    elif args.method == "P2P":
        bench_p2p(args.model, args.iters)
    elif args.method == "GNS":
        bench_gns(args.iters)
    elif args.async_ab:
        bench_host_async_ab(args.model, args.iters, passes=args.passes)
    else:
        bench_host(args.model, args.iters)


if __name__ == "__main__":
    main()
