"""Benchmark: ResNet-50 training throughput (images/sec/chip) on TPU,
running through the framework's own training path.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

The step is built the way users build it: a `jax.sharding.Mesh` over all
chips, `shard_map` SPMD, and the `synchronous_sgd` optimizer wrapper whose
traced `pmean` is the framework's gradient AllReduce (one chip degenerates
to an identity reduce, but the compiled program is the real S-SGD path).
Cross-replica batch-norm stats are pmean-synced like the gradients.

It runs on a TPU only: it fails at start on any other platform, takes the
chip's peak from the one `device_kind` table (`kungfu_tpu.parallel.chip`)
and XLA's own FLOP count of the compiled step, and fails where either is
missing.

Execution shape (round 5): the host loop dispatches ONE jit call that
`lax.scan`s over INNER distinct pre-staged batches — the standard TPU
train-loop pattern (amortizes per-dispatch latency). Batches are distinct
per scan step so XLA cannot hoist per-batch input transforms out of the
loop; inputs are fed bfloat16.

Profile note (round 5, earlier stack; not measured on the current one):
the device step is bandwidth-bound, not compute-bound. Per 47 ms device
step at batch 128: conv fusions ~21 ms running at ~65% sustained MXU efficiency
(the chip's measured large-matmul ceiling), batch-norm statistic
reductions (convert_reduce fusions) ~22 ms, maxpool backward
(select_and_scatter) ~0.7 ms. The norm reductions are HBM-limited: a
GroupNorm variant times identically, and neither MXU-dot-based stats nor
layout changes move it — XLA's cost model puts the step's arithmetic
intensity at ~70 FLOP/byte, below the v5e compute/bandwidth ratio of 240,
so the roofline is memory bandwidth.

MFU convention: FLOPs = multiplies + adds (2 FLOPs per MAC), the standard
MFU accounting (PaLM appendix / scaling-book). ResNet-50 forward at
224x224 is 4.1 GMACs = 8.2 GFLOPs/img; training ~= 3x forward = 24.6
GFLOPs/img. This matches XLA's own cost analysis of the compiled step
(3.06e12 flops / 128 imgs = 23.9 GFLOPs/img), which is the figure
used. (Rounds 1-4 divided by peak using MAC counts — i.e. reported
half the standard-convention MFU.) `mfu_macs` preserves the old
accounting for cross-round comparability.

Baseline: the reference's headline workload is ResNet-50 synchronous SGD
(README "Benchmark", 16x V100). Published-era per-GPU throughput for
TF ResNet-50 fp32 on V100 is ~350 images/sec (the regime of the
reference's charts, benchmarks/system/result/sync-scalability.svg);
vs_baseline = our images/sec/chip / 350.

Second metric (resize latency, BASELINE.md north star #2): bench_resize.py.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

BASELINE_IMG_PER_SEC = 350.0  # TF ResNet-50 fp32 on V100, reference era
INNER = 16  # scanned train steps per dispatch
ANALYTIC_FLOPS_PER_IMG = 24.6e9  # 3 x the 8.2 GFLOPs forward, see above


def main() -> None:
    from kungfu_tpu.models.resnet import init_resnet, resnet50, resnet_loss
    from kungfu_tpu.optimizers import synchronous_sgd
    from kungfu_tpu.parallel import make_mesh
    from kungfu_tpu.parallel.chip import enable_compile_cache, require_tpu

    chip = require_tpu()
    enable_compile_cache()
    n_chips = jax.device_count()
    per_chip_batch = 128
    batch = per_chip_batch * n_chips
    image_size = 224
    model = resnet50(num_classes=1000)
    key = jax.random.PRNGKey(0)
    params, batch_stats = init_resnet(key, model, image_size, batch=2)

    mesh = make_mesh({"dp": n_chips})
    opt = synchronous_sgd(optax.sgd(0.1, momentum=0.9), axis_name="dp")
    opt_state = opt.init(params)

    def local_loop(params, batch_stats, opt_state, images, labels):
        """INNER training steps over distinct batches, one dispatch."""

        def one(carry, batch_data):
            params, batch_stats, opt_state = carry

            def loss_fn(p):
                return resnet_loss(model, p, batch_stats, batch_data)

            (loss, new_stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params)
            # synchronous_sgd's update pmeans the grads over dp (the AllReduce)
            updates, opt_state2 = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            # cross-replica BN stats, like the gradient sync
            new_stats = jax.tree.map(lambda x: lax.pmean(x, "dp"), new_stats)
            return (params, new_stats, opt_state2), lax.pmean(loss, "dp")

        (params, batch_stats, opt_state), losses = lax.scan(
            one, (params, batch_stats, opt_state), (images, labels)
        )
        return params, batch_stats, opt_state, losses[-1]

    step = jax.jit(
        shard_map(
            local_loop,
            mesh=mesh,
            in_specs=(P(), P(), P(), P(None, "dp"), P(None, "dp")),
            out_specs=(P(), P(), P(), P()),
            check_vma=False,
        ),
        donate_argnums=(0, 1, 2),
    )

    sharded = NamedSharding(mesh, P(None, "dp"))
    # INNER distinct bf16 batches, staged on device once (synthetic data,
    # like the reference's benchmark harness)
    images = jax.device_put(
        jax.random.normal(
            key, (INNER, batch, image_size, image_size, 3), jnp.bfloat16
        ),
        sharded,
    )
    labels = jax.device_put(
        jnp.zeros((INNER, batch), jnp.int32), sharded
    )

    # FLOPs of the compiled step from XLA's cost model (per-image). XLA
    # counts the scan (while-loop) body ONCE, not per trip, so the
    # per-image figure divides by batch only; a figure far from the
    # analytic one means that convention changed, and the run fails
    # rather than report an MFU built on it.
    xla_flops = step.lower(
        params, batch_stats, opt_state, images, labels
    ).compile().cost_analysis()["flops"]
    train_flops_per_img = xla_flops / batch
    if not 0.5 <= train_flops_per_img / ANALYTIC_FLOPS_PER_IMG <= 2.0:
        raise RuntimeError(
            f"XLA cost analysis gives {train_flops_per_img / 1e9:.1f} "
            "GFLOPs/img for ResNet-50 training; expected about "
            f"{ANALYTIC_FLOPS_PER_IMG / 1e9:.1f}"
        )

    # warmup/compile
    for _ in range(2):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, images, labels
        )
    float(jax.device_get(loss))

    # best-of-windows: the minimum over several dispatches rejects
    # interference from other tenants of the host (timeit-min methodology)
    best_dt = float("inf")
    for _ in range(6):
        t0 = time.perf_counter()
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, images, labels
        )
        float(jax.device_get(loss))
        best_dt = min(best_dt, (time.perf_counter() - t0) / INNER)

    per_chip = per_chip_batch / best_dt
    mfu = per_chip * train_flops_per_img / chip.bf16_flops
    print(
        json.dumps(
            {
                "metric": "resnet50_ssgd_train_throughput_per_chip",
                "value": round(per_chip, 2),
                "unit": "images/sec/chip",
                "vs_baseline": round(per_chip / BASELINE_IMG_PER_SEC, 3),
                "step_ms": round(best_dt * 1e3, 2),
                "mfu": round(mfu, 4),
                "mfu_macs": round(mfu / 2.0, 4),
                "flops_per_img": round(train_flops_per_img / 1e9, 1),
                "device": jax.devices()[0].device_kind,
            }
        )
    )


if __name__ == "__main__":
    main()
