"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh
axis, with ppermute stage handoffs.

Beyond-reference capability (the reference is data-parallel only,
SURVEY §2.4): the transformer's layer-stacked parameter layout (leading
layer axis) shards directly over a ``pp`` mesh axis — each stage holds
n_layers/pp contiguous blocks — and the classic GPipe schedule runs as a
`lax.scan` over M + P - 1 ticks: at every tick each stage transforms the
activation it holds and hands it to the next stage via `ppermute` (ICI
neighbor traffic), stage 0 injects a fresh microbatch, and the last stage
accumulates the LM loss. Backward differentiates straight through the
scan + ppermute (the transpose of a shift is the reverse shift), giving
1F1B-equivalent math with GPipe scheduling.

Composes with data parallelism: batch over ``dp``, layers over ``pp``.
Bubble fraction is (P-1)/(M+P-1); pick n_micro >= ~4x the stage count.
Each stage also computes the (cheap) LM head every tick — dead compute on
non-final stages that XLA cannot skip under SPMD; acceptable because the
head is O(D*V) vs the stages' O(L/P * D^2 * S) blocks.

Negative results (round 5, measured at pp=4 on the 8-device CPU mesh,
vocab-heavy config where the dead head compute is LARGEST): gating the
per-tick head (and the stage-0 embed gather) behind `lax.cond` so only
the owning stage executes it ran 2x SLOWER end-to-end — AD through a
conditional inside the tick scan costs far more than the skipped flops;
hoisting the head out of the scan over stacked per-tick outputs (one
large matmul, single mask site) was 13% slower (extra stacked-activation
traffic, and the off-stage copies remain dead under where()). The
where()-masked schedule stands as the measured-fastest formulation; a
hand-scheduled 1F1B (manual backward interleave) is the remaining
approach and is out of scope while its main win (activation memory)
is already bounded by the scan's per-tick residuals.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def make_pp_transformer_loss(cfg, mesh, n_micro: int, pp_axis: str = "pp",
                             dp_axis: str = None):
    """Pipelined causal-LM loss for kungfu_tpu.models.transformer params.

    batch = (tokens, targets), both (B, S); B divisible by n_micro (and by
    the dp axis when given). Returns loss_fn(params, batch) -> replicated
    scalar, jit/grad-compatible."""
    from kungfu_tpu.models.transformer import _block, lm_head_loss

    n_stages = mesh.shape[pp_axis]
    if cfg.n_layers % n_stages:
        raise ValueError(
            f"n_layers {cfg.n_layers} not divisible by pp={n_stages}"
        )

    def shard_fn(params, batch):
        tokens, targets = batch
        stage = lax.axis_index(pp_axis)
        B, S = tokens.shape
        if B % n_micro:
            raise ValueError(f"batch {B} not divisible by n_micro {n_micro}")
        b = B // n_micro
        dt = cfg.dtype
        embed = params["embed"].astype(dt)
        pos = params["pos_embed"].astype(dt)[:S]
        micro_tok = tokens.reshape(n_micro, b, S)
        micro_tgt = targets.reshape(n_micro, b, S)

        is_first = stage == 0
        is_last = stage == n_stages - 1
        shift = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            act_in, loss_acc = carry
            # stage 0 injects microbatch t (while t < n_micro); the value
            # is ignored on other stages / out-of-range ticks
            m_in = jnp.clip(t, 0, n_micro - 1)
            x0 = embed[micro_tok[m_in]] + pos
            x = jnp.where(is_first, x0, act_in)
            x, _ = lax.scan(
                lambda h, layer: (_block(h, layer, cfg), None),
                x,
                params["layers"],  # THIS stage's layer slice
            )
            # the microbatch leaving the last stage at tick t entered at
            # t - (n_stages - 1)
            m_out = t - (n_stages - 1)
            valid = (m_out >= 0) & (m_out < n_micro)
            tgt = micro_tgt[jnp.clip(m_out, 0, n_micro - 1)]
            l = lm_head_loss(params, x, tgt, cfg)
            loss_acc = loss_acc + jnp.where(is_last & valid, l, 0.0)
            act_out = (
                lax.ppermute(x, pp_axis, shift) if n_stages > 1 else x
            )
            return (act_out, loss_acc), None

        act0 = jnp.zeros((b, S, cfg.d_model), dt)
        ticks = jnp.arange(n_micro + n_stages - 1)
        (_, loss_acc), _ = lax.scan(tick, (act0, jnp.float32(0.0)), ticks)
        # only the last stage accumulated anything; share it with everyone
        loss = lax.psum(jnp.where(is_last, loss_acc, 0.0), pp_axis) / n_micro
        if dp_axis is not None:
            loss = lax.pmean(loss, dp_axis)
        return loss

    batch_spec = P(dp_axis) if dp_axis is not None else P()
    param_specs = {
        "embed": P(),
        "pos_embed": P(),
        "ln_f_scale": P(),
        # layer-stacked leaves shard their leading (layer) axis over pp
        "layers": jax.tree.map(lambda _: P(pp_axis), {
            "ln1_scale": 0, "ln2_scale": 0, "wqkv": 0, "wo": 0,
            "w_in": 0, "w_out": 0,
        }),
    }
    return jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(param_specs, (batch_spec, batch_spec)),
        out_specs=P(),
        check_vma=False,
    )
