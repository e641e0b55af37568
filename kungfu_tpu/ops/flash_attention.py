"""Pallas flash attention: fused causal self-attention for the MXU.

The hot op done as a TPU kernel (pallas_guide.md playbook): per (batch x
head, q-block) grid program, the q tile stays in VMEM while K/V stream
through block by block with an online (flash) softmax — the (S, S) score
matrix never materializes in HBM, so peak memory is O(BLK_Q x S_block)
instead of O(S^2). Causal programs stop at their diagonal block (the
upper-triangular half is never computed at all).

Differentiable via custom_vjp: the forward kernel also emits the per-row
log-sum-exp, and the backward runs two fused Pallas kernels (dq over
k-blocks; dk/dv over q-blocks) that recompute exact block probabilities
from it — the standard two-pass flash backward. Neither direction ever
materializes an (S, S) tensor. A sequence length the blocks do not
divide is an error, not a dense fallback.

The kernels compile with Mosaic unless the caller passes
`interpret=True` (the tests, on the CPU mesh); the backend is never
consulted to choose. `models/transformer.py` runs them as the attention
core of a configuration with `attn_core="flash"`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _dense_reference(q, k, v, causal: bool, sm_scale: float):
    S = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _nt(a, b):
    """a @ b.T with float32 accumulation, the operands in the type they
    came in: bfloat16 q/k/v go to the MXU as bfloat16 (an upcast to float32
    first costs the multi-pass float32 matmul), float32 ones stay float32."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
            blk_q: int, blk_k: int, causal: bool, sm_scale: float):
    """One (bh, q-block, k-block) grid program. The TPU grid runs the
    LAST dimension sequentially on one core, so the (m, l, acc) flash
    accumulators live in VMEM scratch across the k-block sweep; K/V
    arrive one block at a time via BlockSpec streaming — VMEM holds
    O(blk) state regardless of S."""
    from jax.experimental import pallas as pl

    kb = pl.program_id(2)
    qi = pl.program_id(1)
    n_kb = pl.num_programs(2)
    q_off = qi * blk_q
    k_off = kb * blk_k

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    # causal: blocks fully above the diagonal contribute nothing
    live = (k_off <= q_off + blk_q - 1) if causal else (kb >= 0)

    @pl.when(live)
    def _compute():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = _nt(q, k) * sm_scale
        if causal:
            qpos = q_off + lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
            kpos = k_off + lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
            mask = kpos <= qpos
            s = jnp.where(mask, s, NEG_INF)
            maskf = mask.astype(jnp.float32)
        else:
            maskf = 1.0
        m = m_scr[:, :1]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new) * maskf
        corr = jnp.exp(m - m_new)
        l_scr[:, :1] = l_scr[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_scr[:, :1] = m_new

    @pl.when(kb == n_kb - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)
        # log-sum-exp per row: the backward recomputes exact block probs
        # as exp(s - lse) without re-running the online max/sum recurrence.
        # Stored 8-lane-replicated: Mosaic wants the last block dim ==
        # the array dim (8) and the stats are sublane-oriented anyway,
        # so this layout round-trips with zero relayouts.
        lse_ref[0] = jnp.broadcast_to(
            m_scr[:, :1] + jnp.log(l_scr[:, :1]), lse_ref[0].shape
        )


def _blocks(S: int, blk_q: int, blk_k: int):
    """Clamp the block sizes to S; raise when they do not tile it."""
    blk_q = min(blk_q, S)
    blk_k = min(blk_k, S)
    if S % blk_q or S % blk_k:
        raise ValueError(
            f"flash_attention: sequence length {S} is not a multiple of "
            f"the blocks ({blk_q}, {blk_k})"
        )
    return blk_q, blk_k


def _kv_index(blk_q, blk_k, causal, b, i, j):
    if not causal:
        return (b, j, 0)
    diag = (i * blk_q + blk_q - 1) // blk_k  # last live k-block for q-block i
    return (b, jnp.minimum(j, diag), 0)


def _forward(q, k, v, causal: bool, sm_scale: float, blk_q: int,
             blk_k: int, interpret, with_lse: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, hd = q.shape
    blk_q, blk_k = _blocks(S, blk_q, blk_k)
    qf = q.reshape(B * H, S, hd)
    kf = k.reshape(B * H, S, hd)
    vf = v.reshape(B * H, S, hd)
    out, lse = pl.pallas_call(
        functools.partial(_kernel, blk_q=blk_q, blk_k=blk_k, causal=causal,
                          sm_scale=sm_scale),
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
            jax.ShapeDtypeStruct((B * H, S, 8), jnp.float32),
        ],
        grid=(B * H, S // blk_q, S // blk_k),
        in_specs=[
            pl.BlockSpec((1, blk_q, hd), lambda b, i, j: (b, i, 0)),
            # causal: clamp the K/V block index at the q-block's diagonal
            # so dead above-diagonal blocks repeat the previous index and
            # Pallas skips their HBM fetch entirely (pl.when already
            # skips their compute)
            pl.BlockSpec((1, blk_k, hd), functools.partial(_kv_index, blk_q, blk_k, causal)),
            pl.BlockSpec((1, blk_k, hd), functools.partial(_kv_index, blk_q, blk_k, causal)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_q, 8), lambda b, i, j: (b, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, 128), jnp.float32),  # m (lane-replicated col 0)
            pltpu.VMEM((blk_q, 128), jnp.float32),  # l
            pltpu.VMEM((blk_q, hd), jnp.float32),  # acc
        ],
        interpret=interpret,
    )(qf, kf, vf)
    out = out.reshape(B, H, S, hd)
    if with_lse:
        return out, lse  # (B*H, S, 8), lane-replicated
    return out


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref,
               dq_scr, *, blk_q: int, blk_k: int, causal: bool,
               sm_scale: float):
    """dQ: per (bh, q-block) program, k-blocks stream sequentially.
    Block probs are recomputed exactly from the saved row LSE (standard
    two-pass flash backward), so no (S, S) tensor exists anywhere:
        p  = exp(q k^T * scale - lse)
        ds = p * (dO v^T - delta)
        dq += ds @ k * scale
    """
    from jax.experimental import pallas as pl

    kb = pl.program_id(2)
    qi = pl.program_id(1)
    n_kb = pl.num_programs(2)
    q_off = qi * blk_q
    k_off = kb * blk_k

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr[...])

    live = (k_off <= q_off + blk_q - 1) if causal else (kb >= 0)

    @pl.when(live)
    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = dl_ref[0][:, :1]
        s = _nt(q, k) * sm_scale
        p = jnp.exp(s - lse)
        if causal:
            qpos = q_off + lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
            kpos = k_off + lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
            p = jnp.where(kpos <= qpos, p, 0.0)
        ds = p * (_nt(do, v) - delta)
        dq_scr[...] += jnp.dot(ds.astype(k.dtype), k,
                               preferred_element_type=jnp.float32) * sm_scale

    @pl.when(kb == n_kb - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dk_ref,
                dv_ref, dk_scr, dv_scr, *, blk_q: int, blk_k: int,
                causal: bool, sm_scale: float):
    """dK/dV: per (bh, k-block) program, q-blocks stream sequentially:
        p   = exp(q k^T * scale - lse)
        dv += p^T @ dO
        ds  = p * (dO v^T - delta)
        dk += ds^T @ q * scale
    """
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    kj = pl.program_id(1)
    n_qb = pl.num_programs(2)
    q_off = qi * blk_q
    k_off = kj * blk_k

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr[...])
        dv_scr[...] = jnp.zeros_like(dv_scr[...])

    live = (q_off + blk_q - 1 >= k_off) if causal else (qi >= 0)

    @pl.when(live)
    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = dl_ref[0][:, :1]
        s = _nt(q, k) * sm_scale
        p = jnp.exp(s - lse)
        if causal:
            qpos = q_off + lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
            kpos = k_off + lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
            p = jnp.where(kpos <= qpos, p, 0.0)
        # transposed in float32, then cast: Mosaic transposes 32-bit tiles
        dv_scr[...] += jnp.dot(p.T.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        ds = p * (_nt(do, v) - delta)
        dk_scr[...] += jnp.dot(ds.T.astype(q.dtype), q,
                               preferred_element_type=jnp.float32) * sm_scale

    @pl.when(qi == n_qb - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _q_index(blk_q, blk_k, causal, b, j, i):
    """dK/dV grid: clamp dead above-diagonal q-block fetches at the
    k-block's first live q-block (mirror of _kv_index)."""
    if not causal:
        return (b, i, 0)
    lo = (j * blk_k) // blk_q
    return (b, jnp.maximum(i, lo), 0)


def _q_index2(blk_q, blk_k, causal, b, j, i):
    if not causal:
        return (b, i, 0)
    lo = (j * blk_k) // blk_q
    return (b, jnp.maximum(i, lo), 0)


def _backward_kernels(q, k, v, o, lse, g, causal, sm_scale, blk_q, blk_k,
                      interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, hd = q.shape
    # delta = rowsum(dO * O): one fused elementwise+reduce pass, XLA's
    # job; 8-lane-replicated to match the LSE layout (see _finalize)
    delta = jnp.sum(
        g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )  # (B, H, S)
    qf = q.reshape(B * H, S, hd)
    kf = k.reshape(B * H, S, hd)
    vf = v.reshape(B * H, S, hd)
    gf = g.reshape(B * H, S, hd)
    lsef = lse  # (B*H, S, 8) straight from the forward kernel
    deltaf = jnp.broadcast_to(
        delta.reshape(B * H, S)[:, :, None], (B * H, S, 8)
    )

    q_spec = pl.BlockSpec((1, blk_q, hd), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec(
        (1, blk_k, hd), functools.partial(_kv_index, blk_q, blk_k, causal)
    )
    row_spec = pl.BlockSpec((1, blk_q, 8), lambda b, i, j: (b, i, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, blk_q=blk_q, blk_k=blk_k,
                          causal=causal, sm_scale=sm_scale),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        grid=(B * H, S // blk_q, S // blk_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((blk_q, hd), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, gf, lsef, deltaf)

    qi_spec = pl.BlockSpec(
        (1, blk_q, hd), functools.partial(_q_index, blk_q, blk_k, causal)
    )
    row_i_spec = pl.BlockSpec(
        (1, blk_q, 8), functools.partial(_q_index2, blk_q, blk_k, causal)
    )
    kj_spec = pl.BlockSpec((1, blk_k, hd), lambda b, j, i: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, blk_q=blk_q, blk_k=blk_k,
                          causal=causal, sm_scale=sm_scale),
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, hd), k.dtype),
            jax.ShapeDtypeStruct((B * H, S, hd), v.dtype),
        ],
        grid=(B * H, S // blk_k, S // blk_q),
        in_specs=[qi_spec, kj_spec, kj_spec, qi_spec, row_i_spec, row_i_spec],
        out_specs=[kj_spec, kj_spec],
        scratch_shapes=[
            pltpu.VMEM((blk_k, hd), jnp.float32),
            pltpu.VMEM((blk_k, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, gf, lsef, deltaf)

    shape = (B, H, S, hd)
    return dq.reshape(shape), dk.reshape(shape), dv.reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = True, sm_scale: float = None,
                    blk_q: int = 512, blk_k: int = 512,
                    interpret: bool = False):
    """Fused causal attention for (B, H, S, hd) q/k/v; drop-in for the
    transformer's pluggable attention core:

        _block(x, layer, cfg, core=lambda q, k, v: flash_attention(q, k, v))

    Forward AND backward are Pallas kernels (two-pass flash backward:
    dq streams k-blocks, dk/dv stream q-blocks, block probs recomputed
    from the forward's saved row log-sum-exp). S must be a multiple of
    both block sizes (each clamped to S). q, k, v go to the MXU in the type
    they come in (bfloat16 in the model), accumulated in float32. On the
    v5e at (2, 16, 4096, 128) bfloat16 and 512 x 512 blocks, inside the
    layer scan under `value_and_grad`: 2.39 ms forward, 4.21 ms backward,
    31.7 % of the bf16 peak for the causal core's required operations
    (`flash_roofline_pct`, cell `olmoe_1b_7b.ssgd_seq4096_1chip`; PERF.md,
    PR 27). A dense core's float32 scores are 1.07 GB a sequence there.
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    return _forward(q, k, v, causal, sm_scale, blk_q, blk_k, interpret)


def _fwd(q, k, v, causal, sm_scale, blk_q, blk_k, interpret):
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    out, lse = _forward(
        q, k, v, causal, sm_scale, blk_q, blk_k, interpret, with_lse=True
    )
    return out, (q, k, v, out, lse)


def _bwd(causal, sm_scale, blk_q, blk_k, interpret, res, g):
    q, k, v, o, lse = res
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    # fused two-pass flash backward kernels (dq, then dk/dv)
    blk_q, blk_k = _blocks(q.shape[2], blk_q, blk_k)
    return _backward_kernels(
        q, k, v, o, lse, g, causal, sm_scale, blk_q, blk_k, interpret
    )


flash_attention.defvjp(_fwd, _bwd)
