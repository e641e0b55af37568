"""Pallas flash attention: fused causal self-attention for the MXU.

The hot op done as a TPU kernel (pallas_guide.md playbook): per (batch x
head, q-block) grid program, the q tile stays in VMEM while K/V stream
through block by block with an online (flash) softmax — the (S, S) score
matrix never materializes in HBM, so peak memory is O(BLK_Q x S_block)
instead of O(S^2). Causal programs stop at their diagonal block (the
upper-triangular half is never computed at all).

A grid step is one (q-block, k-block) pair, and in the forward kernel the
block's own offsets say which of three it is (`_interior`): **dead**, above
the diagonal or wholly behind the window, and skipped, fetch and all;
**interior**, every key of it seen by every query of it (120 of a head's 136
visited blocks at 8,192 positions and blocks of 512, `block_counts`),
computed with no mask at all; or **edge**, crossed by the diagonal or by the
window's far side (every block of a band core at window 512), computed under
`_mask`. The two bodies are one function, and give the same bits: on an
interior block the select is the identity and the product is by 1.0. The
two backward kernels mask every live block: they stand at their matmuls'
time and a second body measured nothing there. The forward kernel's running
statistics m and l live as whole (blk_q, 128) lane-replicated tiles and meet
the (blk_q, blk_k) scores as copies side by side (`_across`): read as column
0 and broadcast a step they, not the masks and not the MXU, were the largest
cost of a forward block (PERF.md, PR 42).

Differentiable via custom_vjp: the forward kernel also emits the per-row
log-sum-exp, and the backward runs two fused Pallas kernels (dq over
k-blocks; dk/dv over q-blocks) that recompute exact block probabilities
from it — the standard two-pass flash backward. Neither direction ever
materializes an (S, S) tensor. A sequence length the blocks do not
divide is an error, not a dense fallback. Between the passes every call
keeps q, k, v, its output and the log-sum-exp as one number a row, the last
two under the names `flash_out` and `flash_lse`: a `jax.checkpoint` around
the caller whose policy keeps those names runs everything else again and the
forward kernel once (`models/transformer._layer_again`; PERF.md, PR 45).

Two things a layer may ask beside the causal mask (ROADMAP D4, PR 33).
**Grouped heads**: k and v with fewer heads than q, H = g x Hkv; query head
h reads key/value head h // g through the index maps (no copy of k, v is
made), and the dK/dV kernel's grid runs over the key/value heads with the
g query heads of a group inside its sequential sweep, so dk, dv are summed
in VMEM and written once. **A window**: key j is seen by query i iff
0 <= i - j < window; every kernel's sweep then covers the blocks that
touch the band and no others (`_kv_steps`, `_q_steps`: 2 of 16 at blocks
of 512, window 512 and 8,192 positions). A call with neither runs the
kernels it ran before both.

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
from jax.ad_checkpoint import checkpoint_name

NEG_INF = -1e30


def _dense_reference(q, k, v, causal: bool, sm_scale: float, window=None):
    S = q.shape[2]
    g = q.shape[1] // k.shape[1]
    if g > 1:  # query head h reads key/value head h // g
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        behind = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
        mask = behind >= 0 if window is None else (behind >= 0) & (behind < window)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _nt(a, b):
    """a @ b.T with float32 accumulation, the operands in the type they
    came in: bfloat16 q/k/v go to the MXU as bfloat16 (an upcast to float32
    first costs the multi-pass float32 matmul), float32 ones stay float32."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _first_kv_block(blk_q: int, blk_k: int, window, i):
    """The first k-block that q-block i's rows see under a window: the
    block of key i * blk_q - (window - 1), row 0's oldest."""
    return jnp.maximum(i * blk_q - (window - 1), 0) // blk_k


def _last_q_block(blk_q: int, blk_k: int, window, S: int, j):
    """The last q-block that sees k-block j: the block of query
    j * blk_k + blk_k - 1 + window - 1, the newest key's last reader."""
    return jnp.minimum(j * blk_k + blk_k + window - 2, S - 1) // blk_q


def _kv_steps(S: int, blk_q: int, blk_k: int, window) -> int:
    """The length of a q-block's sweep over k-blocks: all of them without a
    window, and with one the most that any q-block's band touches, first
    live block to diagonal."""
    if window is None:
        return S // blk_k
    return max((r + blk_q - 1) // blk_k - max(r - (window - 1), 0) // blk_k + 1
               for r in range(0, S, blk_q))


def _q_steps(S: int, blk_q: int, blk_k: int, window) -> int:
    """The length of a k-block's sweep over q-blocks, as `_kv_steps`:
    diagonal to last reader."""
    if window is None:
        return S // blk_q
    return max(min(c + blk_k + window - 2, S - 1) // blk_q - c // blk_q + 1
               for c in range(0, S, blk_k))


def _interior(q_off, k_off, blk_q: int, blk_k: int, window, docs=None):
    """Whether every key of the block at (q_off, k_off) is seen by every
    query of it: the newest key is no later than the oldest query, under a
    window the oldest key is inside the newest query's, and in a packed
    sequence (`docs`, the block's `_documents`) the oldest key is of the
    newest query's document: the numbers never fall, so every position
    between them is of it too. `_mask` is all true there and the forward
    kernel leaves it out. Offsets traced or plain."""
    inside = k_off + blk_k - 1 <= q_off
    if window is not None:
        inside &= q_off + blk_q - 1 - k_off < window
    if docs is not None:
        of_query, of_key = docs
        inside &= jnp.max(of_query) == jnp.min(of_key)
    return inside


def block_counts(S: int, blk_q: int, blk_k: int, window=None):
    """(visited, edge) blocks a head of a causal core: the live blocks of a
    sweep, and those of them that a mask's edge crosses, the only ones the
    forward kernel masks; the rest are interior. The forward/dQ sweep and the dK/dV sweep
    visit the same blocks. 136 and 16 at 8,192 positions and blocks of 512,
    528 and 32 at 16,384, 36 and 8 at 4,096; under window 512 every visited
    block is an edge block."""
    visited = edge = 0
    for q_off in range(0, S, blk_q):
        for k_off in range(0, S, blk_k):
            if k_off > q_off + blk_q - 1 or (
                    window is not None and q_off - (k_off + blk_k - 1) >= window):
                continue  # dead: above the diagonal, or wholly behind the band
            visited += 1
            edge += not _interior(q_off, k_off, blk_q, blk_k, window)
    return visited, edge


def _count_blocks(kernels, heads: int, S, blk_q, blk_k, causal, window,
                  interior_unmasked: bool):
    """At trace time, what a run of each of the `kernels` being built visits
    and masks over its heads, added to the counters
    `kungfu_flash_blocks_visited_total` and `kungfu_flash_blocks_masked_total`
    (docs/telemetry.md): a sum over the kernels traced, not over their runs."""
    from kungfu_tpu.telemetry import metrics

    if causal:
        visited, edge = block_counts(S, blk_q, blk_k, window)
        masked = edge if interior_unmasked else visited
    else:
        visited, masked = (S // blk_q) * (S // blk_k), 0
    for name, text, blocks in (
            ("kungfu_flash_blocks_visited_total",
             "blocks a run of each flash kernel traced so far visits, by kernel",
             visited),
            ("kungfu_flash_blocks_masked_total",
             "those of them computed under the mask", masked)):
        family = metrics.counter(name, text, ("kernel",))
        for kernel in kernels:
            family.labels(kernel).inc(heads * blocks)


def _across(stat, n: int):
    """A (rows, 128) lane-replicated row statistic, m, l or a correction, as
    wide as the (rows, n) tile it meets: itself at 128 lanes, whole copies
    side by side at a multiple of them (no lane is moved), and column 0 to
    broadcast at any other width (the tests' small blocks and heads)."""
    from jax.experimental.pallas import tpu as pltpu

    if n % stat.shape[-1]:
        return stat[:, :1]
    return stat if n == stat.shape[-1] else pltpu.repeat(stat, n // stat.shape[-1], 1)


def _mask(q_off, k_off, blk_q: int, blk_k: int, window, docs=None):
    """(blk_q, blk_k) bool: key seen by query: causal, inside the window,
    and in a packed sequence of the query's own document (`docs`)."""
    qpos = q_off + lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    kpos = k_off + lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    seen = kpos <= qpos
    if window is not None:
        seen &= qpos - kpos < window
    if docs is not None:
        of_query, of_key = docs
        seen &= of_query == of_key
    return seen


def _documents(of_query_ref, of_key_ref):
    """A block's documents in a packed sequence: the queries' numbers as a
    column (blk_q, 1) and the keys' as a row (1, blk_k), from the lane- and
    sublane-replicated blocks the kernels are handed (`_packed`)."""
    return of_query_ref[0][:, :1], of_key_ref[0][:1, :]


def _kernel(q_ref, k_ref, v_ref, *refs, blk_q: int, blk_k: int, causal: bool,
            sm_scale: float, window=None):
    """One (bh, q-block, k-block) grid program. The TPU grid runs the
    LAST dimension sequentially on one core, so the (m, l, acc) flash
    accumulators live in VMEM scratch across the k-block sweep; K/V
    arrive one block at a time via BlockSpec streaming — VMEM holds
    O(blk) state regardless of S. Under a window the sweep starts at the
    q-block's first live k-block. `refs`: the two blocks of a packed
    sequence's document numbers first, where there are any (`_packed`), then
    o, the log-sum-exp and the scratch."""
    from jax.experimental import pallas as pl

    *packed, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    docs = _documents(*packed) if packed else None
    step = pl.program_id(2)
    qi = pl.program_id(1)
    n_kb = pl.num_programs(2)
    kb = step if window is None else step + _first_kv_block(blk_q, blk_k, window, qi)
    q_off = qi * blk_q
    k_off = kb * blk_k

    @pl.when(step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    # causal: blocks fully above the diagonal contribute nothing
    live = (k_off <= q_off + blk_q - 1) if causal else (kb >= 0)

    def _compute(masked: bool):
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = _nt(q, k) * sm_scale
        if masked:
            mask = _mask(q_off, k_off, blk_q, blk_k, window, docs)
            s = jnp.where(mask, s, NEG_INF)
        m = m_scr[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _across(m_new, blk_k))
        if masked:  # a row with no key in this block nor before it: s - m is 0
            p = p * mask.astype(jnp.float32)
        corr = jnp.exp(m - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * _across(corr, acc_scr.shape[-1]) + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    # one of the two runs: under the mask where an edge crosses the block,
    # without where it is interior (every block of a call that is not causal)
    interior = (_interior(q_off, k_off, blk_q, blk_k, window, docs)
                if causal else True)
    pl.when(live & interior)(lambda: _compute(False))
    if causal:
        pl.when(live & jnp.logical_not(interior))(lambda: _compute(True))

    @pl.when(step == n_kb - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / _across(l, acc_scr.shape[-1])).astype(o_ref.dtype)
        # log-sum-exp per row: the backward recomputes exact block probs
        # as exp(s - lse) without re-running the online max/sum recurrence.
        # Stored 8-lane-replicated: Mosaic wants the last block dim ==
        # the array dim (8) and the stats are sublane-oriented anyway,
        # so this layout round-trips with zero relayouts.
        lse_ref[0] = (m_scr[...] + jnp.log(l))[:, :lse_ref.shape[-1]]


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


def _group(q, k, v, causal: bool, window, segments=None) -> int:
    """Query heads to a key/value head; raises for shapes and masks the
    kernels do not run."""
    if segments is not None and not (
            causal and segments.shape == q.shape[:1] + q.shape[2:3]):
        raise ValueError(
            f"flash_attention: segments {segments.shape} number the documents "
            f"of a causal core's positions, (B, S) = {q.shape[:1] + q.shape[2:3]}")
    H, Hkv = q.shape[1], k.shape[1]
    if k.shape != v.shape or H % Hkv or (
            k.shape[:1] + k.shape[2:] != q.shape[:1] + q.shape[2:]):
        raise ValueError(
            f"flash_attention: q {q.shape} against k {k.shape}, v {v.shape}: "
            "k and v share a shape, and q's heads are a multiple of theirs")
    if window is not None and not (causal and window >= 1):
        raise ValueError("flash_attention: a window is causal (0 <= i - j < "
                         f"window) and at least 1, got {window}")
    return H // Hkv


def _packed(segments):
    """What the kernels read of a packed sequence, or nothing: the
    documents' numbers (B, S) as the queries', (B, S, 8) with every lane a
    copy, and as the keys', (B, 8, S) with every sublane one, so that a block
    of either is a tile Mosaic takes and their comparison needs no
    transposition (`_documents`)."""
    if segments is None:
        return ()
    B, S = segments.shape
    numbers = segments.astype(jnp.int32)
    return (jnp.broadcast_to(numbers[:, :, None], (B, S, 8)),
            jnp.broadcast_to(numbers[:, None, :], (B, 8, S)))


def _packed_specs(segments, blk_q: int, blk_k: int, of_query, of_key):
    """The block specs of `_packed`'s two arrays, or none: `of_query` and
    `of_key` give a grid step's (batch, block) of each."""
    from jax.experimental import pallas as pl

    if segments is None:
        return []

    def queries(*at):
        b, i = of_query(*at)
        return (b, i, 0)

    def keys(*at):
        b, j = of_key(*at)
        return (b, 0, j)

    return [pl.BlockSpec((1, blk_q, 8), queries),
            pl.BlockSpec((1, 8, blk_k), keys)]


def _kv_head(g: int, b):
    """Row b of q's (B * H) leading axis reads row b // g of k's and v's
    (B * Hkv): H = g * Hkv, so the batch index carries over."""
    return b if g == 1 else lax.div(b, g)


def _kv_index(blk_q, blk_k, causal, window, g, b, i, j):
    """Step j of q-block i's sweep: its k-block, clamped at the diagonal so
    that a dead step repeats the last live index and Pallas skips the
    fetch (`pl.when` already skips the compute)."""
    if not causal:
        return (_kv_head(g, b), j, 0)
    diag = (i * blk_q + blk_q - 1) // blk_k  # last live k-block for q-block i
    j = j if window is None else j + _first_kv_block(blk_q, blk_k, window, i)
    return (_kv_head(g, b), jnp.minimum(j, diag), 0)


def _forward(q, k, v, causal: bool, sm_scale: float, blk_q: int,
             blk_k: int, interpret, with_lse: bool = False, window=None,
             segments=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, hd = q.shape
    g = _group(q, k, v, causal, window, segments)
    blk_q, blk_k = _blocks(S, blk_q, blk_k)
    qf = q.reshape(B * H, S, hd)
    kf = k.reshape(B * H // g, S, hd)
    vf = v.reshape(B * H // g, S, hd)
    kv_index = functools.partial(_kv_index, blk_q, blk_k, causal, window, g)
    _count_blocks(("forward",), B * H, S, blk_q, blk_k, causal, window, True)
    out, lse = pl.pallas_call(
        functools.partial(_kernel, blk_q=blk_q, blk_k=blk_k, causal=causal,
                          sm_scale=sm_scale, window=window),
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
            jax.ShapeDtypeStruct((B * H, S, 8), jnp.float32),
        ],
        grid=(B * H, S // blk_q, _kv_steps(S, blk_q, blk_k, window)),
        in_specs=[
            pl.BlockSpec((1, blk_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, hd), kv_index),
            pl.BlockSpec((1, blk_k, hd), kv_index),
        ] + _packed_specs(segments, blk_q, blk_k,
                          lambda b, i, j: (b // H, i),
                          lambda b, i, j: (b // H, kv_index(b, i, j)[1])),
        out_specs=[
            pl.BlockSpec((1, blk_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_q, 8), lambda b, i, j: (b, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, 128), jnp.float32),  # m, every lane a copy
            pltpu.VMEM((blk_q, 128), jnp.float32),  # l
            pltpu.VMEM((blk_q, hd), jnp.float32),  # acc
        ],
        interpret=interpret,
    )(qf, kf, vf, *_packed(segments))
    out = out.reshape(B, H, S, hd)
    if with_lse:
        return out, lse  # (B*H, S, 8), lane-replicated
    return out


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, *refs,
               blk_q: int, blk_k: int, causal: bool, sm_scale: float,
               window=None):
    """dQ: per (bh, q-block) program, k-blocks stream sequentially.
    Block probs are recomputed exactly from the saved row LSE (standard
    two-pass flash backward), so no (S, S) tensor exists anywhere:
        p  = exp(q k^T * scale - lse)
        ds = p * (dO v^T - delta)
        dq += ds @ k * scale
    `refs`: a packed sequence's document numbers first (`_packed`), then dq
    and the scratch.
    """
    from jax.experimental import pallas as pl

    *packed, dq_ref, dq_scr = refs
    docs = _documents(*packed) if packed else None
    step = pl.program_id(2)
    qi = pl.program_id(1)
    n_kb = pl.num_programs(2)
    kb = step if window is None else step + _first_kv_block(blk_q, blk_k, window, qi)
    q_off = qi * blk_q
    k_off = kb * blk_k

    @pl.when(step == 0)
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
            p = jnp.where(_mask(q_off, k_off, blk_q, blk_k, window, docs), p, 0.0)
        ds = p * (_nt(do, v) - delta)
        dq_scr[...] += jnp.dot(ds.astype(k.dtype), k,
                               preferred_element_type=jnp.float32) * sm_scale

    @pl.when(step == n_kb - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, *refs,
                blk_q: int, blk_k: int, causal: bool, sm_scale: float,
                window=None, n_qb=None, seq=None):
    """dK/dV: per (key/value head, k-block) program, the q-blocks of each
    of the group's query heads stream sequentially (`n_qb` steps a head,
    all of the sweep where the heads are not grouped), so a group's dk
    and dv are summed in the scratch and written once:
        p   = exp(q k^T * scale - lse)
        dv += p^T @ dO
        ds  = p * (dO v^T - delta)
        dk += ds^T @ q * scale
    `refs`: a packed sequence's document numbers first (`_packed`), then dk,
    dv and the scratch.
    """
    from jax.experimental import pallas as pl

    *packed, dk_ref, dv_ref, dk_scr, dv_scr = refs
    docs = _documents(*packed) if packed else None
    step = pl.program_id(2)
    kj = pl.program_id(1)
    n_steps = pl.num_programs(2)
    qi = step if n_qb is None else lax.rem(step, n_qb)
    if window is not None:
        qi = qi + (kj * blk_k) // blk_q  # the k-block's first live q-block
    q_off = qi * blk_q
    k_off = kj * blk_k

    @pl.when(step == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr[...])
        dv_scr[...] = jnp.zeros_like(dv_scr[...])

    if window is not None:  # from the diagonal, as far as the last reader
        live = qi <= _last_q_block(blk_q, blk_k, window, seq, kj)
    else:
        live = (q_off + blk_q - 1 >= k_off) if causal else (qi >= 0)

    @pl.when(live)
    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = dl_ref[0][:, :1]
        s = _nt(q, k) * sm_scale
        p = jnp.exp(s - lse)
        if causal:
            p = jnp.where(_mask(q_off, k_off, blk_q, blk_k, window, docs), p, 0.0)
        # transposed in float32, then cast: Mosaic transposes 32-bit tiles
        dv_scr[...] += jnp.dot(p.T.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        ds = p * (_nt(do, v) - delta)
        dk_scr[...] += jnp.dot(ds.T.astype(q.dtype), q,
                               preferred_element_type=jnp.float32) * sm_scale

    @pl.when(step == n_steps - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _q_index(blk_q, blk_k, causal, window, g, n_qb, S, b, j, i):
    """dK/dV grid, step i of k-block j's sweep: which query head of the
    group (step // n_qb) and which of its q-blocks. Dead fetches are
    clamped: above the diagonal at the k-block's first live q-block
    (mirror of _kv_index), past the window at its last."""
    if g > 1:
        b, i = b * g + lax.div(i, n_qb), lax.rem(i, n_qb)
    if not causal:
        return (b, i, 0)
    lo = (j * blk_k) // blk_q
    if window is None:
        return (b, jnp.maximum(i, lo), 0)
    return (b, jnp.minimum(i + lo, _last_q_block(blk_q, blk_k, window, S, j)), 0)


def _backward_kernels(q, k, v, o, lse, g, causal, sm_scale, blk_q, blk_k,
                      interpret, window=None, segments=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, hd = q.shape
    group = _group(q, k, v, causal, window, segments)
    Hkv = H // group
    # delta = rowsum(dO * O): one fused elementwise+reduce pass, XLA's
    # job; 8-lane-replicated to match the LSE layout (see _finalize)
    delta = jnp.sum(
        g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )  # (B, H, S)
    qf = q.reshape(B * H, S, hd)
    kf = k.reshape(B * Hkv, S, hd)
    vf = v.reshape(B * Hkv, S, hd)
    gf = g.reshape(B * H, S, hd)
    lsef = lse  # (B*H, S, 8), as the forward kernel writes it
    deltaf = jnp.broadcast_to(
        delta.reshape(B * H, S)[:, :, None], (B * H, S, 8)
    )

    q_spec = pl.BlockSpec((1, blk_q, hd), lambda b, i, j: (b, i, 0))
    kv_index = functools.partial(_kv_index, blk_q, blk_k, causal, window, group)
    kv_spec = pl.BlockSpec((1, blk_k, hd), kv_index)
    packed = _packed(segments)
    row_spec = pl.BlockSpec((1, blk_q, 8), lambda b, i, j: (b, i, 0))
    _count_blocks(("dq", "dkv"), B * H, S, blk_q, blk_k, causal, window, False)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, blk_q=blk_q, blk_k=blk_k,
                          causal=causal, sm_scale=sm_scale, window=window),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        grid=(B * H, S // blk_q, _kv_steps(S, blk_q, blk_k, window)),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec]
        + _packed_specs(segments, blk_q, blk_k,
                        lambda b, i, j: (b // H, i),
                        lambda b, i, j: (b // H, kv_index(b, i, j)[1])),
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((blk_q, hd), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, gf, lsef, deltaf, *packed)

    # one sweep over a k-block's q-blocks for each query head of the group
    n_qb = _q_steps(S, blk_q, blk_k, window)
    q_index = functools.partial(_q_index, blk_q, blk_k, causal, window,
                                group, n_qb, S)
    qi_spec = pl.BlockSpec((1, blk_q, hd), q_index)
    row_i_spec = pl.BlockSpec((1, blk_q, 8), q_index)
    kj_spec = pl.BlockSpec((1, blk_k, hd), lambda b, j, i: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, blk_q=blk_q, blk_k=blk_k,
                          causal=causal, sm_scale=sm_scale, window=window,
                          n_qb=n_qb if group > 1 else None, seq=S),
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, S, hd), k.dtype),
            jax.ShapeDtypeStruct((B * Hkv, S, hd), v.dtype),
        ],
        grid=(B * Hkv, S // blk_k, group * n_qb),
        in_specs=[qi_spec, kj_spec, kj_spec, qi_spec, row_i_spec, row_i_spec]
        + _packed_specs(segments, blk_q, blk_k,
                        lambda b, j, i: (q_index(b, j, i)[0] // H,
                                         q_index(b, j, i)[1]),
                        lambda b, j, i: (b // Hkv, j)),
        out_specs=[kj_spec, kj_spec],
        scratch_shapes=[
            pltpu.VMEM((blk_k, hd), jnp.float32),
            pltpu.VMEM((blk_k, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, gf, lsef, deltaf, *packed)

    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal: bool = True, sm_scale: float = None,
                    blk_q: int = 512, blk_k: int = 512,
                    interpret: bool = False, window: int = None,
                    segments=None):
    """Fused causal attention for (B, H, S, hd) q and (B, Hkv, S, hd) k, v,
    H a multiple of Hkv (equal: plain multi-head); drop-in for the
    transformer's pluggable attention core:

        _block(x, layer, cfg, core=lambda q, k, v: flash_attention(q, k, v))

    `window`: key j is seen by query i iff 0 <= i - j < window (None: every
    earlier key), and only the blocks of that band are visited.

    `segments` (B, S) whole numbers that never fall along the sequence
    number each position's document in a packed row: key j is seen by query
    i iff besides both are of one document, so a packed row is its documents
    run one at a time, values and gradients. The kernels mask: a block whose
    keys are all of earlier documents than its queries is visited and
    computed to nothing (ROADMAP R3(b) has what skipping them would save).

    Forward AND backward are Pallas kernels (two-pass flash backward:
    dq streams k-blocks, dk/dv stream q-blocks, block probs recomputed
    from the forward's saved row log-sum-exp). S must be a multiple of
    both block sizes (each clamped to S). q, k, v go to the MXU in the type
    they come in (bfloat16 in the model), accumulated in float32.

    On the v5e, bfloat16, 512 x 512 blocks, a run of each kernel inside the
    cells' steps (their traced runs, PERF.md, PR 42): ms, and ps a score of
    a visited block beside what the MXU needs for the kernel's 2, 3 and 4
    matmuls a score:

        (B, H on Hkv, S, hd)            forward        dQ             dK/dV
        (2, 16 on 16,  4096, 128)      1.39  4.6/2.6   2.06  6.8/3.9   2.22  7.4/5.2
        (1, 48 on 8,   8192, 128)      7.50  4.4/2.6  10.54  6.2/3.9  12.52  7.3/5.2
        (1, 72 on 8,   8192, 128) w512 2.98  5.1/2.6   3.42  5.8/3.9   4.14  7.1/5.2
        (1, 20 on 20,  8192, 256)      5.49  7.7/5.2   7.34 10.3/7.8   8.69 12.2/10.4
        (1, 16 on 2,  16384, 256)     15.49  7.0/5.2  21.02  9.5/7.8  26.77 12.1/10.4

    (`olmoe_1b_7b.ssgd_seq4096_1chip`; `laguna_s_2_1.ssgd_1seq_1chip`, a full
    and a window layer; `glm_4_7_flash.ssgd_mtp_8k_1chip`;
    `qwen3_next_80b_a3b.ssgd_longseq_1chip`). Timed alone, the forward kernel
    with nothing but its two matmuls in it takes 93 % of its time at head 128
    and 95 % at 256: what a live grid step takes over the MXU's time, 0.55
    to 0.9 us whatever the head size, is the step's own; the two backward
    kernels are at that floor too, and
    a dead step costs 0.19 us. A dense core's float32 scores are 1.07 GB a
    sequence of 4,096.
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    return _forward(q, k, v, causal, sm_scale, blk_q, blk_k, interpret,
                    window=window, segments=segments)


def _fwd(q, k, v, causal, sm_scale, blk_q, blk_k, interpret, window,
         segments=None):
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    out, lse = _forward(
        q, k, v, causal, sm_scale, blk_q, blk_k, interpret, with_lse=True,
        window=window, segments=segments
    )
    # kept between the passes as one number a row: the 8 replicated lanes
    # are padded to 128 in HBM, 302 MB a layer of 72 heads at 8,192
    # positions for 2.4 MB of numbers (`_bwd` lays them out again)
    lse = lse[:, :, 0]
    # names for a checkpoint around the caller that runs the layer again
    # and keeps these two, so that the forward kernel runs once
    # (`models/transformer._layer_again`); the identity anywhere else
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, out, lse, segments)


def _bwd(causal, sm_scale, blk_q, blk_k, interpret, window, res, g):
    q, k, v, o, lse, segments = res
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    lse = jnp.broadcast_to(lse[:, :, None], lse.shape + (8,))
    # fused two-pass flash backward kernels (dq, then dk/dv)
    blk_q, blk_k = _blocks(q.shape[2], blk_q, blk_k)
    return _backward_kernels(
        q, k, v, o, lse, g, causal, sm_scale, blk_q, blk_k, interpret,
        window=window, segments=segments
    ) + (None,)


flash_attention.defvjp(_fwd, _bwd)
