"""Pallas flash attention: fused causal self-attention for the MXU.

The hot op done as a TPU kernel (pallas_guide.md playbook): per (batch x
head, q-block) grid program, the q tile stays in VMEM while K/V stream
through block by block with an online (flash) softmax — the (S, S) score
matrix never materializes in HBM, so peak memory is O(BLK_Q x S_block)
instead of O(S^2). Causal programs stop at their diagonal block (the
upper-triangular half is never computed at all, nor stepped over).

A grid step is one (q-block, k-block) pair, and in the forward kernel the
block's own offsets say which of three it is (`_interior`): **dead**, no
query of it sees a key of it; **interior**, every key of it seen by every
query of it (120 of a head's 136 visited blocks at 8,192 positions and blocks
of 512, `block_counts`), computed with no mask at all; or **edge**, crossed
by the diagonal, by the window's far side (every block of a band core at
window 512) or by a document's boundary, computed under `_mask`. Dead names
three cases, and none runs a kernel's body (PR 53). A block **above the
diagonal** of a causal core without a window is no grid step at all: the
sweeps run over a table of their live pairs that the kernels and their index
maps read from scalar memory (`_live_pairs`: 136 steps a head at 8,192
positions where a square grid has 256, 528 of 1,024 at 16,384, 36 of 64 at
4,096). A block **wholly outside the band** of a windowed core is left out by
the sweep's length (`_kv_steps`, `_q_steps`), and what that leaves above the
diagonal in the first rows is a step that computes nothing and fetches
nothing (its index is clamped to the last live block's). A block **of other
documents** in a packed sequence, every key of an earlier document than every
query, is known by its data alone (`_meet`, on the blocks' oldest and newest
documents, which `_packed` lays into scalar memory): it takes its grid step,
runs no body in any of the three kernels and fetches nothing, its index
clamped to its sweep's nearest live block's. The two bodies are one function,
and give the same bits: on an
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
same kernels.

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

from kungfu_tpu.ops.kernel_call import kernel_call

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


def _by_table(causal: bool, window) -> bool:
    """Whether a call's sweeps run over a table of their live block pairs
    (`_live_pairs`): a causal core without a window, whose square grid would
    hold a dead step for every block above the diagonal. A band's sweeps are
    as long as the band is wide already, and every pair of a core that is
    not causal is live."""
    return causal and window is None


def _live_pairs(S: int, blk_q: int, blk_k: int, group=None):
    """The grid of a causal sweep without a window, one step a live block
    pair, as the int32 table the kernels and their index maps read by scalar
    prefetch: the shapes' alone, numpy, a constant of the program. Forward
    and dQ (`group` None): rows (q-block, k-block), a q-block's k-blocks
    first to diagonal. dK/dV: rows (k-block, query head of the group,
    q-block), a k-block's q-blocks diagonal to last, once a query head of
    the `group`. Flat, row after row: one dimension is what scalar memory
    pads least."""
    import numpy as np

    n_q = S // blk_q
    if group is None:
        steps = [(i, j) for i in range(n_q)
                 for j in range((i * blk_q + blk_q - 1) // blk_k + 1)]
    else:
        steps = [(j, h, i) for j in range(S // blk_k) for h in range(group)
                 for i in range(j * blk_k // blk_q, n_q)]
    return np.asarray(steps, np.int32).T.reshape(-1)


def _row(table, r: int, rows: int, step):
    """Entry `step` of row r of a flat table of `rows` rows."""
    return table[r * (table.shape[0] // rows) + step]


def _interior(q_off, k_off, blk_q: int, blk_k: int, window, ends=None):
    """Whether every key of the block at (q_off, k_off) is seen by every
    query of it: the newest key is no later than the oldest query, under a
    window the oldest key is inside the newest query's, and in a packed
    sequence (`ends`, the block's `_ends`) the oldest key is of the
    newest query's document: the numbers never fall, so every position
    between them is of it too. `_mask` is all true there and the forward
    kernel leaves it out. Offsets traced or plain."""
    inside = k_off + blk_k - 1 <= q_off
    if window is not None:
        inside &= q_off + blk_q - 1 - k_off < window
    if ends is not None:
        _, newest_query, oldest_key, _ = ends
        inside &= newest_query == oldest_key
    return inside


def _meet(ends):
    """Whether a block of a packed sequence may hold a key of a query's
    document: its newest key's document is no earlier than its oldest
    query's (`ends`, the block's `_ends`). The numbers never fall along the
    sequence, so under the causal mask this is exact: a key before a query
    is of no later document, and where every key is of an earlier one
    `_mask` is false everywhere. Such a block is dead and runs no kernel's
    body."""
    oldest_query, _, _, newest_key = ends
    return newest_key >= oldest_query


def _ends(documents, q_off: int, k_off: int, blk_q: int, blk_k: int):
    """(oldest query's, newest query's, oldest key's, newest key's) document
    of the block at (q_off, k_off), from a row's numbers: what `_meet` and
    `_interior` go by, and what the kernels read as four scalars (`_packed`)."""
    return (documents[q_off], documents[q_off + blk_q - 1],
            documents[k_off], documents[k_off + blk_k - 1])


def block_counts(S: int, blk_q: int, blk_k: int, window=None, documents=None):
    """(visited, edge) blocks a head of a causal core: the live blocks of a
    sweep, and those of them that a mask's edge crosses, the only ones the
    forward kernel masks; the rest are interior. The forward/dQ sweep and the dK/dV sweep
    visit the same blocks. 136 and 16 at 8,192 positions and blocks of 512,
    528 and 32 at 16,384, 36 and 8 at 4,096; under window 512 every visited
    block is an edge block. `documents`, the numbers of a packed row's S
    positions (numpy), leaves the blocks of other documents out of the
    visited (`_meet`) and counts a block that a boundary crosses as an edge
    block: what the kernels do for that row, which their traced program
    cannot know."""
    visited = edge = 0
    for q_off in range(0, S, blk_q):
        for k_off in range(0, S, blk_k):
            ends = None if documents is None else _ends(
                documents, q_off, k_off, blk_q, blk_k)
            if k_off > q_off + blk_q - 1 or (
                    window is not None and q_off - (k_off + blk_k - 1) >= window):
                continue  # dead: above the diagonal, or wholly behind the band
            if ends is not None and not _meet(ends):
                continue  # dead: every key of an earlier document than every query
            visited += 1
            edge += not _interior(q_off, k_off, blk_q, blk_k, window, ends)
    return visited, edge


def grid_steps(S: int, blk_q: int, blk_k: int, causal: bool, window=None):
    """Grid steps a head of each kernel's sweep, dead ones included: the
    live pairs alone of a causal core without a window (`_live_pairs`:
    `block_counts`' visited, 136 at 8,192 positions and blocks of 512 where
    the square grid had 256), the band's width a row of a windowed one
    (`_kv_steps`, `_q_steps`), every pair of a core that is not causal.
    (forward and dQ, dK/dV)."""
    if _by_table(causal, window):  # both tables list the same pairs
        return (_live_pairs(S, blk_q, blk_k).size // 2,) * 2
    return ((S // blk_q) * _kv_steps(S, blk_q, blk_k, window),
            (S // blk_k) * _q_steps(S, blk_q, blk_k, window))


def _count_blocks(kernels, heads: int, S, blk_q, blk_k, causal, window,
                  interior_unmasked: bool):
    """At trace time, what a run of each of the `kernels` being built visits
    and masks over its heads and how many grid steps it takes to, added to
    the counters `kungfu_flash_blocks_visited_total`,
    `kungfu_flash_blocks_masked_total` and `kungfu_flash_grid_steps_total`
    (docs/telemetry.md): a sum over the kernels traced, not over their runs.
    What is known at trace time: a packed row's blocks of other documents
    count as visited here and are skipped by its data."""
    from kungfu_tpu.telemetry import metrics

    if causal:
        visited, edge = block_counts(S, blk_q, blk_k, window)
        masked = edge if interior_unmasked else visited
    else:
        visited, masked = (S // blk_q) * (S // blk_k), 0
    by_q, by_k = grid_steps(S, blk_q, blk_k, causal, window)
    for kernel in kernels:
        for name, text, n in (
                ("kungfu_flash_blocks_visited_total",
                 "blocks a run of each flash kernel traced so far visits, by kernel",
                 visited),
                ("kungfu_flash_blocks_masked_total",
                 "those of them computed under the mask", masked),
                ("kungfu_flash_grid_steps_total",
                 "grid steps it takes to visit them, the dead ones among them",
                 by_k if kernel == "dkv" else by_q)):
            metrics.counter(name, text, ("kernel",)).labels(kernel).inc(heads * n)


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


# `_packed`'s bounds, three a q-block and three a k-block of a row
OLDEST_Q, NEWEST_Q, FIRST_LIVE_K, OLDEST_K, NEWEST_K, LAST_LIVE_Q = range(6)


def _bound(bounds, S: int, blk_q: int, blk_k: int, batch, which: int, block):
    """Entry `which` of `_packed`'s bounds for a `block` of row `batch`: a
    read of scalar memory, in a kernel or an index map."""
    n_q, n_k = S // blk_q, S // blk_k
    start = which * n_q if which < OLDEST_K else 3 * n_q + (which - OLDEST_K) * n_k
    return bounds[batch * 3 * (n_q + n_k) + start + block]


def _block_ends(bounds, packed, blk_q: int, blk_k: int, qi, kb):
    """Inside a kernel: a packed sequence's `_ends` of the block pair (qi,
    kb), four scalars of `_packed`'s bounds; `packed`: (the grid's rows a
    batch row, S). A step that is dead by shape may name a block past the
    last: it reads the last one's, and is dead whatever they say."""
    from jax.experimental import pallas as pl

    rows, S = packed
    batch = pl.program_id(0) // rows
    qi, kb = jnp.minimum(qi, S // blk_q - 1), jnp.minimum(kb, S // blk_k - 1)
    return tuple(_bound(bounds, S, blk_q, blk_k, batch, which,
                        qi if which < OLDEST_K else kb)
                 for which in (OLDEST_Q, NEWEST_Q, OLDEST_K, NEWEST_K))


def _live_kv(q_off, k_off, kb, blk_q: int, causal: bool, window, ends):
    """Whether the forward and the dQ kernel compute this grid step: under
    the diagonal (every step of a table is; a square grid's upper half and a
    band's last steps of its first rows are not), and in a packed sequence
    not a block of other documents (`_meet`). Behind the band no step
    falls: a band's sweep starts at its first live block."""
    if _by_table(causal, window):
        live = True
    else:  # causal: blocks fully above the diagonal contribute nothing
        live = (k_off <= q_off + blk_q - 1) if causal else (kb >= 0)
    if ends is not None:
        live &= _meet(ends)
    return live


def _kv_sweep_step(refs, blk_q: int, blk_k: int, causal: bool, window, packed):
    """Inside the forward and the dQ kernel: the grid step's q-block and
    k-block, where it stands in the q-block's sweep and how long that is,
    the block's documents' `_ends` (None: one document a row), and the
    kernel's other refs. By the table (`_by_table`) the sweep is the row's
    live k-blocks, 0 to the diagonal; else the grid's last axis, under a
    window from the q-block's first live k-block. The refs in scalar memory
    come first: the table, then a packed sequence's bounds."""
    from jax.experimental import pallas as pl

    if _by_table(causal, window):
        table, *refs = refs
        pair = pl.program_id(1)
        qi, kb = _row(table, 0, 2, pair), _row(table, 1, 2, pair)
        step, n_kb = kb, (qi * blk_q + blk_q - 1) // blk_k + 1
    else:
        step = pl.program_id(2)
        qi = pl.program_id(1)
        n_kb = pl.num_programs(2)
        kb = step if window is None else step + _first_kv_block(blk_q, blk_k, window, qi)
    ends = None
    if packed:
        bounds, *refs = refs
        ends = _block_ends(bounds, packed, blk_q, blk_k, qi, kb)
    return qi, kb, step, n_kb, ends, refs


def _kernel(*refs, blk_q: int, blk_k: int, causal: bool, sm_scale: float,
            window=None, packed=None):
    """One (bh, q-block, k-block) grid program. The TPU grid runs the
    LAST dimension sequentially on one core, so the (m, l, acc) flash
    accumulators live in VMEM scratch across the k-block sweep; K/V
    arrive one block at a time via BlockSpec streaming — VMEM holds
    O(blk) state regardless of S. Under a window the sweep starts at the
    q-block's first live k-block. `refs`: what lies in scalar memory first
    (`_kv_sweep_step`), then q, k, v, then the two blocks of a packed
    sequence's document numbers, where there are any (`_packed`; `packed`
    says so: (heads a batch row, S)), then o, the log-sum-exp and the
    scratch."""
    from jax.experimental import pallas as pl

    qi, kb, step, n_kb, ends, refs = _kv_sweep_step(
        refs, blk_q, blk_k, causal, window, packed)
    q_ref, k_ref, v_ref, *numbers, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    docs = _documents(*numbers) if packed else None
    q_off = qi * blk_q
    k_off = kb * blk_k

    @pl.when(step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    live = _live_kv(q_off, k_off, kb, blk_q, causal, window, ends)

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
    interior = (_interior(q_off, k_off, blk_q, blk_k, window, ends)
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
    if k.shape[:-1] != v.shape[:-1] or H % Hkv or (
            k.shape[:1] + k.shape[2:] != q.shape[:1] + q.shape[2:]):
        raise ValueError(
            f"flash_attention: q {q.shape} against k {k.shape}, v {v.shape}: "
            "k and v share their heads and positions, k's features are q's, "
            "and q's heads are a multiple of theirs")
    if window is not None and not (causal and window >= 1):
        raise ValueError("flash_attention: a window is causal (0 <= i - j < "
                         f"window) and at least 1, got {window}")
    return H // Hkv


def _packed(segments, blk_q: int, blk_k: int):
    """What the kernels read of a packed sequence, or nothing:
    ([bounds], [the queries' numbers, the keys']). The numbers (B, S) as the
    queries', (B, S, 8) with every lane a copy, and as the keys', (B, 8, S)
    with every sublane one, so that a block of either is a tile Mosaic takes
    and their comparison needs no transposition (`_documents`): the mask's
    operands. The bounds, for scalar memory (`_bound`): a row after the
    other, every q-block's oldest and newest document and the first k-block
    that holds a key of its oldest document or a later one, then every
    k-block's oldest and newest document and the last q-block that holds a
    query of its newest document or an earlier one. By the two block
    numbers the index maps give a block of other documents (`_meet` false:
    k-blocks before the first, q-blocks behind the last) the index of its
    sweep's nearest live block, so that nothing is fetched for it; by the
    four documents the kernels know such a block, and an interior one,
    without a reduction over the tiles."""
    if segments is None:
        return [], []
    B, S = segments.shape
    numbers = segments.astype(jnp.int32)
    oldest_q, newest_q = numbers[:, ::blk_q], numbers[:, blk_q - 1::blk_q]
    oldest_k, newest_k = numbers[:, ::blk_k], numbers[:, blk_k - 1::blk_k]
    first_live_k = jnp.sum(newest_k[:, None, :] < oldest_q[:, :, None], axis=2)
    last_live_q = jnp.sum(oldest_q[:, None, :] <= newest_k[:, :, None], axis=2) - 1
    bounds = jnp.concatenate([oldest_q, newest_q, first_live_k,
                              oldest_k, newest_k, last_live_q], axis=1)
    return ([bounds.astype(jnp.int32).reshape(-1)],
            [jnp.broadcast_to(numbers[:, :, None], (B, S, 8)),
             jnp.broadcast_to(numbers[:, None, :], (B, 8, S))])


def _packed_specs(segments, blk_q: int, blk_k: int, of_query, of_key):
    """The block specs of `_packed`'s two arrays of numbers, or none:
    `of_query` and `of_key` give a grid step's (batch, block) of each."""
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


def _swept_for_index(rows: int, causal, window, b, *step):
    """The block a sweep runs for, at a grid step: the q-block of the forward
    and the dQ kernel, the k-block of the dK/dV kernel: the grid's second
    axis, or by the table (`_by_table`: step = (entry, table), `rows` rows)
    the entry's first number."""
    if _by_table(causal, window):
        at, table = step[:2]
        return (b, _row(table, 0, rows, at), 0)
    return (b, step[0], 0)


def _kv_index(blk_q, blk_k, causal, window, g, packed, b, *step):
    """Step j of q-block i's sweep: its k-block, clamped at the diagonal so
    that a dead step repeats the last live index and Pallas skips the
    fetch (`pl.when` already skips the compute). By the table (`_by_table`:
    step = (pair, table)) no step is dead by shape and the pair's second
    entry is the block. In a packed sequence (`packed`: (heads a batch row,
    S); the bounds are the step's last entry) a block of other documents
    takes the index of the q-block's first live one, which follows it."""
    if _by_table(causal, window):
        pair, table = step[:2]
        i, block = _row(table, 0, 2, pair), _row(table, 1, 2, pair)
        head = _kv_head(g, b)
    else:
        i, j = step[:2]
        if not causal:
            return (_kv_head(g, b), j, 0)
        diag = (i * blk_q + blk_q - 1) // blk_k  # last live k-block for q-block i
        j = j if window is None else j + _first_kv_block(blk_q, blk_k, window, i)
        head = _kv_head(g, b)
        block = jnp.minimum(j, diag)
    if packed:
        heads, S = packed
        block = jnp.maximum(block, _bound(step[-1], S, blk_q, blk_k, b // heads,
                                          FIRST_LIVE_K, i))
    return (head, block, 0)


def _kv_grid(rows: int, S, blk_q, blk_k, causal, window):
    """(grid, [table]) of the forward and the dQ kernel over `rows` heads:
    the live pairs a head and their table (`_by_table`), or q-blocks by
    k-blocks (the band's width under a window) and no table."""
    if _by_table(causal, window):
        table = _live_pairs(S, blk_q, blk_k)
        return (rows, table.size // 2), [table]
    return (rows, S // blk_q, _kv_steps(S, blk_q, blk_k, window)), []


def _sweep_call(kernel, grid, prefetched, **call):
    """`kernel_call(kernel, grid=grid, **call)`; where anything is
    `prefetched` (the table of live pairs, a packed sequence's bounds), the
    call with those as its first operands, in scalar memory before the grid
    runs: the kernel's first refs and every index map's last arguments."""
    from jax.experimental.pallas import tpu as pltpu

    if not prefetched:
        return kernel_call(kernel, grid=grid, **call)
    specs = {name: call.pop(name)
             for name in ("in_specs", "out_specs", "scratch_shapes")}
    return functools.partial(kernel_call(
        kernel, grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetched), grid=grid, **specs), **call),
        *prefetched)


def _forward(q, k, v, causal: bool, sm_scale: float, blk_q: int,
             blk_k: int, interpret, with_lse: bool = False, window=None,
             segments=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, hd = q.shape
    hd_v = v.shape[-1]  # the values' head size, and o's: q's and k's or its own
    g = _group(q, k, v, causal, window, segments)
    blk_q, blk_k = _blocks(S, blk_q, blk_k)
    qf = q.reshape(B * H, S, hd)
    kf = k.reshape(B * H // g, S, hd)
    vf = v.reshape(B * H // g, S, hd_v)
    packed = None if segments is None else (H, S)
    bounds, numbers = _packed(segments, blk_q, blk_k)
    q_index = functools.partial(_swept_for_index, 2, causal, window)
    kv_index = functools.partial(_kv_index, blk_q, blk_k, causal, window, g, packed)
    _count_blocks(("forward",), B * H, S, blk_q, blk_k, causal, window, True)
    grid, table = _kv_grid(B * H, S, blk_q, blk_k, causal, window)
    out, lse = _sweep_call(
        functools.partial(_kernel, blk_q=blk_q, blk_k=blk_k, causal=causal,
                          sm_scale=sm_scale, window=window, packed=packed),
        grid, table + bounds,
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, hd_v), q.dtype),
            jax.ShapeDtypeStruct((B * H, S, 8), jnp.float32),
        ],
        in_specs=[
            pl.BlockSpec((1, blk_q, hd), q_index),
            pl.BlockSpec((1, blk_k, hd), kv_index),
            pl.BlockSpec((1, blk_k, hd_v), kv_index),
        ] + _packed_specs(segments, blk_q, blk_k,
                          lambda b, *step: (b // H, q_index(b, *step)[1]),
                          lambda b, *step: (b // H, kv_index(b, *step)[1])),
        out_specs=[
            pl.BlockSpec((1, blk_q, hd_v), q_index),
            pl.BlockSpec((1, blk_q, 8), q_index),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, 128), jnp.float32),  # m, every lane a copy
            pltpu.VMEM((blk_q, 128), jnp.float32),  # l
            pltpu.VMEM((blk_q, hd_v), jnp.float32),  # acc
        ],
        interpret=interpret,
    )(qf, kf, vf, *numbers)
    out = out.reshape(B, H, S, hd_v)
    if with_lse:
        return out, lse  # (B*H, S, 8), lane-replicated
    return out


def _dq_kernel(*refs, blk_q: int, blk_k: int, causal: bool, sm_scale: float,
               window=None, packed=None):
    """dQ: per (bh, q-block) program, k-blocks stream sequentially.
    Block probs are recomputed exactly from the saved row LSE (standard
    two-pass flash backward), so no (S, S) tensor exists anywhere:
        p  = exp(q k^T * scale - lse)
        ds = p * (dO v^T - delta)
        dq += ds @ k * scale
    `refs`: what lies in scalar memory first (`_kv_sweep_step`), then q, k,
    v, dO, the log-sum-exp and delta, then a packed sequence's document
    numbers (`_packed`), then dq and the scratch.
    """
    from jax.experimental import pallas as pl

    qi, kb, step, n_kb, ends, refs = _kv_sweep_step(
        refs, blk_q, blk_k, causal, window, packed)
    q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, *numbers, dq_ref, dq_scr = refs
    docs = _documents(*numbers) if packed else None
    q_off = qi * blk_q
    k_off = kb * blk_k

    @pl.when(step == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr[...])

    live = _live_kv(q_off, k_off, kb, blk_q, causal, window, ends)

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


def _q_sweep_step(refs, blk_q: int, blk_k: int, causal: bool, window,
                  group: int, seq: int, packed):
    """Inside the dK/dV kernel: the grid step's k-block and q-block, where it
    stands in the k-block's sweep and how long that is, the block's
    documents' `_ends` (None: one document a row), and the kernel's other
    refs. By the table (`_by_table`) the sweep is the k-block's live
    q-blocks, diagonal to last, once a query head of the `group`; else the
    grid's last axis, `_q_steps` a head, from the k-block's first live
    q-block under a window. The refs in scalar memory come first: the
    table, then a packed sequence's bounds."""
    from jax.experimental import pallas as pl

    if _by_table(causal, window):
        table, *refs = refs
        at = pl.program_id(1)
        kj, head, qi = (_row(table, r, 3, at) for r in range(3))
        first = (kj * blk_k) // blk_q
        per_head = seq // blk_q - first
        step, n_steps = head * per_head + qi - first, group * per_head
    else:
        step = pl.program_id(2)
        kj = pl.program_id(1)
        n_steps = pl.num_programs(2)
        qi = step if group == 1 else lax.rem(
            step, _q_steps(seq, blk_q, blk_k, window))
        if window is not None:
            qi = qi + (kj * blk_k) // blk_q  # the k-block's first live q-block
    ends = None
    if packed:
        bounds, *refs = refs
        ends = _block_ends(bounds, packed, blk_q, blk_k, qi, kj)
    return kj, qi, step, n_steps, ends, refs


def _dkv_kernel(*refs, blk_q: int, blk_k: int, causal: bool, sm_scale: float,
                window=None, group=1, seq=None, packed=None):
    """dK/dV: per (key/value head, k-block) program, the q-blocks of each
    of the `group`'s query heads stream sequentially (a sweep a head, one
    after the other), so a group's dk
    and dv are summed in the scratch and written once:
        p   = exp(q k^T * scale - lse)
        dv += p^T @ dO
        ds  = p * (dO v^T - delta)
        dk += ds^T @ q * scale
    `refs`: what lies in scalar memory first (`_q_sweep_step`), then q, k,
    v, dO, the log-sum-exp and delta, then a packed sequence's document
    numbers (`_packed`; `packed`: (key/value heads a batch row, S)), then
    dk, dv and the scratch.
    """
    from jax.experimental import pallas as pl

    kj, qi, step, n_steps, ends, refs = _q_sweep_step(
        refs, blk_q, blk_k, causal, window, group, seq, packed)
    (q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, *numbers,
     dk_ref, dv_ref, dk_scr, dv_scr) = refs
    docs = _documents(*numbers) if packed else None
    q_off = qi * blk_q
    k_off = kj * blk_k

    @pl.when(step == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr[...])
        dv_scr[...] = jnp.zeros_like(dv_scr[...])

    if _by_table(causal, window):
        live = True  # every step of the table is under the diagonal
    elif window is not None:  # from the diagonal, as far as the last reader
        live = qi <= _last_q_block(blk_q, blk_k, window, seq, kj)
    else:
        live = (q_off + blk_q - 1 >= k_off) if causal else (qi >= 0)
    if ends is not None:
        live &= _meet(ends)

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


def _q_index(blk_q, blk_k, causal, window, g, n_qb, S, packed, b, *step):
    """dK/dV grid, step i of k-block j's sweep: which query head of the
    group (step // n_qb) and which of its q-blocks. Dead fetches are
    clamped: above the diagonal at the k-block's first live q-block
    (mirror of _kv_index), past the window at its last. By the table
    (`_by_table`: step = (entry, table)) no step is dead by shape, and the
    entry names the head and the q-block. In a packed sequence (`packed`:
    (key/value heads a batch row, S); the bounds are the step's last entry)
    a block of other documents takes the index of the k-block's last live
    one, which precedes it."""
    batch = b // packed[0] if packed else None
    if _by_table(causal, window):
        at, table = step[:2]
        j, b, i = (_row(table, 0, 3, at), b * g + _row(table, 1, 3, at),
                   _row(table, 2, 3, at))
    else:
        j, i = step[:2]
        if g > 1:
            b, i = b * g + lax.div(i, n_qb), lax.rem(i, n_qb)
        if not causal:
            return (b, i, 0)
        lo = (j * blk_k) // blk_q
        if window is None:
            i = jnp.maximum(i, lo)
        else:
            i = jnp.minimum(i + lo, _last_q_block(blk_q, blk_k, window, S, j))
    if packed:
        i = jnp.minimum(i, _bound(step[-1], S, blk_q, blk_k, batch, LAST_LIVE_Q, j))
    return (b, i, 0)


def _backward_kernels(q, k, v, o, lse, g, causal, sm_scale, blk_q, blk_k,
                      interpret, window=None, segments=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, hd = q.shape
    hd_v = v.shape[-1]  # of v, o, dO and dv
    group = _group(q, k, v, causal, window, segments)
    Hkv = H // group
    # delta = rowsum(dO * O): one fused elementwise+reduce pass, XLA's
    # job; 8-lane-replicated to match the LSE layout (see _finalize)
    delta = jnp.sum(
        g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )  # (B, H, S)
    qf = q.reshape(B * H, S, hd)
    kf = k.reshape(B * Hkv, S, hd)
    vf = v.reshape(B * Hkv, S, hd_v)
    gf = g.reshape(B * H, S, hd_v)
    lsef = lse  # (B*H, S, 8), as the forward kernel writes it
    deltaf = jnp.broadcast_to(
        delta.reshape(B * H, S)[:, :, None], (B * H, S, 8)
    )

    packed = None if segments is None else (H, S)
    bounds, numbers = _packed(segments, blk_q, blk_k)
    q_index = functools.partial(_swept_for_index, 2, causal, window)
    q_spec = pl.BlockSpec((1, blk_q, hd), q_index)
    kv_index = functools.partial(_kv_index, blk_q, blk_k, causal, window, group,
                                 packed)
    kv_spec = pl.BlockSpec((1, blk_k, hd), kv_index)
    v_spec = pl.BlockSpec((1, blk_k, hd_v), kv_index)
    do_spec = pl.BlockSpec((1, blk_q, hd_v), q_index)
    row_spec = pl.BlockSpec((1, blk_q, 8), q_index)
    _count_blocks(("dq", "dkv"), B * H, S, blk_q, blk_k, causal, window, False)

    grid, table = _kv_grid(B * H, S, blk_q, blk_k, causal, window)
    dq = _sweep_call(
        functools.partial(_dq_kernel, blk_q=blk_q, blk_k=blk_k, causal=causal,
                          sm_scale=sm_scale, window=window, packed=packed),
        grid, table + bounds,
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        in_specs=[q_spec, kv_spec, v_spec, do_spec, row_spec, row_spec]
        + _packed_specs(segments, blk_q, blk_k,
                        lambda b, *step: (b // H, q_index(b, *step)[1]),
                        lambda b, *step: (b // H, kv_index(b, *step)[1])),
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((blk_q, hd), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, gf, lsef, deltaf, *numbers)

    # one sweep over a k-block's q-blocks for each query head of the group
    n_qb = _q_steps(S, blk_q, blk_k, window)
    packed = None if segments is None else (Hkv, S)
    if _by_table(causal, window):
        table = [_live_pairs(S, blk_q, blk_k, group)]
        grid = (B * Hkv, table[0].size // 3)
    else:
        table, grid = [], (B * Hkv, S // blk_k, group * n_qb)
    q_index = functools.partial(_q_index, blk_q, blk_k, causal, window,
                                group, n_qb, S, packed)
    k_index = functools.partial(_swept_for_index, 3, causal, window)
    qi_spec = pl.BlockSpec((1, blk_q, hd), q_index)
    row_i_spec = pl.BlockSpec((1, blk_q, 8), q_index)
    kj_spec = pl.BlockSpec((1, blk_k, hd), k_index)
    vj_spec = pl.BlockSpec((1, blk_k, hd_v), k_index)
    doi_spec = pl.BlockSpec((1, blk_q, hd_v), q_index)
    dk, dv = _sweep_call(
        functools.partial(_dkv_kernel, blk_q=blk_q, blk_k=blk_k,
                          causal=causal, sm_scale=sm_scale, window=window,
                          group=group, seq=S, packed=packed),
        grid, table + bounds,
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, S, hd), k.dtype),
            jax.ShapeDtypeStruct((B * Hkv, S, hd_v), v.dtype),
        ],
        in_specs=[qi_spec, kj_spec, vj_spec, doi_spec, row_i_spec, row_i_spec]
        + _packed_specs(segments, blk_q, blk_k,
                        lambda b, *step: (q_index(b, *step)[0] // H,
                                          q_index(b, *step)[1]),
                        lambda b, *step: (b // Hkv, k_index(b, *step)[1])),
        out_specs=[kj_spec, vj_spec],
        scratch_shapes=[
            pltpu.VMEM((blk_k, hd), jnp.float32),
            pltpu.VMEM((blk_k, hd_v), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, gf, lsef, deltaf, *numbers)

    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal: bool = True, sm_scale: float = None,
                    blk_q: int = 512, blk_k: int = 512,
                    interpret: bool = False, window: int = None,
                    segments=None):
    """Fused causal attention for (B, H, S, hd) q, (B, Hkv, S, hd) k and (B,
    Hkv, S, hd_v) v, H a multiple of Hkv (equal: plain multi-head), -> (B, H,
    S, hd_v); the values' head size is q's and k's or its own (latent
    attention's 192 on 128: `hd` of q, k, dq, dk, `hd_v` of v, o, dO, dv and
    the forward kernel's accumulator; with the two equal every program is
    what it was); drop-in for the transformer's pluggable attention core:

        _block(x, layer, cfg, core=lambda q, k, v: flash_attention(q, k, v))

    `window`: key j is seen by query i iff 0 <= i - j < window (None: every
    earlier key), and only the blocks of that band are visited.

    `segments` (B, S) whole numbers that never fall along the sequence
    number each position's document in a packed row: key j is seen by query
    i iff besides both are of one document, so a packed row is its documents
    run one at a time, values and gradients. A block whose keys are all of
    earlier documents than its queries takes its grid step, runs no body,
    forward or backward, and fetches nothing (`_meet`, `_packed`): it added
    exact zeros, so the bits are those of computing it.

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
    kernels are at that floor too. A dense core's float32 scores are 1.07 GB
    a sequence of 4,096.

    Since PR 53 a causal sweep without a window holds no step above the
    diagonal. The kernels alone before and after (a probe's host clock over
    40 calls, some 5 % over the traces' readings; dQ and dK/dV with the row
    sums' pass; PERF.md, PR 53), ms:

        (B, H on Hkv, S, hd)          forward          dQ               dK/dV
        (1, 20 on 20,  8192, 256)     5.58 ->  4.98    7.97 ->  7.23    9.36 ->  9.27
        (1, 16 on 2,  16384, 256)    16.90 -> 15.47   21.84 -> 21.03   27.52 -> 27.41
        (1, 48 on 8,   8192, 128)     8.60 ->  7.77   11.57 -> 10.33   13.56 -> 13.51
        (1, 32 on 8,   8192, 64) *    9.90 ->  3.85    8.01 ->  3.69   10.15 ->  5.35

    (* a packed row with 46 of its 136 blocks a head live, the Granite cell's
    shape: 5.42, 5.10 and 7.05 with 82 live). A step dead by shape cost 0.10
    to 0.31 us where it followed its sweep's live steps (forward, dQ) and
    0.01 to 0.04 where it led them (dK/dV); a block of other documents still
    takes a step of 0.4 (forward, dQ) to 0.7 us (dK/dV), its index maps' and
    the pipeline's own.
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
