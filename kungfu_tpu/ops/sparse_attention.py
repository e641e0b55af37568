"""Learned sparse attention: an attention core over keys that the model
chooses (DeepSeek-V3.2-Exp's "DeepSeek Sparse Attention", PR 61).

Every core of `ops/flash_attention.py` sees a set of keys fixed by shape: all
earlier ones, a band, a document. Here a **lightning indexer** scores every
(query, key) pair, the k best-scored keys at or before a query are its
**choice**, the softmax **core** runs over the chosen keys alone, and the
indexer learns from the core it prunes: its loss is the KL divergence of its
own distribution over the chosen keys from the **head-mean of the core's
probabilities** there. Five pieces, each with its `plain` `jax.numpy` form
(the tests', and any shape the blocks do not tile):

- `index_scores(qI, kI, w)`: I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]),
  float32 from float32 products at the highest precision (a key's rank among
  8,192 is decided in the seventh digit: PERF.md, PR 61). One Pallas kernel
  forward over the live block pairs of the causal half (`_live_pairs`), the
  indexer's heads in a loop inside a grid step, so that no (heads, S, S) array
  exists; one backward, which makes the heads' scores again and writes dqI, dw
  and, a q-block at a time, the parts of dkI. **Only I[t, s <= t] is
  defined**: a block above the diagonal is never written, and every reader
  masks before it reads. **The float32 products are written out as their six
  bfloat16 terms** (PR 64): x = hi + mid + lo in bfloat16s (`_pieces`), and a
  product at the highest precision is hi.hi + mid.hi + hi.mid + hi.lo +
  mid.mid + lo.hi, each one pass of the MXU summed in float32; no term is
  left out (a bfloat16x3 product keeps the first three and is a lower
  precision than the indexer states). Left to the compiler each term is a
  pass of its own, and at the indexer's head of 64 each uses half of the
  128 x 128 array: q . k^T contracts over d (half its depth), ds . k and
  ds^T . q write d columns (half its width). Here the terms share passes.
  *The scores*: the pieces stand side by side along the contraction,
  [q_hi | q_mid | q_hi | q_hi | q_mid | q_lo] . [k_hi | k_hi | k_mid | k_lo |
  k_mid | k_hi]^T, one product 6 d deep (`_deep`), of which the array takes
  128 a pass: 128 // d terms share one, **three passes for six at d = 64**,
  one at the tests' d = 16, the compiler's own six at d = 128. *The two
  gradients* are made transposed, dq^T = k^T ds^T and dk^T = q^T ds: `ds`
  (a 512 x 512 tile, split once) stands in the array, by its own pieces, and
  the pieces of k^T or q^T, hi over mid over lo (`_stacked`), stream past it
  as rows, which the array pads to nothing: ds_hi meets all 3 d rows, ds_mid
  the first 2 d, ds_lo the first d, six terms for 6 d rows a tile, **three
  passes' worth for six at d = 64** where side by side along the d columns
  they would take four, and no `ds.T` is made. Nine half-array passes a head
  and block for eighteen, three forward for six (PERF.md, PR 64: 2.4 ms a
  layer forward for 4.6, 8.9 backward for 15.2).
- `select(I, k)`: for each query t the min(t + 1, k) keys s <= t with the
  largest I[t, s], a tie to the lower position, as one byte a pair (B, S, S)
  int8: 67 MB a layer at 8,192 positions (a bit a pair would be 8.4 MB and an
  unpacking in every kernel; I itself with a threshold a row 268 MB read by
  every head). Exact: the k-th largest of a row is found by a search over the
  float's bits, 32 counting passes, and a tie at that value is cut at a
  position found by log2(S) more. No sort, no `lax.approx_max_k` (a different
  choice, not a faster one). Nothing is differentiated through it. One Pallas
  kernel (PR 62): **a block of whole rows of I comes into VMEM once** and
  every pass runs there, where `plain_select`'s 45 passes each read I from
  HBM (15.1 ms a layer at 8,192 positions against the kernel's 1.4: PERF.md,
  PR 62). The block holds the rows' ordered integer form, the keys after a
  row's own position at a number no threshold reaches; a pass compares it a
  128-lane slice at a time with the rows' thresholds, adds the hits tile on
  tile and sums across the lanes once a row; the passes are loops inside the
  kernel, their turns `SELECT_SPAN` keys each and no more of them than reach
  the block's last row's own position (the causal half is half the work);
  the position passes run only in a block in which some row has more keys at
  its threshold than it wants. **The block's rows** are the largest multiple
  of 32 (an int8 tile) that divides S and whose float32 block is within
  `SELECT_BLOCK_BYTES`, 4 MB: 128 rows at 8,192 positions (32 rows took 2.0
  ms a layer, 64 1.6, 128 1.4); `plain_select` where no such block is or the
  lanes do not tile S.
- `sparse_attention(q, k, v, chosen)`: softmax(q k^T / sqrt(hd)) v over the
  chosen keys of each query, grouped heads as `flash_attention`'s. Forward, dQ
  and dK/dV are that file's kernels with the byte mask in the place of the
  causal comparison (the choice lies under the diagonal already), over the same
  table of live block pairs: a block pair in which nothing is chosen could be
  skipped, and with a choice a position none is (PERF.md section 7). It hands
  back the rows' log-sum-exp beside the output, for `head_mean_probs`; nothing
  is differentiated through that.
- `head_mean_probs(q, k, lse, chosen)`: p[t, s] = mean over the query heads of
  exp(q . k / sqrt(hd) - lse) at the chosen keys, (B, S, S) float32, and the
  rows' sum of p log p. One kernel, the heads innermost in its grid, so a
  block of p is summed in VMEM and written once: no (H, S, S) array exists.
  Defined where `chosen` is; the rest is never written.
- `indexer_kl(I, chosen, p, entropy)`: mean over the rows of sum over the
  chosen keys of p log p - p log_softmax(I), the softmax over the chosen keys
  alone. Plain `jax.numpy`: its derivative in I is (softmax - p) / rows.

The kernels compile with Mosaic unless the caller passes `interpret=True`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from kungfu_tpu.ops.flash_attention import (NEG_INF, _across, _blocks,
                                            _live_pairs, _nt, _row, _sweep_call)
from kungfu_tpu.ops.gated_delta import VMEM_LIMIT
from kungfu_tpu.ops.kernel_call import kernel_call

_HIGHEST = lax.Precision.HIGHEST


def _params(*semantics):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


def _last_kv_step(qi, blk_q: int, blk_k: int):
    """The last k-block of q-block qi's sweep: the diagonal's."""
    return (qi * blk_q + blk_q - 1) // blk_k


def _pair(table, at):
    """(q-block, k-block) of entry `at` of the forward table."""
    return _row(table, 0, 2, at), _row(table, 1, 2, at)


# -- the indexer's scores ---------------------------------------------------


def plain_index_scores(qI, kI, w):
    """I (B, S, S) of qI (B, S, Hi, d), kI (B, S, d), w (B, S, Hi), float32:
    every pair, those above the diagonal too."""
    s = jnp.einsum("btjd,bsd->bjts", qI, kI, precision=_HIGHEST)
    return jnp.einsum("btj,bjts->bts", w, jax.nn.relu(s), precision=_HIGHEST)


# A float32 product at the highest precision is six bfloat16 products of the
# operands' pieces (x = hi + mid + lo, each a bfloat16): of the nine, those
# whose order is 2^-16 of the product's or above. (left piece, right piece),
# 0 hi, 1 mid, 2 lo, in the order they stand side by side along a contraction:
_SIX_TERMS = ((0, 0), (1, 0), (0, 1), (0, 2), (1, 1), (2, 0))


def _pieces(x):
    """float32 x as three bfloat16s, hi + mid + lo = x to float32's last bit:
    each the nearest bfloat16 of what the ones before it left."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def _deep(x, side: int):
    """x (rows, d) float32 as (rows, 6 d) bfloat16: the pieces that side
    (0 left, 1 right) of the six terms takes, side by side along the
    contraction, so that left . right^T over 6 d is the float32 product over
    d: the array is 128 deep, so 128 // d terms share a pass of it."""
    pieces = _pieces(x)
    return jnp.concatenate([pieces[term[side]] for term in _SIX_TERMS], axis=-1)


def _stacked(x):
    """x (rows, n) float32 as (3 rows, n) bfloat16: hi over mid over lo."""
    return jnp.concatenate(_pieces(x), axis=0)


def _head_scores(q, k_deep):
    """q k^T of float32 q (blk_q, d) and a block of keys as `_deep(k, 1)`:
    the six terms in one contraction, 6 d deep."""
    return _nt(_deep(q, 0), k_deep)


def _index_kernel(table, q_ref, k_ref, w_ref, o_ref, *, heads: int):
    """A block of I: the heads' scores one after another, relu, times the
    head's weight a query, summed. q (1, heads, blk_q, d), k (1, blk_k, d),
    w (1, blk_q, heads). A head's scores are one bfloat16 product 6 d deep
    (`_deep`): at d = 64 three passes of the 128-deep array for the six
    64-deep ones of a float32 product, with no term left out."""
    k_deep, w = _deep(k_ref[0], 1), w_ref[0]
    acc = jnp.zeros(o_ref.shape[1:], jnp.float32)
    for j in range(heads):
        s = _head_scores(q_ref[0, j], k_deep)
        acc = acc + w[:, j:j + 1] * jnp.maximum(s, 0.0)
    o_ref[0] = acc


def _index_bwd_kernel(table, q_ref, k_ref, w_ref, di_ref, dq_ref, dw_ref,
                      dkp_ref, dq_scr, dw_scr, dk_scr, *, heads: int,
                      blk_q: int, blk_k: int):
    """The scores' gradients from dI, a q-block's sweep over its k-blocks:
    ds_j = dI w_j [s_j > 0]; dq_j += ds_j k and dw_j += sum_s dI relu(s_j)
    in scratch, written at the sweep's end; dk's part of this q-block, sum_j
    ds_j^T q_j, written a block pair (the caller adds the q-blocks up).

    Three float32 products a head and block, each as its six bfloat16 terms:
    s_j again as the forward kernel makes it (three 128-deep passes at d =
    64), and the two gradients **transposed**, dq_j^T = k^T ds_j^T and dk^T =
    sum_j q_j^T ds_j, so that `ds` (blk_q, blk_k), split into its pieces
    once, stands in the array as it is or transposed by the product itself
    (no `ds.T`), and what streams past it is the rows of k^T's or q_j^T's
    pieces, hi over mid over lo (`_stacked`): ds's hi meets all 3 d rows, its
    mid the first 2 d, its lo the first d. Rows are not padded to the
    array's 128, so the six terms cost 6 d rows a tile of `ds` where a
    product with d columns out costs 128 a term: at d = 64 nine half-array
    passes a head and block in all for the parent's eighteen. The three row
    groups of a transposed gradient are added up, and the sum transposed
    back, once a grid step (dk) or once a sweep (dq)."""
    from jax.experimental import pallas as pl

    qi, kb = _pair(table, pl.program_id(1))
    d = k_ref.shape[-1]

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr[...])
        dw_scr[...] = jnp.zeros_like(dw_scr[...])

    def folded(x):
        """(3 d, n) as the sum of its three row groups, transposed."""
        return (x[:d] + x[d:2 * d] + x[2 * d:]).T

    k, w, di = k_ref[0], w_ref[0], di_ref[0]
    k_deep, k_rows = _deep(k, 1), _stacked(k.T)
    lane = lax.broadcasted_iota(jnp.int32, dw_scr.shape, 1)
    dk_scr[...] = jnp.zeros_like(dk_scr[...])
    dw = jnp.zeros(dw_scr.shape, jnp.float32)
    for j in range(heads):
        q = q_ref[0, j]
        q_rows = _stacked(q.T)
        s = _head_scores(q, k_deep)
        live = s > 0.0
        dw = dw + jnp.where(lane == j, jnp.sum(
            jnp.where(live, di * s, 0.0), axis=-1, keepdims=True), 0.0)
        ds = jnp.where(live, di * w[:, j:j + 1], 0.0)
        # ds's piece i with the first 3 - i pieces of the other operand
        for i, piece in enumerate(_pieces(ds)):
            rows = (3 - i) * d
            dq_scr[j, :rows] += _nt(k_rows[:rows], piece)
            dk_scr[:rows] += jnp.dot(q_rows[:rows], piece,
                                     preferred_element_type=jnp.float32)
    dw_scr[...] += dw
    dkp_ref[0, 0] = folded(dk_scr[...])

    @pl.when(kb == _last_kv_step(qi, blk_q, blk_k))
    def _finalize():
        for j in range(heads):
            dq_ref[0, j] = folded(dq_scr[j])
        dw_ref[0] = dw_scr[...]


def _index_specs(Hi, d, blk_q, blk_k):
    from jax.experimental import pallas as pl

    return dict(
        q=pl.BlockSpec((1, Hi, blk_q, d),
                       lambda b, at, t: (b, 0, _pair(t, at)[0], 0)),
        k=pl.BlockSpec((1, blk_k, d), lambda b, at, t: (b, _pair(t, at)[1], 0)),
        w=pl.BlockSpec((1, blk_q, Hi), lambda b, at, t: (b, _pair(t, at)[0], 0)),
        pair=pl.BlockSpec((1, blk_q, blk_k), lambda b, at, t: (b, *_pair(t, at))))


def _index_forward(qh, kI, w, blk_q, blk_k, interpret):
    B, Hi, S, d = qh.shape
    table = _live_pairs(S, blk_q, blk_k)
    spec = _index_specs(Hi, d, blk_q, blk_k)
    return _sweep_call(
        functools.partial(_index_kernel, heads=Hi), (B, table.size // 2), [table],
        out_shape=jax.ShapeDtypeStruct((B, S, S), jnp.float32),
        in_specs=[spec["q"], spec["k"], spec["w"]], out_specs=spec["pair"],
        scratch_shapes=[], compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret, name="dsa_index_scores")(qh, kI, w)


def _index_backward(qh, kI, w, dI, blk_q, blk_k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Hi, S, d = qh.shape
    n_q = S // blk_q
    table = _live_pairs(S, blk_q, blk_k)
    spec = _index_specs(Hi, d, blk_q, blk_k)
    dq, dw, parts = _sweep_call(
        functools.partial(_index_bwd_kernel, heads=Hi, blk_q=blk_q, blk_k=blk_k),
        (B, table.size // 2), [table],
        out_shape=[jax.ShapeDtypeStruct(qh.shape, jnp.float32),
                   jax.ShapeDtypeStruct(w.shape, jnp.float32),
                   jax.ShapeDtypeStruct((B, n_q, S, d), jnp.float32)],
        in_specs=[spec["q"], spec["k"], spec["w"], spec["pair"]],
        out_specs=[spec["q"], spec["w"],
                   pl.BlockSpec((1, 1, blk_k, d),
                                lambda b, at, t: (b, *_pair(t, at), 0))],
        scratch_shapes=[pltpu.VMEM((Hi, 3 * d, blk_q), jnp.float32),
                        pltpu.VMEM((blk_q, Hi), jnp.float32),
                        pltpu.VMEM((3 * d, blk_k), jnp.float32)],
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret, name="dsa_index_scores_bwd")(qh, kI, w, dI)
    # a q-block wrote its parts of dk for the k-blocks up to its diagonal
    written = (np.arange(S)[None, :] // blk_k
               <= _last_kv_step(np.arange(n_q), blk_q, blk_k)[:, None])
    dk = jnp.sum(jnp.where(written[None, :, :, None], parts, 0.0), axis=1)
    return dq, dk, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def index_scores(qI, kI, w, blk_q: int = 512, blk_k: int = 512,
                 interpret: bool = False):
    """I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) for s <= t, (B, S, S)
    float32, of qI (B, S, Hi, d), kI (B, S, d) and w (B, S, Hi), float32.
    Blocks above the diagonal are never written: mask before reading. Keeps
    its three inputs; the backward kernel makes the heads' scores again."""
    return _index_fwd(qI, kI, w, blk_q, blk_k, interpret)[0]


def _index_fwd(qI, kI, w, blk_q, blk_k, interpret):
    blk_q, blk_k = _blocks(qI.shape[1], blk_q, blk_k)
    qh = qI.transpose(0, 2, 1, 3)  # a head's rows together, as the kernels read
    return _index_forward(qh, kI, w, blk_q, blk_k, interpret), (qh, kI, w)


def _index_bwd(blk_q, blk_k, interpret, res, dI):
    qh, kI, w = res
    blk_q, blk_k = _blocks(qh.shape[2], blk_q, blk_k)
    dq, dk, dw = _index_backward(qh, kI, w, dI, blk_q, blk_k, interpret)
    return dq.transpose(0, 2, 1, 3), dk, dw


index_scores.defvjp(_index_fwd, _index_bwd)


# -- the choice ---------------------------------------------------------------


def _ordered(x):
    """float32 -> uint32 whose unsigned order is the floats' (-0.0 as +0.0)."""
    bits = lax.bitcast_convert_type(jnp.where(x == 0.0, 0.0, x), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def plain_select(I, k: int):
    """`select` as 32 + log2(S) passes of XLA's over the whole of I: the
    tests' oracle, the "dense" path and any shape the kernel does not take."""
    B, S, _ = I.shape
    t = lax.broadcasted_iota(jnp.int32, (1, S, S), 1)
    s = lax.broadcasted_iota(jnp.int32, (1, S, S), 2)
    causal = s <= t
    want = jnp.minimum(jnp.arange(S, dtype=jnp.int32) + 1, k)[None, :, None]
    keys = _ordered(lax.stop_gradient(I))

    def count(seen):
        return jnp.sum(seen & causal, axis=-1, keepdims=True, dtype=jnp.int32)

    def value_bit(i, T):
        higher = T | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        return jnp.where(count(keys >= higher) >= want, higher, T)

    T = lax.fori_loop(0, 32, value_bit, jnp.zeros((B, S, 1), jnp.uint32))
    above, level = keys > T, keys == T
    short = want - count(above)  # of the keys that equal T, the first so many
    bits = max(1, int(S - 1).bit_length())

    def position_bit(i, P):
        further = P | (jnp.int32(1 << (bits - 1)) >> i)
        return jnp.where(count(level & (s < further)) < short, further, P)

    P = lax.fori_loop(0, bits, position_bit, jnp.zeros((B, S, 1), jnp.int32))
    return ((above | (level & (s <= P))) & causal).astype(jnp.int8)


_LOWEST = -(1 << 31)  # the signed ordered form no threshold of the search reaches
LANES = 128  # of a register tile: the keys a comparison takes a row
SELECT_BLOCK_BYTES = 4 << 20  # of a block of whole rows of the scores
SELECT_SPAN = 1024  # keys a turn of a counting pass's loop


def _signed_order(x):
    """float32 -> int32 whose signed order is the floats' (-0.0 as +0.0):
    `_ordered`'s map with the top bit turned, for a chip that compares signed
    numbers."""
    bits = lax.bitcast_convert_type(jnp.where(x == 0.0, 0.0, x), jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _select_kernel(i_ref, o_ref, keys_scr, *, k: int, span: int):
    """The choice of a block of whole rows: the scores (1, rows, S) come once,
    their ordered form stands in `keys_scr` with the keys after a row's own
    position at the lowest number, and every counting pass of `plain_select`
    runs over that. What is a number a row is a (rows, LANES) tile with every
    lane a copy. Keys are taken `span` at a turn of a loop, and the turns end
    at the span that holds the block's last row's own position: the rest of
    the block is written as not chosen and never read."""
    from jax.experimental import pallas as pl

    rows, S = keys_scr.shape
    row0 = pl.program_id(1) * rows
    spans = (row0 + rows + span - 1) // span
    t = row0 + lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
    lane = lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    want = jnp.minimum(t + 1, k)

    def over(n, a_slice, carry=None):
        """`a_slice(at, carry)` for every LANES keys of the spans, `at` the
        first one's position."""
        def a_span(j, carry):
            for c in range(0, span, LANES):
                carry = a_slice(pl.multiple_of(j * span + c, LANES), carry)
            return carry

        return lax.fori_loop(0, n, a_span, carry)

    def order(at, _):
        keys_scr[:, pl.ds(at, LANES)] = jnp.where(
            at + lane <= t, _signed_order(i_ref[0, :, pl.ds(at, LANES)]), _LOWEST)

    over(spans, order)

    def count(hit):
        """How many keys of each row `hit(its ordered form, its position)`:
        the slices' hits added tile on tile, one sum across the lanes a row."""
        acc = over(spans, lambda at, acc: acc + hit(
            keys_scr[:, pl.ds(at, LANES)], at + lane).astype(jnp.int32),
            jnp.zeros((rows, LANES), jnp.int32))
        return jnp.broadcast_to(jnp.sum(acc, axis=-1, keepdims=True), acc.shape)

    def value_bit(i, found):
        T, n_T = found
        higher = T ^ lax.shift_right_logical(jnp.int32(_LOWEST), i)
        n = count(lambda keys, s: keys >= higher)
        return jnp.where(n >= want, higher, T), jnp.where(n >= want, n, n_T)

    # T, and how many keys stand at or above it: every key a row sees at first
    T, n_T = lax.fori_loop(0, 32, value_bit,
                           (jnp.full((rows, LANES), _LOWEST, jnp.int32), t + 1))
    bits = max(1, int(S - 1).bit_length())

    def cut_the_ties():
        short = want - count(lambda keys, s: keys > T)

        def position_bit(i, P):
            further = P | (jnp.int32(1 << (bits - 1)) >> i)
            n = count(lambda keys, s: (keys == T) & (s < further) & (s <= t))
            return jnp.where(n < short, further, P)

        return lax.fori_loop(0, bits, position_bit,
                             jnp.zeros((rows, LANES), jnp.int32))

    # a block in which no row has more keys at its T than it wants takes them
    # all: the position passes run where a tie is cut
    P = lax.cond(jnp.max(n_T - want) > 0, cut_the_ties,
                 lambda: jnp.full((rows, LANES), S, jnp.int32))

    def write(at, _):
        keys, s = keys_scr[:, pl.ds(at, LANES)], at + lane
        o_ref[0, :, pl.ds(at, LANES)] = (
            ((keys > T) | ((keys == T) & (s <= P))) & (s <= t)).astype(jnp.int8)

    over(spans, write)

    def nothing(j, _):
        o_ref[0, :, pl.ds(pl.multiple_of(j * span, span), span)] = jnp.zeros(
            (rows, span), jnp.int8)

    lax.fori_loop(spans, S // span, nothing, None)


def _select_rows(S: int) -> int:
    """The rows of the kernel's block at S keys a row: the largest multiple of
    32 (an int8 tile's rows) that divides S and whose float32 block is within
    `SELECT_BLOCK_BYTES`; 0 where there is none or the lanes do not tile S."""
    if S % LANES:
        return 0
    fit = [r for r in range(32, S + 1, 32)
           if S % r == 0 and r * S * 4 <= SELECT_BLOCK_BYTES]
    return max(fit, default=0)


def select(I, k: int, interpret: bool = False):
    """The choice (B, S, S) int8 of scores I (B, S, S): 1 at the min(t + 1, k)
    keys s <= t with the largest I[t, s], a tie to the lower position; 0
    elsewhere, every s > t among them, whatever I holds there. The k-th
    largest value of a row by bisection over the 32 bits of its ordered form
    (the largest T with at least that many keys >= T), then, among the keys
    that equal it, the position up to which they are taken by bisection over
    the positions: 32 + log2(S) counting passes and no sort, run by one
    kernel over a block of whole rows that it reads once (`_select_kernel`);
    `plain_select` where no such block tiles S."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, _ = I.shape
    rows = _select_rows(S)
    if not rows:
        return plain_select(I, k)
    span = SELECT_SPAN if S % SELECT_SPAN == 0 else LANES
    block = pl.BlockSpec((1, rows, S), lambda b, r: (b, r, 0))
    return kernel_call(
        functools.partial(_select_kernel, k=k, span=span),
        grid=(B, S // rows), out_shape=jax.ShapeDtypeStruct((B, S, S), jnp.int8),
        in_specs=[block], out_specs=block,
        scratch_shapes=[pltpu.VMEM((rows, S), jnp.int32)],
        compiler_params=_params("parallel", "parallel"),
        interpret=interpret, name="dsa_select")(lax.stop_gradient(I))


# -- the core over the chosen keys ------------------------------------------------


def _seen(c_ref):
    """The block's choice as booleans, from its bytes."""
    return c_ref[0].astype(jnp.int32) != 0


def plain_sparse_attention(q, k, v, chosen, sm_scale=None):
    """(output (B, H, S, hd), log-sum-exp (B, H, S)) of q (B, H, S, hd), k and
    v (B, Hkv, S, hd) and the choice (B, S, S): a dense softmax under the
    mask, float32."""
    sm_scale = sm_scale or q.shape[-1] ** -0.5
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32), precision=_HIGHEST) * sm_scale
    s = jnp.where(chosen[:, None] != 0, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", jnp.exp(s - lse[..., None]),
                     v.astype(jnp.float32), precision=_HIGHEST)
    return out.astype(q.dtype), lax.stop_gradient(lse)


def _core_kernel(table, q_ref, k_ref, v_ref, c_ref, o_ref, lse_ref, m_scr,
                 l_scr, acc_scr, *, blk_q: int, blk_k: int, sm_scale: float):
    """`flash_attention._kernel` under the choice: one (head, q-block,
    k-block) step of the online softmax, the block's bytes its mask."""
    from jax.experimental import pallas as pl

    qi, kb = _pair(table, pl.program_id(1))

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    q, k, v = q_ref[0], k_ref[0], v_ref[0]
    seen = _seen(c_ref)
    s = jnp.where(seen, _nt(q, k) * sm_scale, NEG_INF)
    m = m_scr[...]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    # a row with no chosen key in this block nor before it: s - m is 0
    p = jnp.where(seen, jnp.exp(s - _across(m_new, blk_k)), 0.0)
    corr = jnp.exp(m - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * _across(corr, acc_scr.shape[-1]) + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(kb == _last_kv_step(qi, blk_q, blk_k))
    def _finalize():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / _across(l, acc_scr.shape[-1])).astype(o_ref.dtype)
        lse_ref[0] = (m_scr[...] + jnp.log(l))[:, :lse_ref.shape[-1]]


def _probs(q_ref, k_ref, lse_ref, c_ref, sm_scale):
    """A block's probabilities from the rows' log-sum-exp, 0 where not chosen."""
    s = _nt(q_ref[0], k_ref[0]) * sm_scale
    return jnp.where(_seen(c_ref), jnp.exp(s - lse_ref[0][:, :1]), 0.0)


def _dq_kernel(table, q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, c_ref,
               dq_ref, dq_scr, *, blk_q: int, blk_k: int, sm_scale: float):
    """`flash_attention._dq_kernel` under the choice."""
    from jax.experimental import pallas as pl

    qi, kb = _pair(table, pl.program_id(1))

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr[...])

    k = k_ref[0]
    p = _probs(q_ref, k_ref, lse_ref, c_ref, sm_scale)
    ds = p * (_nt(do_ref[0], v_ref[0]) - dl_ref[0][:, :1])
    dq_scr[...] += jnp.dot(ds.astype(k.dtype), k,
                           preferred_element_type=jnp.float32) * sm_scale

    @pl.when(kb == _last_kv_step(qi, blk_q, blk_k))
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(table, q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, c_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, blk_q: int, blk_k: int,
                sm_scale: float, group: int, seq: int):
    """`flash_attention._dkv_kernel` under the choice: a k-block's sweep over
    its q-blocks, once a query head of the group, summed in scratch."""
    from jax.experimental import pallas as pl

    at = pl.program_id(1)
    kj, head, qi = (_row(table, r, 3, at) for r in range(3))
    first = (kj * blk_k) // blk_q
    per_head = seq // blk_q - first
    step = head * per_head + qi - first

    @pl.when(step == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr[...])
        dv_scr[...] = jnp.zeros_like(dv_scr[...])

    q, do = q_ref[0], do_ref[0]
    p = _probs(q_ref, k_ref, lse_ref, c_ref, sm_scale)
    dv_scr[...] += jnp.dot(p.T.astype(do.dtype), do,
                           preferred_element_type=jnp.float32)
    ds = p * (_nt(do, v_ref[0]) - dl_ref[0][:, :1])
    dk_scr[...] += jnp.dot(ds.T.astype(q.dtype), q,
                           preferred_element_type=jnp.float32) * sm_scale

    @pl.when(step == group * per_head - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _kv_specs(H, g, hd, blk_q, blk_k):
    """The block specs of a q-block's sweep over k-blocks, the grid's first
    axis over B * H query heads: q's, k's and v's, a row statistic's, the
    choice's."""
    from jax.experimental import pallas as pl

    def q_at(b, at, t):
        return (b, _pair(t, at)[0], 0)

    return dict(
        q=pl.BlockSpec((1, blk_q, hd), q_at),
        kv=pl.BlockSpec((1, blk_k, hd),
                        lambda b, at, t: (b // g, _pair(t, at)[1], 0)),
        row=pl.BlockSpec((1, blk_q, 8), q_at),
        chosen=pl.BlockSpec((1, blk_q, blk_k),
                            lambda b, at, t: (b // H, *_pair(t, at))))


def _core_forward(q, k, v, chosen, sm_scale, blk_q, blk_k, interpret):
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, hd = q.shape
    g = H // k.shape[1]
    table = _live_pairs(S, blk_q, blk_k)
    spec = _kv_specs(H, g, hd, blk_q, blk_k)
    out, lse = _sweep_call(
        functools.partial(_core_kernel, blk_q=blk_q, blk_k=blk_k,
                          sm_scale=sm_scale),
        (B * H, table.size // 2), [table],
        out_shape=[jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
                   jax.ShapeDtypeStruct((B * H, S, 8), jnp.float32)],
        in_specs=[spec["q"], spec["kv"], spec["kv"], spec["chosen"]],
        out_specs=[spec["q"], spec["row"]],
        scratch_shapes=[pltpu.VMEM((blk_q, 128), jnp.float32),
                        pltpu.VMEM((blk_q, 128), jnp.float32),
                        pltpu.VMEM((blk_q, hd), jnp.float32)],
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret, name="dsa_core_forward",
    )(q.reshape(B * H, S, hd), k.reshape(-1, S, hd), v.reshape(-1, S, hd), chosen)
    return out.reshape(q.shape), lse[:, :, 0].reshape(B, H, S)


def _rows(x, B, H, S):
    """A number a row (B, H, S) as the kernels read it: (B * H, S, 8), every
    lane a copy."""
    return jnp.broadcast_to(x.reshape(B * H, S)[:, :, None], (B * H, S, 8))


def _core_backward(q, k, v, chosen, o, lse, do, sm_scale, blk_q, blk_k,
                   interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, hd = q.shape
    Hkv = k.shape[1]
    g = H // Hkv
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    qf, dof = q.reshape(B * H, S, hd), do.reshape(B * H, S, hd)
    kf, vf = k.reshape(B * Hkv, S, hd), v.reshape(B * Hkv, S, hd)
    lsef, deltaf = _rows(lse, B, H, S), _rows(delta, B, H, S)

    table = _live_pairs(S, blk_q, blk_k)
    spec = _kv_specs(H, g, hd, blk_q, blk_k)
    dq = _sweep_call(
        functools.partial(_dq_kernel, blk_q=blk_q, blk_k=blk_k, sm_scale=sm_scale),
        (B * H, table.size // 2), [table],
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        in_specs=[spec["q"], spec["kv"], spec["kv"], spec["q"], spec["row"],
                  spec["row"], spec["chosen"]],
        out_specs=spec["q"],
        scratch_shapes=[pltpu.VMEM((blk_q, hd), jnp.float32)],
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret, name="dsa_core_dq",
    )(qf, kf, vf, dof, lsef, deltaf, chosen)

    # a k-block's sweep: its q-blocks diagonal to last, once a head of the group
    table = _live_pairs(S, blk_q, blk_k, g)

    def q_at(b, at, t):
        return (b * g + _row(t, 1, 3, at), _row(t, 2, 3, at), 0)

    def k_at(b, at, t):
        return (b, _row(t, 0, 3, at), 0)

    qi_spec = pl.BlockSpec((1, blk_q, hd), q_at)
    row_spec = pl.BlockSpec((1, blk_q, 8), q_at)
    kj_spec = pl.BlockSpec((1, blk_k, hd), k_at)
    chosen_spec = pl.BlockSpec(
        (1, blk_q, blk_k),
        lambda b, at, t: (b // Hkv, _row(t, 2, 3, at), _row(t, 0, 3, at)))
    dk, dv = _sweep_call(
        functools.partial(_dkv_kernel, blk_q=blk_q, blk_k=blk_k,
                          sm_scale=sm_scale, group=g, seq=S),
        (B * Hkv, table.size // 3), [table],
        out_shape=[jax.ShapeDtypeStruct((B * Hkv, S, hd), k.dtype),
                   jax.ShapeDtypeStruct((B * Hkv, S, hd), v.dtype)],
        in_specs=[qi_spec, kj_spec, kj_spec, qi_spec, row_spec, row_spec,
                  chosen_spec],
        out_specs=[kj_spec, kj_spec],
        scratch_shapes=[pltpu.VMEM((blk_k, hd), jnp.float32),
                        pltpu.VMEM((blk_k, hd), jnp.float32)],
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret, name="dsa_core_dkv",
    )(qf, kf, vf, dof, lsef, deltaf, chosen)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def sparse_attention(q, k, v, chosen, sm_scale: float = None, blk_q: int = 512,
                     blk_k: int = 512, interpret: bool = False):
    """(output (B, H, S, hd), the rows' log-sum-exp (B, H, S) float32) of the
    softmax core over the chosen keys: q (B, H, S, hd), k and v (B, Hkv, S,
    hd), H a multiple of Hkv (query head h reads key/value head h // (H /
    Hkv)), `chosen` (B, S, S) int8 from `select`, one choice for all heads.
    Forward, dQ and dK/dV are Pallas kernels over the live block pairs of the
    causal half. The gradients reach q, k and v through the chosen keys only;
    the log-sum-exp is handed on for `head_mean_probs` and takes no
    cotangent. Keeps q, k, v, the choice, the output and the log-sum-exp, the
    last two under `flash_attention`'s names `flash_out` and `flash_lse`, so
    that a layer run again keeps them and does not run the forward kernel a
    second time (`models/transformer._layer_again`)."""
    return _core_fwd(q, k, v, chosen, sm_scale, blk_q, blk_k, interpret)[0]


def _core_fwd(q, k, v, chosen, sm_scale, blk_q, blk_k, interpret):
    sm_scale = sm_scale or q.shape[-1] ** -0.5
    blk_q, blk_k = _blocks(q.shape[2], blk_q, blk_k)
    out, lse = _core_forward(q, k, v, chosen, sm_scale, blk_q, blk_k, interpret)
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return (out, lse), (q, k, v, chosen, out, lse)


def _core_bwd(sm_scale, blk_q, blk_k, interpret, res, cotangents):
    q, k, v, chosen, out, lse = res
    sm_scale = sm_scale or q.shape[-1] ** -0.5
    blk_q, blk_k = _blocks(q.shape[2], blk_q, blk_k)
    return (*_core_backward(q, k, v, chosen, out, lse, cotangents[0], sm_scale,
                            blk_q, blk_k, interpret), None)


sparse_attention.defvjp(_core_fwd, _core_bwd)


# -- what the indexer learns from ---------------------------------------------------


def _entropy(p, chosen):
    """sum over a row's chosen keys of p log p, (B, S)."""
    return jnp.sum(jnp.where(chosen != 0, jax.scipy.special.xlogy(p, p), 0.0),
                   axis=-1)


def plain_head_mean_probs(q, k, lse, chosen, sm_scale=None):
    """`head_mean_probs` through the (B, H, S, S) probabilities."""
    sm_scale = sm_scale or q.shape[-1] ** -0.5
    k = jnp.repeat(k, q.shape[1] // k.shape[1], axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32), precision=_HIGHEST) * sm_scale
    a = jnp.where(chosen[:, None] != 0, jnp.exp(s - lse[..., None]), 0.0)
    p = lax.stop_gradient(jnp.mean(a, axis=1))
    return p, _entropy(p, chosen)


def _mean_kernel(table, q_ref, k_ref, lse_ref, c_ref, p_ref, acc_scr, *,
                 heads: int, sm_scale: float):
    """A block of p: the heads innermost, their probabilities summed in
    scratch and written once, over the heads."""
    from jax.experimental import pallas as pl

    h = pl.program_id(2)

    @pl.when(h == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    acc_scr[...] += _probs(q_ref, k_ref, lse_ref, c_ref, sm_scale)

    @pl.when(h == heads - 1)
    def _finalize():
        p_ref[0] = acc_scr[...] * (1.0 / heads)


def head_mean_probs(q, k, lse, chosen, sm_scale: float = None,
                    blk_q: int = 512, blk_k: int = 512, interpret: bool = False):
    """(p (B, S, S) float32, the rows' sum of p log p (B, S)): p[t, s] the
    mean over the H query heads of the core's probability of key s for query
    t, exp(q . k sm_scale - lse), at the chosen keys; defined where `chosen`
    is set and never written in a block above the diagonal. Every input is a
    constant here: the indexer's target takes no gradient."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q, k, lse = (lax.stop_gradient(x) for x in (q, k, lse))
    B, H, S, hd = q.shape
    Hkv = k.shape[1]
    g = H // Hkv
    sm_scale = sm_scale or hd ** -0.5
    blk_q, blk_k = _blocks(S, blk_q, blk_k)
    table = _live_pairs(S, blk_q, blk_k)

    def q_at(b, at, h, t):
        return (b * H + h, _pair(t, at)[0], 0)

    def pair_at(b, at, h, t):
        return (b, *_pair(t, at))

    p = _sweep_call(
        functools.partial(_mean_kernel, heads=H, sm_scale=sm_scale),
        (B, table.size // 2, H), [table],
        out_shape=jax.ShapeDtypeStruct((B, S, S), jnp.float32),
        in_specs=[pl.BlockSpec((1, blk_q, hd), q_at),
                  pl.BlockSpec((1, blk_k, hd), lambda b, at, h, t: (
                      b * Hkv + h // g, _pair(t, at)[1], 0)),
                  pl.BlockSpec((1, blk_q, 8), q_at),
                  pl.BlockSpec((1, blk_q, blk_k), pair_at)],
        out_specs=pl.BlockSpec((1, blk_q, blk_k), pair_at),
        scratch_shapes=[pltpu.VMEM((blk_q, blk_k), jnp.float32)],
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret, name="dsa_head_mean_probs",
    )(q.reshape(B * H, S, hd), k.reshape(B * Hkv, S, hd), _rows(lse, B, H, S),
      chosen)
    p = lax.stop_gradient(p)
    return p, _entropy(p, chosen)


def indexer_kl(I, chosen, p, entropy):
    """The KL divergence of the indexer's distribution from the core's, the
    mean over the rows (B, S): sum over a row's chosen keys of p log p - p
    log_softmax(I), the softmax over the chosen keys alone; float32. Reads I
    and p under the mask only. Its derivative in I is (softmax over the chosen
    of I - p) / rows, since a row's p sum to one; p and `entropy` are
    constants."""
    seen = chosen != 0
    lse = jax.nn.logsumexp(jnp.where(seen, I, -jnp.inf), axis=-1, keepdims=True)
    # masked before any product: what was never written may be a NaN, and a
    # zero cotangent times it is one too
    I, p = jnp.where(seen, I, 0.0), jnp.where(seen, lax.stop_gradient(p), 0.0)
    cross = jnp.sum(p * (I - lse), axis=-1)
    return jnp.mean(lax.stop_gradient(entropy) - cross)
