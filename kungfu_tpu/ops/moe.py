"""Top-k routed mixture-of-experts feed-forward, on one shard or across an
expert axis.

Beyond-reference capability (the reference is data-parallel only,
SURVEY §2.4). `moe_ffn` is one function for both layouts:

- **One shard** (`axis_size == 1`, the model's path in
  `models/transformer.py`): nothing crosses a wire, so nothing needs a
  capacity. The T x top_k token-choices are ordered by expert, the group
  sizes counted, the experts run over groups of the sizes that came
  (`ops/grouped_matmul.py`: the repo's Pallas kernels where the shape tiles
  and the program is the TPU's, `jax.lax.ragged_dot` anywhere else), and the
  results go back by the inverse order, weighted by their gates. Every
  token-choice is computed whatever the load: no drops, no dense pass over
  all experts. Told which experts it holds (`held`, one
  chip's share of a layer whose experts lie on several chips), the shard
  routes over all of the router's experts and computes the part of the
  result that its own give, as dropless; it exchanges nothing.
- **Across an expert axis** (`axis_size > 1`, inside a `shard_map`): E =
  axis_size * experts_per_device global experts. Each shard packs its
  tokens into per-expert capacity buckets (choices side by side on the
  bucket axis so ONE all_to_all carries them all), exchanges buckets with
  every peer over ICI, runs its local experts over the equal groups that
  arrived, and sends results back the way they came. A token-choice over
  capacity is dropped (combine weight 0, the residual path carries the
  token): the capacity is what bounds the wire.

The gate rule and the expert function are the caller's: `switch_gates`
(top-1 raw, top-k renormalised) with `gelu_experts` by default, `raw_gates`
with `swiglu_experts` for OLMoE, renormalised and scaled gates with
`relu2_experts` (two matrices, w_down (relu(w_up x))^2, their widths filled
with zeros to what the grouped matmul runs well at) for Nemotron-H,
`reglu_experts` (w_down (relu(w_gate x) * w_up x)) for SmallThinker. So is
the router's rule (`route`, PR 41):
the scores are a softmax over the experts or a sigmoid an expert, and a
selection bias an expert may be added for the choice and not for the weight
(GLM-4.7-Flash, after DeepSeek-V3). And so may the routing itself be (PR 65):
`moe_ffn(routing=...)` takes what `route` made of other rows than those the
experts transform (SmallThinker routes from the layer's input, ahead of the
mixer), with the order of the token-choices (`dispatch_plan`) where the caller
made that too, in all three layouts. `switch_moe` (top-1, one expert per
device) is the round-4 surface, a thin special case.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from kungfu_tpu.ops.grouped_matmul import grouped_matmul
from kungfu_tpu.ops.row_moves import add_rows, take_rows


class MoeAux(NamedTuple):
    """What the router says beside the output. `load_balance` = E * sum_e
    f_e P_e, f_e the share of the token-choices that went to expert e and
    P_e its mean router probability (1 when both are uniform); `z_loss` =
    mean over tokens of logsumexp(router logits)^2; `counts` (E,) int32 =
    token-choices computed per expert (held here, under `held`), so T * top_k
    - counts.sum() is what was dropped (0 on one shard by construction, of
    the token-choices that fell on its experts); `chosen` (T, top_k) int32
    = the experts the router took for each token; `bias_moved` () int32 =
    those of them that a selection bias changed against a choice on the
    scores alone (`bias_moved`), None where the router has no bias."""
    load_balance: jax.Array
    z_loss: jax.Array
    counts: jax.Array
    chosen: jax.Array
    bias_moved: jax.Array = None


def switch_gates(top_probs):
    """Top-1 keeps the RAW router probability (switch semantics); top-k
    renormalises over the chosen experts (GShard/Mixtral combine)."""
    if top_probs.shape[-1] == 1:
        return top_probs
    return renormalised_gates(top_probs)


def raw_gates(top_probs):
    """The chosen experts' softmax probabilities as they are (OLMoE,
    `norm_topk_prob` false)."""
    return top_probs


def renormalised_gates(top_probs):
    """The chosen experts' probabilities over their sum, whatever top_k
    (`norm_topk_prob` true)."""
    return top_probs / jnp.sum(top_probs, axis=-1, keepdims=True)


def scaled(gates, scale: float):
    """`gates` times a constant (a routed scaling factor); `gates` itself
    at 1."""
    if scale == 1.0:
        return gates
    return lambda top_probs: scale * gates(top_probs)


def gelu_experts(rows, experts, group_sizes):
    """Two-matrix gelu experts: experts = (w_in (e, D, F), w_out (e, F, D));
    rows (N, D) ordered by expert in groups of `group_sizes` (e,)."""
    w_in, w_out = experts
    h = jax.nn.gelu(grouped_matmul(rows, w_in.astype(rows.dtype), group_sizes))
    return grouped_matmul(h, w_out.astype(rows.dtype), group_sizes)


@functools.partial(jax.checkpoint, prevent_cse=False)
def _silu_gate_down(gate, up, w_down, group_sizes):
    """(silu(gate) * up) @ w_down over the groups. Keeps gate, up and
    w_down; the silu, the product and with them the matmul's operand are
    recomputed (PERF.md, PR 25's rule)."""
    return grouped_matmul(jax.nn.silu(gate) * up, w_down, group_sizes)


def swiglu_experts(rows, experts, group_sizes):
    """Three-matrix gated-silu experts: experts = (w_gate (e, D, F), w_up
    (e, D, F), w_down (e, F, D)); y = w_down (silu(w_gate x) * w_up x)."""
    w_gate, w_up, w_down = (w.astype(rows.dtype) for w in experts)
    gate = grouped_matmul(rows, w_gate, group_sizes)
    up = grouped_matmul(rows, w_up, group_sizes)
    return _silu_gate_down(gate, up, w_down, group_sizes)


@functools.partial(jax.checkpoint, prevent_cse=False)
def _relu2_down(up, w_down, group_sizes):
    """relu(up)^2 @ w_down over the groups. Keeps up and w_down; the square,
    the matmul's operand, is recomputed, as `_silu_gate_down`'s is."""
    return grouped_matmul(jnp.square(jax.nn.relu(up)), w_down, group_sizes)


GROUPED_WIDTH = 512  # `relu2_experts` feeds the grouped matmul multiples of it


def _to_width(a, axis: int):
    """`a` with zeros after it along `axis`, up to the next multiple of
    `GROUPED_WIDTH`."""
    pad = -a.shape[axis] % GROUPED_WIDTH
    if not pad:
        return a
    return jnp.pad(a, [(0, pad if i == axis else 0) for i in range(a.ndim)])


def relu2_experts(rows, experts, group_sizes):
    """Two-matrix squared-relu experts (Nemotron-H's): experts = (w_up (e, D,
    F), w_down (e, F, D)); y = w_down (relu(w_up x))^2, no gate matrix. The
    grouped matmul is fed widths that are multiples of `GROUPED_WIDTH`, D
    and F filled with zeros where the weights are cast (relu(0)^2 = 0 meets
    zero rows of w_down, and the output's further columns are cut): XLA's
    grouped matmul on the TPU takes 2.0 ms for 3,072 rows of 2,688 x 1,856
    and 0.7 for 2,688 x 2,048, and a share's layer, forward and backward,
    50.1 ms at 2,688 and 1,856 and 24.2 at 3,072 and 2,048, to the last bit
    the same numbers (PERF.md, PR 43)."""
    D = rows.shape[-1]
    w_up, w_down = (_to_width(_to_width(w.astype(rows.dtype), 1), 2)
                    for w in experts)
    up = grouped_matmul(_to_width(rows, 1), w_up, group_sizes)
    return _relu2_down(up, w_down, group_sizes)[:, :D]


@functools.partial(jax.checkpoint, prevent_cse=False)
def _relu_gate_down(gate, up, w_down, group_sizes):
    """(relu(gate) * up) @ w_down over the groups. Keeps gate, up and w_down;
    the relu, the product and with them the matmul's operand are recomputed,
    as `_silu_gate_down`'s are."""
    return grouped_matmul(jax.nn.relu(gate) * up, w_down, group_sizes)


def reglu_experts(rows, experts, group_sizes):
    """Three-matrix relu-gated experts (SmallThinker's sparse ReGLU): experts
    = (w_gate (e, D, F), w_up (e, D, F), w_down (e, F, D)); y = w_down
    (relu(w_gate x) * w_up x). `swiglu_experts` with the relu in the silu's
    place: where the gate's product is not positive the row of w_down is
    multiplied by an exact zero (`transformer.gate_zero_shares` counts them)."""
    w_gate, w_up, w_down = (w.astype(rows.dtype) for w in experts)
    gate = grouped_matmul(rows, w_gate, group_sizes)
    up = grouped_matmul(rows, w_up, group_sizes)
    return _relu_gate_down(gate, up, w_down, group_sizes)


@jax.custom_vjp
def _permute_rows(x, perm, inverse):
    """x[perm] for a permutation `perm` of x's rows with `inverse` its
    inverse. The backward pass is the gather g[inverse]: autodiff writes a
    scatter-add for a gather whose indices it cannot know to be distinct
    (7.3 ms a step against 2.2 for the gather at 65,536 x 2,048 bf16 on the
    v5e; PERF.md, PR 27)."""
    return x[perm]


_permute_rows.defvjp(
    lambda x, perm, inverse: (x[perm], (perm, inverse)),
    lambda res, g: (g[res[1]], None, None))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch_rows(x, order, inverse, top_k: int):
    """Token-choice c = t * top_k + j reads token t: x[order // top_k], the
    T * top_k rows in the order `order` of the token-choices (`inverse` its
    inverse). Backward: the rows' cotangents back in token order by a
    gather, summed over each token's top_k in float32; not the scatter-add
    of 65,536 rows into 8,192 that autodiff writes."""
    return x[order // top_k]


def _dispatch_rows_bwd(top_k, res, g):
    inverse, = res
    g = g[inverse].reshape(-1, top_k, g.shape[-1])
    return jnp.sum(g.astype(jnp.float32), axis=1).astype(g.dtype), None, None


_dispatch_rows.defvjp(
    lambda x, order, inverse, top_k: (x[order // top_k], (inverse,)),
    _dispatch_rows_bwd)


def _count_choices(chosen, n_experts: int):
    """How many of the token-choices `chosen` (any shape, int32) named each
    of `n_experts` experts: (n_experts,) int32, numpy's `bincount`. A choice
    outside 0 .. n_experts - 1 names none. Every choice is compared with
    every expert, the experts down the rows and the choices along the lanes,
    and the rows summed: one reduce fusion that writes no mask. Not
    `zeros.at[chosen].add(1)`: the v5e applies a scatter's updates one after
    another, 8.7 ns each in four cells' steps (1.430 ms for 163,840 choices
    over 512 experts, 0.715 for 81,920 over 256, 0.430 for 49,152 over 128,
    0.572 for 65,536 over 64), where this takes 0.053, 0.013, 0.005 and
    0.002 ms, and 0.005 to 0.010 more where the choices' (T, top_k) layout
    has to be made flat first (PERF.md, PR 50)."""
    experts = jnp.arange(n_experts, dtype=chosen.dtype)
    return jnp.sum(experts[:, None] == chosen.reshape(1, -1), axis=1,
                   dtype=jnp.int32)


def route(x, router_w, top_k: int, scores: str = "softmax", bias=None):
    """(logits, scores, top_scores, top_idx) of tokens x (T, D): the scores
    over all E experts in float32 by the rule `scores`, "softmax" over the
    experts or "sigmoid", each expert's own (the router's matmul at the
    highest precision: it is T x D x E, nothing beside the experts', and a
    rounded router weight moves the k-th choice of a token whose k-th and
    (k+1)-th scores are close), and the top_k largest taken. With `bias`
    (E,), a number an expert (the selection bias of a router balanced
    without an auxiliary loss, DeepSeek-V3's `noaux_tc`), the choice is the
    top_k of scores + bias and `top_scores` are the scores of the chosen,
    without it: the bias moves the choice and never the weight, and the
    loss is constant in it."""
    if scores not in ("softmax", "sigmoid"):
        raise ValueError(f"scores {scores!r} is not 'softmax' or 'sigmoid'")
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    probs = (jax.nn.softmax(logits, axis=-1) if scores == "softmax"
             else jax.nn.sigmoid(logits))
    if bias is None:
        top_probs, top_idx = lax.top_k(probs, top_k)
    else:
        _, top_idx = lax.top_k(probs + bias.astype(jnp.float32), top_k)
        top_probs = jnp.take_along_axis(probs, top_idx, axis=-1)
    return logits, probs, top_probs, top_idx


def bias_moved(probs, top_idx):
    """Token-choices (of `route` under a selection bias) that the bias
    changed: chosen on scores + bias and not among the top_k of the scores
    alone. () int32."""
    _, plain = lax.top_k(probs, top_idx.shape[-1])
    same = (top_idx[:, :, None] == plain[:, None, :]).any(-1)
    return jnp.sum(~same).astype(jnp.int32)


class Plan(NamedTuple):
    """The order of one shard's token-choices (`dispatch_plan`): `order`
    (T * top_k,) int32 lists the token-choices c = t * top_k + j by expert,
    under `held` the held experts' groups first and then every choice that
    fell elsewhere; `back` its inverse (None under `held`, whose combine
    scatters by token); `counts` (E,) int32 the choices an expert."""
    order: jax.Array
    back: jax.Array
    counts: jax.Array


class Routing(NamedTuple):
    """A routing made outside `moe_ffn`, of whatever rows the caller routes
    from: `route`, the four that `route` gives (logits, scores, the chosen
    experts' scores, the chosen experts), and `plan`, `dispatch_plan` of the
    choice where the caller made that too (one shard and a share; across an
    expert axis the buckets are the plan, and `moe_ffn` makes them)."""
    route: tuple
    plan: Plan = None


def dispatch_plan(top_idx, n_experts: int, held=None) -> Plan:
    """The plan of the choice `top_idx` (T, top_k) over `n_experts` experts
    on one shard, of which `held` = (first, count) are here (None: all): a
    stable sort of the T * top_k token-choices by expert and the count an
    expert. It depends on the choice alone, so whoever has the choice can
    make it, beside whatever the layer computes before its experts. A share
    of every expert is no share, as in `moe_ffn`."""
    T, top_k = top_idx.shape
    if held is not None and held[1] == n_experts:
        held = None
    # token-choice c = t * top_k + j; `order` lists them by expert
    flat = top_idx.reshape(T * top_k)
    group, back = flat, None
    if held is not None:
        # the held experts' groups first, in their order, then
        # every token-choice that fell elsewhere
        first, count = held
        group = jnp.where((flat >= first) & (flat < first + count),
                          flat - first, count)
    order = jnp.argsort(group, stable=True)
    if held is None:
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(T * top_k, dtype=order.dtype))  # order's inverse
    return Plan(order, back, _count_choices(flat, n_experts))


def _aux(logits, probs, counts, chosen, biased: bool = False) -> MoeAux:
    """The router's losses from its own choices (`counts` of them an
    expert, kept or not), and where a selection bias made them (`biased`)
    how many it changed."""
    E = probs.shape[-1]
    share = counts.astype(jnp.float32) / chosen.size
    z = jax.nn.logsumexp(logits, axis=-1)
    return MoeAux(E * jnp.sum(share * jnp.mean(probs, axis=0)),
                  jnp.mean(jnp.square(z)), counts, chosen,
                  bias_moved(probs, chosen) if biased else None)


def _chunk_groups(T: int, top_k: int, chunk: int, sizes, i, whole: bool):
    """The groups of rows i * chunk to (i + 1) * chunk of a share's row
    order: the part of each held expert's group (`sizes` of them) that lies
    in the chunk, and in a chunk that runs `whole` the rows of no group as
    the last group's, up to the chunk's end."""
    ends = jnp.cumsum(sizes)
    lo = i * chunk
    here = (jnp.clip(ends, lo, lo + chunk)
            - jnp.clip(ends - sizes, lo, lo + chunk))
    if whole and chunk >= T * min(top_k, sizes.shape[0]):
        # one chunk of all that can fall here starts at row 0 and ends past
        # every group: the same number with nothing to clip
        here = here.at[-1].add(chunk - ends[-1])
    elif whole:
        here = here.at[-1].add(lo + chunk - jnp.clip(ends[-1], lo, lo + chunk))
    return here


def _chunk_part(expert_fn, top_k: int, chunk: int, x, gate, experts, order,
                sizes, i, whole: bool = False):
    """What rows i * chunk to (i + 1) * chunk of a share's row order add to
    the layer: (T, D) float32. Rows past the last group are token-choices
    that fell elsewhere: they weigh nothing, in both passes (the grouped
    matmul leaves whatever it finds in a row of no group). A row is weighed
    by its gate where it lies. The grouped matmul costs what its groups hold
    (5.8 to 16.6 ms a step with the rows that came: PERF.md, PR 41), so the
    groups are the rows that came, and the rows are taken and added back as
    far as the last that came (`ops/row_moves.py`: the movement ends at its
    last live row tile, as the products between do) and the chunk costs
    them; but in a chunk that runs `whole` (`moe_ffn`: a share under a
    selection bias) the rows of no group go in as zeros and are the last
    group's, up to the chunk's end, XLA's gather and scatter-add move every
    row, and the chunk costs its buffer whatever came."""
    T, D = x.shape
    lo = i * chunk
    mine = lax.dynamic_slice(order, (lo,), (chunk,))
    token = mine // top_k  # token-choice c = t * top_k + j reads token t
    if not whole:
        live = jnp.clip(jnp.sum(sizes) - lo, 0, chunk)
        here = _chunk_groups(T, top_k, chunk, sizes, i, whole)
        with jax.named_scope("moe_dispatch"):
            rows = take_rows(x, token, live)
        with jax.named_scope("moe_experts"):
            y = expert_fn(rows, experts, here)
        with jax.named_scope("moe_combine"):
            return add_rows(y, token, live, T, gate.reshape(T * top_k)[mine])
    live = (lo + jnp.arange(chunk) < jnp.sum(sizes))[:, None]
    here = _chunk_groups(T, top_k, chunk, sizes, i, whole)
    with jax.named_scope("moe_dispatch"):
        rows = jnp.where(live, x[token], 0)
    with jax.named_scope("moe_experts"):
        y = expert_fn(rows, experts, here)
    with jax.named_scope("moe_combine"):
        weight = jnp.where(live, gate.reshape(T * top_k, 1)[mine], 0)
        y = jnp.where(live, y.astype(jnp.float32) * weight, 0)
        return jnp.zeros((T, D), jnp.float32).at[token].add(y)


def _live_chunks(top_k: int, chunk: int, T: int, sizes):
    """Chunks of `chunk` rows that the held groups `sizes` fill: none where
    nothing came. But one chunk of all T * min(top_k, held) rows that can
    fall here is run once whatever came, none included: a loop of no or one
    turn is no loop (as a `while` the SmallThinker cell's chunk of all
    98,304 rows read 15 ms a step and 0.78 GB more than unrolled: PERF.md,
    PR 66), and under a selection bias, where `_share_chunk` makes that one
    chunk of two, the count is to be no data. Without a bias `_share_chunk`
    gives half of those rows at the most (PR 68), so but for a share of a
    handful of rows that chunk is the selection bias's. `sizes` may be
    numpy's."""
    if chunk >= T * min(top_k, sizes.shape[0]):
        return 1
    return (sizes.sum() + chunk - 1) // chunk


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _held_part(expert_fn, top_k: int, chunk: int, whole: bool, x, gate,
               experts, order, sizes):
    """The held experts' part of the layer, -> out (T, D). `order` lists the
    token-choices with those for the held experts first, group by group
    (`sizes` (held,) of them an expert). Dropless whatever the load: the
    rows are taken `chunk` at a time, as many chunks as the groups fill
    (`_live_chunks`), up to all T * min(top_k, held) rows that can fall
    here. So the work is that of the rows that came, and the memory that of
    one chunk (PERF.md, PR 33); `whole`, what `moe_ffn` says of a share
    under a selection bias, makes the work that of the chunks the rows
    fill (`_chunk_part`).
    The loop's length is data, so the backward pass is written out: the
    same loop, each chunk run again and transposed (nothing is kept but the
    arguments), its cotangents added up in float32."""
    out = lax.fori_loop(
        0, _live_chunks(top_k, chunk, x.shape[0], sizes),
        lambda i, out: out + _chunk_part(expert_fn, top_k, chunk, x, gate,
                                         experts, order, sizes, i, whole),
        jnp.zeros(x.shape, jnp.float32))
    return out.astype(x.dtype)


def _held_part_fwd(expert_fn, top_k, chunk, whole, x, gate, experts, order,
                   sizes):
    return (_held_part(expert_fn, top_k, chunk, whole, x, gate, experts, order,
                       sizes),
            (x, gate, experts, order, sizes))


def _held_part_bwd(expert_fn, top_k, chunk, whole, res, g):
    x, gate, experts, order, sizes = res
    g = g.astype(jnp.float32)

    def one(i, sums):
        _, transpose = jax.vjp(
            lambda *a: _chunk_part(expert_fn, top_k, chunk, *a, order, sizes, i,
                                   whole),
            x, gate, experts)
        return jax.tree.map(lambda s, d: s + d.astype(jnp.float32), sums,
                            transpose(g))

    sums = lax.fori_loop(
        0, _live_chunks(top_k, chunk, x.shape[0], sizes), one,
        jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                     (x, gate, experts)))
    dx, dgate, dexperts = jax.tree.map(lambda s, a: s.astype(a.dtype), sums,
                                       (x, gate, experts))
    return dx, dgate, dexperts, None, None


_held_part.defvjp(_held_part_fwd, _held_part_bwd)


def _share_chunk(T: int, top_k: int, held: int, n_experts: int,
                 biased: bool = False) -> int:
    """Rows to a chunk of a share's row order: four times what a balanced
    router sends to `held` of `n_experts` experts, so that one chunk is
    the usual case and a step's work does not move with the routing (a
    router with no balancing loss, trained 80 steps on the Laguna cell's
    pool, sends its held experts up to 2.6 times the balanced load, from
    1.0 at the start: PERF.md, PR 33); never more than can fall here. But
    under a selection bias (`biased`), where two such chunks hold all that
    can fall here (a share of an eighth of the experts or more), one chunk
    of all of it: such routers, whose balance is a step's that this program
    does not run, follow a vector the positions share and send a share none
    of a batch's rows or most of them, and a loop of no, one or two chunks
    is a step of three lengths (8 of 64 held behind full attention:
    PERF.md, PR 41). One chunk runs whatever came (`_live_chunks`). Without
    a bias a share's work is that of the rows that came and its step follows
    them: softmax routers with none swing as far under the cells' traffic
    (a layer's held rows between nothing and four balanced loads from one
    step to the next: PERF.md, PR 66), and what steadies such a cell is its
    traffic, not a third chunk rule. So without a bias the chunk is never
    more than half of what can fall here: where four balanced loads are all
    of it (a share of a quarter of the experts or more) the chunk is no
    chunk, it runs whole whatever came, and the dispatch and combine around
    the grouped matmuls, which cost the chunk's rows and not the groups',
    follow nothing (16 of 64 held: 98,304 rows moved a layer where 24,576
    came, PERF.md, PR 68)."""
    most = T * min(top_k, held)
    usual = -(-4 * T * top_k * held // n_experts)
    usual = -(-usual // 8) * 8
    if biased:
        return most if most <= 2 * usual else min(most, usual)
    return min(most, usual, -(-most // 16) * 8)


def moe_ffn(x, router_w, experts, axis_name: str = None, axis_size: int = 1,
            top_k: int = 1, capacity_factor: float = 1.25,
            gates=switch_gates, expert_fn=gelu_experts, held=None,
            scores: str = "softmax", bias=None, routing: Routing = None):
    """x (T, D) tokens on this shard; router_w (D, E); `experts` a tuple of
    THIS device's expert weight stacks (leading dim = experts per device,
    epd; E = axis_size * epd), handed to `expert_fn(rows, experts,
    group_sizes)`. Returns (out (T, D), MoeAux) — out holds nothing of a
    dropped token-choice (the caller adds the residual). `capacity_factor`
    matters only where `axis_size > 1`. With an axis, runs INSIDE a
    shard_map over it, and `load_balance` / `z_loss` are its means.

    `held` = (first, count), on one shard: the router is of any width E and
    this shard's epd = count experts are numbers first to first + count - 1
    of them, one chip's share of a layer whose experts lie on several. The
    router, its top_k and `gates` are over all E; `out` is the part of the
    layer that the held experts give, nothing of the token-choices that
    fell elsewhere (no exchange is made and nothing stands in for one).
    Dropless: up to T * min(top_k, count) rows, the most that can fall
    here, are computed, a chunk at a time (`_held_part`), and `counts` is
    of the held experts.

    `scores` and `bias` are the router's rule (`route`): softmax or sigmoid
    scores, and a selection bias (E,) that moves the choice alone; `gates`
    sees the chosen experts' scores. A share's (`held`) work is its live
    rows' unless a bias says otherwise: without one the grouped matmuls
    get the groups that came, in chunks of four balanced loads and of half
    of what can fall here at the most, as many as the rows fill, and the
    rows are taken and added back as far as the last that came
    (`ops/row_moves.py`). Under a
    bias every chunk the rows reach is computed whole, the rows of no group
    as zeros in the last group, and a share of an eighth or more is one
    chunk of all that can fall here, run whatever came: its cost is its
    chunks', not its rows' (PERF.md, PR 43, PR 66).

    `routing`: a `Routing` made elsewhere, in any of the three layouts: the
    experts then transform x under a choice and gates that `route` made of
    other rows (a router that reads the layer's input, ahead of the mixer),
    `router_w` says the router's width alone, and the gates' derivative goes
    where the routing came from. Its `plan`, where it has one, is the order
    this function would have sorted out of the same choice."""
    if top_k < 1:
        raise ValueError(f"top_k must be at least 1, got {top_k}")
    T, D = x.shape
    epd = experts[0].shape[0]
    E = axis_size * epd
    if held is not None:
        first, count = held
        E = router_w.shape[-1]
        if axis_size != 1 or count != epd or not 0 <= first <= E - count:
            raise ValueError(
                f"held {held}: on one shard, {epd} experts held of the "
                f"router's {E}; across an expert axis the exchange decides")
        if count == E:  # every expert is here: the layer as it always was
            held = None
    if router_w.shape[-1] != E:
        raise ValueError(
            f"router width {router_w.shape[-1]} != axis_size*epd = {E}"
        )
    if top_k > E:
        raise ValueError(f"top_k {top_k} exceeds the {E} experts")
    if routing is not None and routing.route[3].shape != (T, top_k):
        raise ValueError(
            f"a routing of {routing.route[3].shape} choices for {T} rows "
            f"and {top_k} experts a row")

    with jax.named_scope("moe_router"):
        logits, probs, top_probs, top_idx = (
            route(x, router_w, top_k, scores, bias) if routing is None
            else routing.route)
        gate = gates(top_probs)  # (T, top_k), float32

    if axis_size == 1:
        if routing is None or routing.plan is None:
            with jax.named_scope("moe_dispatch"):
                order, back, counts = dispatch_plan(top_idx, E, held)
        else:
            order, back, counts = routing.plan
        if held is not None:
            sizes = counts[first:first + count]
            # Under a selection bias the layer's balance is a step's that
            # no gradient makes and this program does not run (ROADMAP
            # S19): from the initial parameters such routers send a share
            # a third of the balanced load or three times it within a few
            # steps, and the grouped matmul costs the rows its groups hold.
            # There a live chunk costs its buffer whatever came, and a
            # share of an eighth or more is one chunk; anywhere else the
            # share's work is that of the rows that came.
            whole = bias is not None
            chunk = _share_chunk(T, top_k, count, E, whole)
            # every chunk of the rows that can fall here lies inside `order`
            n = -(-T * min(top_k, count) // chunk) * chunk
            order = jnp.pad(order, (0, max(0, n - T * top_k)))
            out = _held_part(expert_fn, top_k, chunk, whole, x, gate,
                             experts, order, sizes)
            with jax.named_scope("moe_router"):
                aux = _aux(logits, probs, counts, top_idx,
                           bias is not None)._replace(counts=sizes)
            return out, aux
        with jax.named_scope("moe_dispatch"):
            rows = _dispatch_rows(x, order, back, top_k)  # (T * top_k, D)
        with jax.named_scope("moe_experts"):
            y = expert_fn(rows, experts, counts)
        with jax.named_scope("moe_combine"):
            y = _permute_rows(y, back, order).reshape(T, top_k, D)
            out = jnp.sum(y.astype(jnp.float32) * gate[:, :, None],
                          axis=1).astype(x.dtype)
        with jax.named_scope("moe_router"):
            aux = _aux(logits, probs, counts, top_idx, bias is not None)
        return out, aux

    C = max(1, int(capacity_factor * T / E))  # per (shard, choice) capacity
    K = top_k * C  # bucket slots per expert on the wire
    with jax.named_scope("moe_dispatch"):
        send = jnp.zeros((E, K, D), x.dtype)
        scat = []
        for j in range(top_k):
            expert_j = top_idx[:, j]  # (T,)
            onehot = jax.nn.one_hot(expert_j, E, dtype=jnp.int32)
            pos = jnp.cumsum(onehot, axis=0) * onehot
            slot = jnp.sum(pos, axis=-1) - 1  # 0-based within (expert, choice)
            kept = slot < C
            se = jnp.where(kept, expert_j, 0)
            sc = jnp.where(kept, j * C + slot, 0)
            send = send.at[se, sc].add(jnp.where(kept[:, None], x, 0),
                                       mode="drop")
            scat.append((se, sc, kept))
        # a choice over capacity names no expert
        within = jnp.stack([kept for _, _, kept in scat], axis=1)
        counts = _count_choices(jnp.where(within, top_idx, E), E)
        # exchange: group bucket rows by destination DEVICE (expert e lives
        # on device e // epd at local index e % epd)
        send = send.reshape(axis_size, epd, K, D)
        recv = lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)  # (axis_size, epd, K, D)
    with jax.named_scope("moe_experts"):
        # the local experts over equal groups: every peer's K slots each
        rows = recv.transpose(1, 0, 2, 3).reshape(epd * axis_size * K, D)
        y = expert_fn(rows, experts,
                      jnp.full((epd,), axis_size * K, jnp.int32))
        y = y.reshape(epd, axis_size, K, D).transpose(1, 0, 2, 3)
    with jax.named_scope("moe_combine"):
        back = lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)
        back = back.reshape(E, K, D)  # my tokens' results, per (expert, slot)
        out = jnp.zeros((T, D), x.dtype)
        for j, (se, sc, kept) in enumerate(scat):
            got = jnp.where(kept[:, None], back[se, sc], 0)
            out = out + got.astype(x.dtype) * gate[:, j, None].astype(x.dtype)
    with jax.named_scope("moe_router"):
        # the losses see the router's choices, kept or not
        aux = _aux(logits, probs, _count_choices(top_idx, E), top_idx,
                   bias is not None)
        aux = aux._replace(load_balance=lax.pmean(aux.load_balance, axis_name),
                           z_loss=lax.pmean(aux.z_loss, axis_name),
                           counts=counts)
    return out, aux


def switch_moe(x, router_w, w_in, w_out, axis_name: str, axis_size: int,
               capacity_factor: float = 1.25):
    """Top-1 switch MoE with one expert per device (the round-4 surface):
    w_in (D, F), w_out (F, D). Returns (out, load-balancing loss). See
    `moe_ffn` for the general form."""
    out, aux = moe_ffn(
        x, router_w, (w_in[None], w_out[None]), axis_name, axis_size,
        top_k=1, capacity_factor=capacity_factor,
    )
    return out, aux.load_balance
