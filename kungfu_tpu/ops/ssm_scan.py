"""The state-space scan of a Mamba-2 layer in Pallas kernels, forward and
backward: the second rule behind `ops.gated_delta`'s chunking (the causal
depthwise convolution that stands before it is that module's, with a bias).

The definition is a diagonal recurrence over the sequence with an (N, P)
state a head (arXiv:2405.21060, Mamba-2's state-space duality), S_0 = 0:

    S_t = exp(g_t) S_{t-1} + k_t v_t^T
    o_t = S_t^T q_t

with q = C and k = B (N features a position, one set for every head of a
group), v = Delta x (P features a head) and g = Delta A <= 0 a head and
position. It is the gated delta rule without its correction: in that module's
notation T = I and U = V, so with G the running sum of g inside a chunk of C
positions, S the state at the chunk's start and d_ij = exp(G_i - G_j):

    O  = (exp(G) Q) S + P V,              P_ij = d_ij q_i.k_j         (j <= i)
    S+ = exp(G_C) S + (exp(G_C - G) K)^T V

No system to solve, no beta, no pass before the kernels. Two kernels on the
delta rule's grid (batch, blocks of heads, blocks of chunks; the last axis in
sequence with each head's float32 state in VMEM scratch), Mosaic where the
program is lowered for the TPU and the same kernels interpreted anywhere else
(`gated_delta._on_platform`):

1. `_forward_kernel`: a grid step reads its positions of q and k once for its
   heads, which are of one group (q and k are (B, groups, S, N) in HBM and
   never repeated a head: the index map sends a block of heads to its group),
   makes q.k^T once a chunk and then, head by head, the decays, P, the output
   and the next state, and writes o and the state each chunk starts from.
2. `_backward_kernel`, the same grid last block to first with dS in scratch:
   each chunk's decays and products are made again from q, k, v, g and the
   kept state, and dv, dg and the block of heads' sum of dq and of dk (float32)
   leave it; the blocks of a group are added up outside.

The matrix products take their operands in the type q, k, v come in
(bfloat16 in the model, so float32 inputs give a float32 computation) and
accumulate in float32; g is float32, every decay is an exponential of a
difference G_i - G_j <= 0 taken in float32 (nothing is divided by a decay, so
a strong one underflows to 0 and nothing overflows), and the state is float32.
The backward pass keeps the four inputs and the chunk-boundary states (B H
S/C N P float32: 0.13 GB a layer of 64 heads of 128 x 64 at 8,192 positions
in chunks of 128). No state a position exists in either pass.

Where a scan is traced the static counters `kungfu_ssm_chunks_total{pass}`
and the gauge `kungfu_ssm_kept_state_bytes` say what it will run and keep
(docs/telemetry.md). `models/transformer.py` runs it as the core of a layer
whose `mixer` is `"mamba2"`, under the scope `ssm_core`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kungfu_tpu.ops.gated_delta import (_NT, _PARAMS, _TN, BLOCK_HEADS,
                                        _block_chunks, _dot, _on_platform,
                                        _rows, _to_column, _to_row)
from kungfu_tpu.ops.kernel_call import kernel_call

CHUNK = 128  # Mamba-2's published chunk_size; the result does not depend on it


def _shared(q, k, marks=None):
    """What a chunk's heads share: q, k (C, N) in their type -> the masks
    and q.k^T (C, C) float32. `marks`, of a packed sequence, are the chunk's
    three rows of `_marks`, (1, C) each: `within`, key j is no later than
    query i and of its document; as columns `reads`, the position is of the
    document the chunk's first state is of, and `ends`, it is of the
    document the chunk ends in; `carries`, that state outlives the chunk."""
    C = q.shape[0]
    at_row = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    at_col = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    x = dict(lower=at_col <= at_row, eye=at_col == at_row,
             qk=_dot(q, k, _NT), q32=q.astype(jnp.float32),
             k32=k.astype(jnp.float32))
    if marks is not None:
        document, reads, ends = marks
        x.update(within=x["lower"] & (_to_column(document, x["eye"]) == document),
                 reads=_to_column(reads, x["eye"]),
                 ends=_to_column(ends, x["eye"]),
                 carries=jnp.min(reads, axis=1, keepdims=True))
    return x


def _head(x, g, dtype):
    """One head's part of a chunk: g (1, C) float32 as a row -> its decays
    and the operands they scale, every decay an exponential of a difference
    of running sums, in float32. Of a packed sequence (`_shared`'s marks) a
    decay that would cross a document's first position is 0, exactly: the
    state is zero before it. The running sums run on across it, and a
    difference of two inside one document is that document's own."""
    lower, eye = x["lower"], x["eye"]
    G = jnp.sum(jnp.where(lower, g, 0.0), axis=1, keepdims=True)  # (C, 1)
    G_end = jnp.sum(g, axis=1, keepdims=True)  # (1, 1)
    decay = jnp.exp(jnp.where(x.get("within", lower), G - _to_row(G, eye),
                              -jnp.inf))
    eG, to_end, a = jnp.exp(G), jnp.exp(G_end - G), jnp.exp(G_end)
    if "within" in x:
        eG, to_end, a = eG * x["reads"], to_end * x["ends"], a * x["carries"]
    return dict(decay=decay, eG=eG, to_end=to_end, a=a,
                P=(decay * x["qk"]).astype(dtype),
                Qg=(x["q32"] * eG).astype(dtype),
                Kd=(x["k32"] * to_end).astype(dtype))


def _next_state(a, S, Kd, v):
    """S+ = exp(G_C) S + (exp(G_C - G) K)^T V: a (1, 1) and S (N, P) float32,
    Kd (C, N) and v (C, P) in their type."""
    return a * S + _dot(Kd, v, _TN)


def _chunk_marks(marks_ref, row):
    """A chunk's three rows of `_marks`, or None where the sequence is one
    document."""
    if marks_ref is None:
        return None
    return tuple(marks_ref[0, i, row, :] for i in range(3))


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, *refs, chunk: int):
    """A block of chunks of some heads of one group, first to last; each
    head's state in `S_scr` from one grid step to the next along the
    sequence, and in `states_ref` as each chunk starts from it, for the
    backward pass. `refs`: the marks of a packed sequence first, where there
    are any, then o, the states and the scratch."""
    *marks_ref, o_ref, states_ref, S_scr = refs
    marks_ref = marks_ref[0] if marks_ref else None

    @pl.when(pl.program_id(2) == 0)
    def _():
        S_scr[...] = jnp.zeros_like(S_scr)

    def one(c, carry):
        rows, row = _rows(c, chunk), pl.ds(c, 1)
        x = _shared(q_ref[0, 0, rows, :], k_ref[0, 0, rows, :],
                    _chunk_marks(marks_ref, row))
        for h in range(S_scr.shape[0]):  # independent chains, side by side
            S = S_scr[h]
            states_ref[0, h, c] = S
            v = v_ref[0, h, rows, :]
            y = _head(x, g_ref[0, h, row, :], v.dtype)
            o_ref[0, h, rows, :] = (_dot(y["Qg"], S.astype(v.dtype))
                                    + _dot(y["P"], v)).astype(o_ref.dtype)
            S_scr[h] = _next_state(y["a"], S, y["Kd"], v)
        return carry

    lax.fori_loop(0, g_ref.shape[2], one, None)


def _backward_kernel(q_ref, k_ref, v_ref, g_ref, states_ref, do_ref, *refs,
                     chunk: int):
    """The same block last chunk to first, the grid's blocks last to first
    (the index maps), the state's cotangent in `dS_scr`. Each chunk's local
    quantities are made again from the inputs and the kept state; dq and dk
    are this block of heads' sums, float32. `refs`: the marks of a packed
    sequence first, where there are any, then dq, dk, dv, dg and the scratch:
    every mask is a factor of a decay, so the cotangents below are the
    unpacked sequence's with the masked decays in them."""
    *marks_ref, dq_ref, dk_ref, dv_ref, dg_ref, dS_scr = refs
    marks_ref = marks_ref[0] if marks_ref else None
    blocks = g_ref.shape[2]

    @pl.when(pl.program_id(2) == 0)
    def _():
        dS_scr[...] = jnp.zeros_like(dS_scr)

    def lanes(t):
        return jnp.sum(t, axis=1, keepdims=True)

    def one(i, carry):
        c = blocks - 1 - i
        rows, row = _rows(c, chunk), pl.ds(c, 1)
        q, k = q_ref[0, 0, rows, :], k_ref[0, 0, rows, :]
        x = _shared(q, k, _chunk_marks(marks_ref, row))
        dq = jnp.zeros(q.shape, jnp.float32)
        dk = jnp.zeros(k.shape, jnp.float32)
        for h in range(dS_scr.shape[0]):  # independent chains, side by side
            v = v_ref[0, h, rows, :]
            dt = v.dtype
            y = _head(x, g_ref[0, h, row, :], dt)
            S, dS = states_ref[0, h, c], dS_scr[h]
            dO, dSd, Sd = do_ref[0, h, rows, :].astype(dt), dS.astype(dt), S.astype(dt)
            eG, to_end, a = y["eG"], y["to_end"], y["a"]
            dv_ref[0, h, rows, :] = (_dot(y["P"], dO, _TN)
                                     + _dot(y["Kd"], dSd)).astype(dv_ref.dtype)
            dS_scr[h] = a * dS + _dot(y["Qg"], dO, _TN)
            da = jnp.sum(lanes(S * dS), axis=0, keepdims=True)
            # the products' other operands
            dKd, dQg = _dot(v, dSd, _NT), _dot(dO, Sd, _NT)
            dP = _dot(dO, v, _NT)
            dqk = (dP * y["decay"]).astype(dt)
            dq = dq + eG * dQg + _dot(dqk, k)
            dk = dk + to_end * dKd + _dot(dqk, q, _TN)
            # the decays: every one an exponential of running sums of g
            d_to_end = lanes(dKd * x["k32"]) * to_end
            d_log = dP * x["qk"] * y["decay"]  # of exp(G_i - G_j), j <= i
            dG = (lanes(d_log)
                  - _to_column(jnp.sum(d_log, axis=0, keepdims=True), x["eye"])
                  + lanes(dQg * x["q32"]) * eG - d_to_end)
            dG_end = da * a + jnp.sum(d_to_end, axis=0, keepdims=True)
            dg_ref[0, h, row, :] = dG_end + jnp.sum(
                jnp.where(x["lower"], dG, 0.0), axis=0, keepdims=True)
        dq_ref[0, 0, rows, :] = dq
        dk_ref[0, 0, rows, :] = dk
        return carry

    lax.fori_loop(0, blocks, one, None)


def _block_heads(H: int, groups: int) -> int:
    """Heads a grid step: the most up to `BLOCK_HEADS` that divide a group's,
    so that a block of heads reads one group's q and k."""
    return max(h for h in range(1, BLOCK_HEADS + 1) if (H // groups) % h == 0)


def _specs(B, H, groups, S, N, P, chunk, back: bool):
    """Block specs by name for a grid (B, blocks of heads, blocks of
    chunks), the blocks in reverse for the backward pass. `group` is q's and
    k's: the group a block of heads belongs to; `sum` the block's own row of
    the (B, blocks of heads, S, N) sums of dq and dk."""
    n_chunks = S // chunk
    n = _block_chunks(n_chunks)
    last = n_chunks // n - 1
    heads = _block_heads(H, groups)
    per_group = H // groups // heads  # blocks of heads a group

    def at(*tail, group=False):
        def index(b, h, s):
            return ((b, h // per_group if group else h,
                     last - s if back else s) + tail)
        return index

    def marks(b, h, s):  # every head's alike
        return (b, 0, last - s if back else s, 0)

    return (B, H // heads, n_chunks // n), dict(
        marks=pl.BlockSpec((1, 3, n, chunk), marks),
        group=pl.BlockSpec((1, 1, n * chunk, N), at(0, group=True)),
        sum=pl.BlockSpec((1, 1, n * chunk, N), at(0)),
        v=pl.BlockSpec((1, heads, n * chunk, P), at(0)),
        row=pl.BlockSpec((1, heads, n, chunk), at(0)),
        state=pl.BlockSpec((1, heads, n, N, P), at(0, 0)),
        scratch=pltpu.VMEM((heads, N, P), jnp.float32))


def _marks(segments, chunk: int):
    """What the kernels read of a packed sequence, (B, 3, S / chunk, chunk)
    float32 (a whole number below 2^24 is one exactly) from `segments` (B,
    S), the number of each position's document, which never falls along the
    sequence: the number itself; 1 where the position is of the document
    that the last position before the chunk is of (none before the first
    chunk: the state is zero there anyway), so that it reads the state the
    chunk starts from; 1 where it is of the document the chunk's last
    position is of, so that what it writes is in the state the chunk ends
    with."""
    B, S = segments.shape
    document = segments.reshape(B, S // chunk, chunk)
    last = document[..., -1:]
    before = jnp.concatenate([jnp.full_like(last[:, :1], -1), last[:, :-1]],
                             axis=1)
    return jnp.stack([document, document == before, document == last],
                     axis=1).astype(jnp.float32)


def _forward(q, k, v, g, *marks, chunk: int, interpret: bool):
    """-> (o, the state at each chunk's start (B, H, S / chunk, N, P)
    float32). `marks`: `_marks` of a packed sequence, or nothing."""
    B, groups, S, N = q.shape
    H, P = v.shape[1], v.shape[-1]
    grid, spec = _specs(B, H, groups, S, N, P, chunk, back=False)
    return kernel_call(
        functools.partial(_forward_kernel, chunk=chunk),
        grid=grid,
        in_specs=[spec["group"], spec["group"], spec["v"], spec["row"]]
        + [spec["marks"]] * len(marks),
        out_specs=[spec["v"], spec["state"]],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((B, H, S // chunk, N, P), jnp.float32)],
        scratch_shapes=[spec["scratch"]],
        interpret=interpret, name="ssm_scan_forward", **_PARAMS,
    )(q, k, v, g.reshape(B, H, S // chunk, chunk), *marks)


def _backward(q, k, v, g, states, do, *marks, chunk: int, interpret: bool):
    """-> (dq, dk (B, blocks of heads, S, N) float32, a block of heads'
    sum each; dv; dg). `marks` as `_forward`'s."""
    B, groups, S, N = q.shape
    H, P = v.shape[1], v.shape[-1]
    grid, spec = _specs(B, H, groups, S, N, P, chunk, back=True)
    rows = (B, H, S // chunk, chunk)
    sums = jax.ShapeDtypeStruct((B, grid[1], S, N), jnp.float32)
    dq, dk, dv, dg = kernel_call(
        functools.partial(_backward_kernel, chunk=chunk),
        grid=grid,
        in_specs=[spec["group"], spec["group"], spec["v"], spec["row"],
                  spec["state"], spec["v"]] + [spec["marks"]] * len(marks),
        out_specs=[spec["sum"], spec["sum"], spec["v"], spec["row"]],
        out_shape=[sums, sums, jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(rows, jnp.float32)],
        scratch_shapes=[spec["scratch"]],
        interpret=interpret, name="ssm_scan_backward", **_PARAMS,
    )(q, k, v, g.reshape(rows), states, do, *marks)
    return dq, dk, dv, dg.reshape(g.shape)


def _count(which: str, B: int, H: int, S: int, chunk: int, N: int, P: int):
    """At trace time, what a run of the pass being built does, added to
    `kungfu_ssm_chunks_total{pass}` (chunks over batch and heads: a sum over
    the scans traced, not over their runs), and, for the forward pass, what
    the scan traced last keeps between its passes, as the gauge
    `kungfu_ssm_kept_state_bytes` (docs/telemetry.md)."""
    from kungfu_tpu.telemetry import metrics

    metrics.counter(
        "kungfu_ssm_chunks_total",
        "chunks a run of each state-space scan pass traced so far computes, "
        "over batch and heads", ("pass",)).labels(which).inc(B * H * (S // chunk))
    if which == "forward":
        metrics.gauge(
            "kungfu_ssm_kept_state_bytes",
            "the float32 chunk-boundary states that the state-space scan "
            "traced last keeps for its backward pass").set(
                B * H * (S // chunk) * N * P * 4)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def ssm_scan(q, k, v, g, chunk: int = CHUNK, segments=None):
    """q, k (B, groups, S, N), v (B, H, S, P), g (B, H, S) float32, the log
    decay <= 0 -> o (B, H, S, P) in v's type: the recurrence of the module's
    head, S_0 = 0, in its chunkwise form; head j reads q and k of group j //
    (H / groups). `chunk` is a power of two that divides S, or this raises.
    `segments` (B, S) whole numbers that never fall along the sequence, where
    given, number each position's document: the state is zero before a
    document's first position, wherever in a chunk it stands, so a packed row
    is its documents run one at a time, values and gradients."""
    return _fwd(q, k, v, g, chunk, segments)[0]


def _fwd(q, k, v, g, chunk, segments=None):
    """-> (o, what the backward pass keeps: the inputs, the states at the
    chunks' starts and the marks of a packed sequence)."""
    (B, groups, S, N), (_, H, _, P) = q.shape, v.shape
    if chunk & (chunk - 1) or S % chunk:
        raise ValueError(f"ssm_scan: the sequence length {S} is no multiple "
                         f"of the chunk {chunk}, a power of two")
    if H % groups:
        raise ValueError(f"ssm_scan: {H} heads are no multiple of {groups} "
                         "groups")
    _count("forward", B, H, S, chunk, N, P)
    marks = () if segments is None else (_marks(segments, chunk),)
    o, states = _on_platform(_forward, q, k, v, g, *marks, chunk=chunk)
    return o, (q, k, v, g, states, marks)


def _bwd(chunk, res, do):
    q, k, v, g, states, marks = res
    (B, groups, S, N), (_, H, _, P) = q.shape, v.shape
    _count("backward", B, H, S, chunk, N, P)
    dq, dk, dv, dg = _on_platform(_backward, q, k, v, g, states, do, *marks,
                                  chunk=chunk)

    def of_group(d):  # a group's blocks of heads, added up
        return jnp.sum(d.reshape(B, groups, -1, S, N), axis=2).astype(q.dtype)

    return of_group(dq), of_group(dk), dv, dg, None


ssm_scan.defvjp(_fwd, _bwd)
