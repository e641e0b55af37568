"""`ops.ssm_conv`: the kernels of a Mamba-2 mixer's convolution, silu and v =
Delta x against `plain`, today's composition, under `jax.grad`: the values and
the four gradients at both cells' `ssm_dims`, one document a row and packed
(a boundary inside a row block, on a block's edge and within K - 1 rows of
one), what takes the plain form, and the counter that says which path a pass
took."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kungfu_tpu.ops import ssm_conv as module
from kungfu_tpu.ops.ssm_conv import document_marks, plain, ssm_conv
from kungfu_tpu.telemetry import metrics

K = 4
GRANITE, NEMOTRON = (64, 64, 128, 1), (64, 64, 128, 8)  # the cells' `ssm_dims`
NAMES = ("dzxbc", "dtaps", "dbias", "ddelta")

CASES = {
    # (H, P, N, G), S, dtype, row blocks a sequence, packed, path
    "granite_f32": (GRANITE, 32, jnp.float32, 2, False, "kernel"),
    "granite_packed_bf16": (GRANITE, 48, jnp.bfloat16, 3, True, "kernel"),
    "nemotron_bf16": (NEMOTRON, 32, jnp.bfloat16, 1, False, "kernel"),
    "nemotron_packed_f32": (NEMOTRON, 48, jnp.float32, 3, True, "kernel"),
    "heads_of_32_packed_f32": ((8, 32, 64, 2), 64, jnp.float32, 2, True, "kernel"),
    "three_taps": ((4, 64, 64, 1), 32, jnp.float32, 1, False, "plain"),
    "groups_that_tile_no_view_packed": ((4, 64, 96, 1), 32, jnp.float32, 1, True,
                                        "plain"),
    "a_sequence_of_no_whole_halo": ((4, 64, 64, 1), 24, jnp.float32, 1, False,
                                    "plain"),
}


def _segments(B, S):
    """Row 0: documents that end inside a row block of 16 (position 9), two
    rows before a block's edge (13: the block's last rows reach over it), on
    the edge (31, so that position 32 is a document's first) and a document
    of one position (32); the other rows two documents each."""
    ends = np.zeros((B, S), bool)
    ends[0, [e for e in (9, 13, 31, 32) if e < S - 1]] = True
    ends[1:, S // 2 + 3] = True  # rows of their own where there are any
    behind = np.pad(ends[:, :-1], ((0, 0), (1, 0)))
    return jnp.asarray(np.cumsum(behind, axis=1), jnp.int32)


def _inputs(dims, S, dtype, taps=K, B=2):
    H, P, N, G = dims
    inner, C = H * P, H * P + 2 * G * N
    rng = np.random.default_rng(S + C)

    def normal(*shape, dtype=jnp.float32):
        return jnp.asarray(rng.standard_normal(shape, np.float32), dtype)

    args = (normal(B, S, inner + C, dtype=dtype), 0.5 * normal(taps, C),
            0.1 * normal(C), jax.nn.softplus(normal(B, S, H)))
    return args, (normal(B, S, C), normal(B, H, S, P))


def _both(op, args, weights, segments):
    """-> ((xbc, v), the gradients of sum(xbc wx) + sum(v wv)), one program."""
    def loss(*a):
        xbc, v = op(*a, segments)
        return (jnp.sum(xbc.astype(jnp.float32) * weights[0])
                + jnp.sum(v.astype(jnp.float32) * weights[1])), (xbc, v)

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3), has_aux=True))(*args)
    return out, grads


def _off(got, want):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _rows(which):
    return {path: metrics.counter("kungfu_ssm_conv_rows_total", "",
                                  ("pass", "path")).labels(which, path).value
            for path in ("kernel", "plain")}


@pytest.mark.parametrize("case", CASES)
def test_values_and_the_four_gradients_equal_the_plain_forms(
        case, monkeypatch, fresh_traces):
    dims, S, dtype, blocks, packed, path = CASES[case]
    H, P, N, G = dims
    B, inner, C = (1 if H == 64 else 2), H * P, H * P + 2 * G * N
    taps = 3 if case == "three_taps" else K
    assert module.tiles(S, inner, C, H, taps) == (path == "kernel")
    # a budget that gives the sequence this many row blocks in both passes
    # (`fresh_traces`: the kernels' builders are jitted and keep their traces)
    monkeypatch.setattr(module, "_block_rows", lambda S, row_bytes: S // blocks)
    segments = _segments(B, S) if packed else None
    args, weights = _inputs(dims, S, dtype, taps, B)

    before = _rows("forward"), _rows("backward")
    (xbc, v), got = _both(ssm_conv, args, weights, segments)
    # the counter: B x S rows a traced pass, all under the path the shape takes
    other = "plain" if path == "kernel" else "kernel"
    forward, backward = _rows("forward"), _rows("backward")
    assert forward[path] - before[0][path] == B * S
    assert backward[path] - before[1][path] == B * S
    assert forward[other] == before[0][other]
    assert backward[other] == before[1][other]

    (want_xbc, want_v), want = _both(plain, args, weights, segments)
    assert xbc.shape == (B, S, C) and v.shape == (B, H, S, P)
    assert xbc.dtype == v.dtype == dtype
    for name, g, a in zip(NAMES, got, args, strict=True):
        assert g.shape == a.shape and g.dtype == a.dtype, name
    # z's columns are never read and have no gradient
    assert not np.any(np.asarray(got[0][..., :inner], np.float32))
    if dtype == jnp.float32:
        for name, g, w in zip(("xbc", "v") + NAMES, (xbc, v) + got,
                              (want_xbc, want_v) + want, strict=True):
            assert _off(g, w) <= 2e-6, name
    else:
        # float32 inside from the same bfloat16 inputs and one rounding where
        # `plain` (a convolution rounded, then a silu) has two: the kernels
        # are nearer the float32 form than `plain` is, and the two differ by
        # what `plain`'s roundings make
        exact, exact_grads = _both(
            plain, tuple(a.astype(jnp.float32) for a in args), weights, segments)
        for name, g, w, e in zip(("xbc", "v") + NAMES, (xbc, v) + got,
                                 (want_xbc, want_v) + want, exact + exact_grads,
                                 strict=True):
            assert _off(g, e) <= max(_off(w, e), 1e-6) * 1.05, name
            assert _off(g, w) <= 6e-3, name
    assert float(jnp.abs(got[1]).min()) > 0 and float(jnp.abs(got[3]).min()) > 0


def test_a_packed_row_is_its_documents_run_one_at_a_time(fresh_traces,
                                                        monkeypatch):
    monkeypatch.setattr(module, "_block_rows", lambda S, row_bytes: 16)
    dims, S = (2, 64, 64, 1), 64
    (zxbc, taps, bias, delta), _ = _inputs(dims, S, jnp.float32, B=1)
    segments = _segments(1, S)
    packed = ssm_conv(zxbc, taps, bias, delta, segments)
    starts = [0, 10, 14, 32, 33, S]
    for lo, hi in zip(starts, starts[1:]):
        alone = ssm_conv(jnp.zeros_like(zxbc).at[:, :hi - lo].set(zxbc[:, lo:hi]),
                         taps, bias,
                         jnp.zeros_like(delta).at[:, :hi - lo].set(delta[:, lo:hi]))
        for mine, theirs in zip(alone, packed):
            np.testing.assert_allclose(mine[..., :hi - lo, :], theirs[..., lo:hi, :],
                                       rtol=1e-6, atol=1e-6)
    one = ssm_conv(zxbc, taps, bias, delta)
    np.testing.assert_allclose(one[0][:, :10], packed[0][:, :10], rtol=1e-6,
                               atol=1e-6)
    assert _off(one[0][:, 10:12], packed[0][:, 10:12]) > 0.1
    # the marks the model makes once a step are the op's own
    marked = ssm_conv(zxbc, taps, bias, delta, segments, document_marks(segments))
    np.testing.assert_array_equal(marked[0], packed[0])
    assert np.asarray(document_marks(segments))[0, :16, 0].tolist() == [
        0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 0, 1, 2, 3, 0, 1]


def test_the_products_are_float32_whatever_the_arrays_type():
    """On bfloat16 arrays the kernels are the float32 form rounded once; the
    same op with its products rounded to bfloat16 as they are made is several
    times further from it."""
    dims, S = (2, 64, 64, 1), 64
    H, P, N, G = dims
    inner = H * P
    (zxbc, taps, bias, delta), _ = _inputs(dims, S, jnp.bfloat16, B=1)
    exact_xbc, exact_v = plain(zxbc.astype(jnp.float32), taps, bias, delta)
    low = jnp.bfloat16
    u = jnp.pad(zxbc[..., inner:], ((0, 0), (K - 1, 0), (0, 0)))
    a = sum(taps[i].astype(low) * u[:, i:i + S] for i in range(K)) + bias.astype(low)
    rounded = jax.nn.silu(a)
    x = rounded[..., :inner].reshape(1, S, H, P)
    rounded_v = (x * delta.astype(low)[..., None]).transpose(0, 2, 1, 3)
    xbc, v = ssm_conv(zxbc, taps, bias, delta)
    assert _off(xbc, exact_xbc) < 3e-3 and 1.5 * _off(xbc, exact_xbc) < _off(
        rounded, exact_xbc)
    assert _off(v, exact_v) < 4e-3 and 1.5 * _off(v, exact_v) < _off(
        rounded_v, exact_v)


@pytest.mark.parametrize("name,dims,packed", [
    ("granite_4_0_h_micro", GRANITE, True),
    ("nemotron_3_nano_30b_a3b", NEMOTRON, False)])
def test_the_cells_shapes_take_the_kernels_in_blocks_that_fit(name, dims, packed):
    """Both configurations' `ssm_dims` at 8,192 positions: the kernels' path,
    256 rows a block forward and 256 (one group) and 128 (eight) backward in
    bfloat16, 32 rows a turn of the loops, the heads' sums in 64 lanes that
    `_fold_order` names once each."""
    H, P, N, G = dims
    inner, C = H * P, H * P + 2 * G * N
    assert module.tiles(8192, inner, C, H, K)
    shapes = [jax.ShapeDtypeStruct(s, t) for s, t in (
        ((1, 8192, inner + C), jnp.bfloat16), ((K, C), jnp.float32),
        ((1, 8192, H), jnp.float32))]
    for passes, up, rows in (((2, 1), False, 256), ((3, 1), True, 256 // min(G, 2))):
        (_, blocks), spec = module._specs(*shapes, passes=passes, up=up)
        assert blocks == 8192 // rows and spec["xbc"].block_shape == (1, rows, C)
        assert module._row_step(rows) == 32
    assert sorted(module._fold_order(inner // 128, P)) == list(range(1, 128, 2))
