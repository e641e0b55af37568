"""`ops.short_conv`: the kernels of LFM2's gated short convolution against the
plain `jnp` form of the same arithmetic under `jax.grad`: the values and both
gradients, a sequence of one row block and of several, float32 and bfloat16,
and what takes the plain form: packed rows (with a document's boundary where
a block's edge would be and elsewhere), other taps, channels that tile no
lane."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kungfu_tpu.ops import short_conv as module
from kungfu_tpu.ops.short_conv import plain, short_conv

CASES = {
    # B, S, D, K, dtype, row blocks a sequence, packed, path
    "f32_one_block": (2, 32, 128, 3, jnp.float32, 1, False, "kernel"),
    "f32_four_blocks": (2, 64, 128, 3, jnp.float32, 4, False, "kernel"),
    "bf16_two_blocks": (1, 64, 256, 3, jnp.bfloat16, 2, False, "kernel"),
    "three_lane_tiles": (1, 48, 384, 3, jnp.float32, 3, False, "kernel"),
    "f32_packed": (2, 64, 128, 3, jnp.float32, 4, True, "plain"),
    "four_taps_packed": (1, 32, 128, 4, jnp.float32, 2, True, "plain"),
    "channels_that_tile_no_lane_packed": (2, 24, 64, 3, jnp.float32, 1, True,
                                          "plain"),
}


def _segments(B, S):
    """Row 0: documents that end inside a row block of 16 (position 9), on a
    block's edge (31, so that position 32 is a document's first), one of a
    single position (32) and one that spans a block and more; the other rows
    two documents each."""
    ends = np.zeros((B, S), bool)
    ends[0, [e for e in (9, 31, 32) if e < S - 1]] = True
    ends[1:, S // 2 + 3] = True
    behind = np.pad(ends[:, :-1], ((0, 0), (1, 0)))
    return jnp.asarray(np.cumsum(behind, axis=1), jnp.int32)


def _inputs(B, S, D, K, dtype):
    rng = np.random.default_rng(S * D + K)

    def normal(*shape, dtype=jnp.float32):
        return jnp.asarray(rng.standard_normal(shape, np.float32), dtype)

    return normal(B, S, 3 * D, dtype=dtype), normal(K, D), normal(B, S, D)


def _both(op, bcx, taps, weight, segments):
    """-> (y, the gradients of sum(y * weight)), one program."""
    def loss(bcx, taps):
        y = op(bcx, taps, segments)
        return jnp.sum(y.astype(jnp.float32) * weight), y

    (_, y), grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                               has_aux=True))(bcx, taps)
    return y, grads


def _off(got, want):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("case", CASES)
def test_values_and_both_gradients_equal_the_plain_forms(case, monkeypatch,
                                                         fresh_traces):
    B, S, D, K, dtype, blocks, packed, path = CASES[case]
    segments = _segments(B, S) if packed else None
    assert module.tiles(S, D, K, segments) == (path == "kernel")
    # a budget that gives the sequence this many row blocks in both passes
    # (`fresh_traces`: the kernels' builders are jitted and keep their traces)
    monkeypatch.setattr(module, "_block_rows", lambda S, row_bytes: S // blocks)
    bcx, taps, weight = _inputs(B, S, D, K, dtype)
    y, (dbcx, dtaps) = _both(short_conv, bcx, taps, weight, segments)
    want, (want_dbcx, want_dtaps) = _both(plain, bcx, taps, weight, segments)
    assert y.shape == (B, S, D) and y.dtype == dbcx.dtype == dtype
    assert dtaps.shape == (K, D) and dtaps.dtype == jnp.float32
    # float32 inside both: a bfloat16 result differs in a last bit here and
    # there, where the two forms' sums were rounded apart
    rtol = 2e-6 if dtype == jnp.float32 else 1e-3
    assert _off(y, want) <= rtol
    assert _off(dbcx, want_dbcx) <= rtol
    assert _off(dtaps, want_dtaps) <= 2e-6
    assert float(jnp.abs(dtaps).min()) > 0


def test_a_packed_row_is_its_documents_run_one_at_a_time(fresh_traces,
                                                        monkeypatch):
    monkeypatch.setattr(module, "_block_rows", lambda S, row_bytes: 16)
    B, S, D, K = 1, 64, 128, 3
    bcx, taps, _ = _inputs(B, S, D, K, jnp.float32)
    segments = _segments(B, S)
    packed = short_conv(bcx, taps, segments)
    starts = [0, 10, 32, 33, S]
    for lo, hi in zip(starts, starts[1:]):
        alone = jnp.zeros_like(bcx).at[:, :hi - lo].set(bcx[:, lo:hi])
        np.testing.assert_allclose(short_conv(bcx=alone, taps=taps)[:, :hi - lo],
                                   packed[:, lo:hi], rtol=1e-6, atol=1e-6)
    one = short_conv(bcx, taps)
    np.testing.assert_allclose(one[:, :10], packed[:, :10], rtol=1e-6, atol=1e-6)
    assert _off(one[:, 10:12], packed[:, 10:12]) > 0.1


def test_the_products_are_float32_whatever_the_arrays_type():
    """On bfloat16 arrays the kernels are the float32 form rounded once; the
    same op with its products rounded to bfloat16 as they are made is several
    times further from it."""
    B, S, D, K = 1, 64, 128, 3
    bcx, taps, _ = _inputs(B, S, D, K, jnp.bfloat16)
    exact = plain(bcx.astype(jnp.float32), taps)
    low = jnp.bfloat16
    b, c, x = (bcx[..., i * D:(i + 1) * D] for i in range(3))
    z = jnp.pad(b * x, ((0, 0), (K - 1, 0), (0, 0)))
    rounded = c * sum(taps[i].astype(low) * z[:, i:i + S] for i in range(K))
    mine = _off(short_conv(bcx, taps), exact)
    assert mine < 3e-3 and 1.5 * mine < _off(rounded, exact)


def test_the_blocks_are_the_largest_that_fit_the_budget():
    assert module._block_rows(8192, 4 * 2048 * 2) == 512
    assert module._block_rows(8192, 7 * 2048 * 2) == 256
