"""`ops.gated_norm`: the kernels of a Mamba-2 mixer's end (`+ D x`, the gate,
the grouped norm) against the plain `jnp` form of the same arithmetic under
`jax.grad`: the values and the five gradients, o given and do returned in the
scan's layout, x and z read as the first columns of wider arrays, and the
counter that says which path a pass took."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kungfu_tpu.ops import gated_norm as module
from kungfu_tpu.ops.gated_norm import gated_norm, plain
from kungfu_tpu.telemetry import metrics

EPS = 1e-5
NAMES = ("do", "dx", "dz", "dD", "dscale")

CASES = {
    # B, H, S, P, groups, dtype, row blocks a sequence, path
    "one_group_f32_one_block": (1, 8, 32, 16, 1, jnp.float32, 1, "kernel"),
    "one_group_bf16_four_blocks": (2, 8, 64, 16, 1, jnp.bfloat16, 4, "kernel"),
    "eight_groups_f32_two_blocks": (1, 16, 32, 64, 8, jnp.float32, 2, "kernel"),
    "eight_groups_bf16_one_block": (1, 16, 32, 64, 8, jnp.bfloat16, 1, "kernel"),
    "heads_of_a_lane_tile_f32_two_blocks": (1, 2, 32, 128, 2, jnp.float32, 2, "kernel"),
    "a_head_that_tiles_no_lane_f32": (1, 3, 24, 24, 1, jnp.float32, 1, "plain"),
    "a_group_of_half_a_lane_tile_bf16": (2, 4, 16, 16, 1, jnp.bfloat16, 1, "plain"),
}


def _inputs(B, H, S, P, dtype):
    """o in the scan's layout, x and z wider than H P by columns the op must
    neither read nor give a gradient, D a head, a scale a feature, and the
    weights of the sum the gradients are taken of."""
    rng = np.random.default_rng(H * P + S)

    def normal(*shape, dtype=jnp.float32):
        return jnp.asarray(rng.standard_normal(shape, np.float32), dtype)

    o = normal(B, H, S, P, dtype=dtype)
    x = normal(B, S, H * P + 128, dtype=dtype)
    z = normal(B, S, H * P + 256, dtype=dtype)
    scale = 1.0 + 0.1 * normal(H * P)
    return (o, x, z, normal(H), scale), normal(B, S, H * P)


def _rows(which):
    return {path: metrics.counter("kungfu_gated_norm_rows_total", "",
                                  ("pass", "path")).labels(which, path).value
            for path in ("kernel", "plain")}


@pytest.mark.parametrize("case", CASES)
def test_values_and_the_five_gradients_equal_the_plain_forms(
        case, monkeypatch, fresh_traces):
    B, H, S, P, groups, dtype, blocks, path = CASES[case]
    assert module.tiles(H, P, S, groups) == (path == "kernel")
    # a budget that gives the sequence this many row blocks in both passes
    # (`fresh_traces`: the kernels' builders are jitted and keep their traces)
    monkeypatch.setattr(module, "_block_rows", lambda S, row_bytes: S // blocks)
    args, weight = _inputs(B, H, S, P, dtype)

    def both(op):
        """-> (y, the gradients of sum(y * weight)), one program."""
        def loss(*a):
            y = op(*a, groups, EPS)
            return jnp.sum(y.astype(jnp.float32) * weight), y

        (_, y), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
        return y, grads

    before = _rows("forward"), _rows("backward")
    y, got = both(gated_norm)
    # the counter: B x S rows a traced pass, all under the path the shape takes
    other = "plain" if path == "kernel" else "kernel"
    forward, backward = _rows("forward"), _rows("backward")
    assert forward[path] - before[0][path] == B * S
    assert backward[path] - before[1][path] == B * S
    assert forward[other] == before[0][other]
    assert backward[other] == before[1][other]

    want_y, want = both(plain)
    assert y.shape == (B, S, H * P) and y.dtype == dtype
    for name, g, w, a in zip(NAMES, got, want, args, strict=True):
        assert g.shape == a.shape and g.dtype == a.dtype, name  # do as o is
    # nothing beyond column H P is read or given a gradient
    assert not np.any(np.asarray(got[1][..., H * P:], np.float32))
    assert not np.any(np.asarray(got[2][..., H * P:], np.float32))

    def off(g, w):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        return np.max(np.abs(g - w)) / np.max(np.abs(w))

    if dtype == jnp.float32:
        assert off(y, want_y) < 1e-5
        for name, g, w in zip(NAMES, got, want, strict=True):
            assert off(g, w) < 1e-5, name
    else:
        # float32 inside from the same bfloat16 inputs: y is the float32
        # result to its own rounding (half a unit in the last place of 8
        # bits, and the last float32 bit's that may tip it), and so are the
        # three gradients that leave in bfloat16; the sums stay float32
        exact = jax.jit(lambda *a: plain(*a, groups, EPS))(
            *(a.astype(jnp.float32) for a in args))
        y, exact = np.asarray(y, np.float64), np.asarray(exact, np.float64)
        assert np.all(np.abs(y - exact) <= 2.0 ** -8 * np.abs(exact) + 1e-30)
        for name, g, w in zip(NAMES, got, want, strict=True):
            assert off(g, w) < (2.0 ** -7 if name in NAMES[:3] else 1e-4), name


@pytest.mark.parametrize("name,dims", [("granite_4_0_h_micro", (64, 64, 128, 1)),
                                       ("nemotron_3_nano_30b_a3b", (64, 64, 128, 8))])
def test_the_cells_shapes_take_the_kernel_in_blocks_that_fit(name, dims):
    """Both configurations' `ssm_dims` at 8,192 positions: the kernel's
    path, 256 rows a block forward and 128 backward in bfloat16 (o's rows of
    64 features padded to a lane tile), inside the budget."""
    H, P, _, groups = dims
    assert module.tiles(H, P, 8192, groups)
    o_row, y_row = H * 128 * 2, H * P * 2
    assert module._block_rows(8192, o_row + 3 * y_row) == 256
    assert module._block_rows(8192, 2 * o_row + 5 * y_row) == 128
    assert 2 * 128 * (2 * o_row + 5 * y_row) <= module.BLOCK_BYTES


def test_a_sequence_no_block_fits_takes_one_chunk_of_rows_a_block():
    assert module._block_rows(48, 1 << 30) == module.CHUNK_ROWS
    assert not module.tiles(8, 16, 24, 1)  # 24 rows are no whole chunks
