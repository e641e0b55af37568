"""`ops/row_moves.py`: the two kernels interpreted against the plain
`jax.numpy` forms, their `custom_vjp`s against `jax.vjp` of the plain forms,
the rule that chooses the path from the shape, and the count of the rows the
kernels visit against counts made by hand. A row at or past `live` is left
as found and is never compared."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jaxprs import interpret_kernels, primitives
from kungfu_tpu.ops import row_moves as rm

T, D, N = 64, 256, 512
TILES = rm.Tiles(128, 128)  # four row tiles, two strips
# nothing, one row, mid-tile, a tile's edge, past a tile's edge, every row
LIVE = (0, 1, 77, 128, 300, N)
TYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _operands(dtype, seed=0):
    """x, a cotangent of its shape, N rows, their indices (every token
    several times over: 512 draws of 64) and their weights."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(k[0], (T, D), dtype),
            jax.random.normal(k[1], (T, D), jnp.float32),
            jax.random.normal(k[2], (N, D), dtype),
            jax.random.randint(k[3], (N,), 0, T),
            jax.random.uniform(k[4], (N,), jnp.float32, 0.5, 1.5))


def _close(got, want, dtype=jnp.float32):
    """To float32's summation order, or to bfloat16's rounding of the
    largest entry."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    rel = 2.0 ** -7 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=rel * max(1.0, np.abs(want).max(initial=0)))


@pytest.fixture(scope="class")
def interpreted():
    """The kernels' path whatever the platform, the kernels interpreted, on
    `TILES` (the rule's row tile is 512 where the rows divide into it). For
    a class: its cases of one type share the interpreted programs, and no
    trace made under the patches outlives it."""
    jax.clear_caches()
    with pytest.MonkeyPatch.context() as m:
        interpret_kernels(m, rm, ("_take", "_add"))
        m.setattr(rm, "tiling", lambda *shape: TILES)
        yield
    jax.clear_caches()


@pytest.mark.parametrize("live", LIVE)
@pytest.mark.parametrize("dtype", TYPES)
def test_take_rows_interpreted_is_the_plain_gather(dtype, live):
    x, _, _, index, _ = _operands(TYPES[dtype])
    got = rm._take(x, index, live, tm=TILES.tm, w=TILES.w, interpret=True)
    assert got.shape == (N, D) and got.dtype == x.dtype
    np.testing.assert_array_equal(
        np.asarray(got[:live], np.float32),
        np.asarray(rm.plain_take_rows(x, index, live)[:live], np.float32))


@pytest.mark.parametrize("weighted", [False, True], ids=["no_weight", "weights"])
@pytest.mark.parametrize("live", LIVE)
@pytest.mark.parametrize("dtype", TYPES)
def test_add_rows_interpreted_is_the_plain_scatter_add(dtype, live, weighted):
    """Every token is named by eight rows on average: repeated indices in
    one tile and across tiles. With nothing live the result is zeros."""
    _, _, y, index, weight = _operands(TYPES[dtype])
    got = rm._add(y, index, live, weight if weighted else jnp.ones((N,)),
                  T=T, tm=TILES.tm, w=TILES.w, interpret=True)
    assert got.shape == (T, D) and got.dtype == jnp.float32
    _close(got, rm.plain_add_rows(y, index, live, T, weight if weighted else None))
    if not live:
        assert float(jnp.abs(got).max()) == 0.0


@pytest.mark.usefixtures("interpreted")
class TestTheCustomVjps:
    @pytest.mark.parametrize("live", LIVE)
    @pytest.mark.parametrize("dtype", TYPES)
    def test_each_is_the_others_transpose(self, dtype, live):
        """`take_rows` and `add_rows` under `jax.vjp`, the kernels interpreted,
        against `jax.vjp` of the plain forms: outputs, x's cotangent (`add_rows`
        of the rows'), y's (the cotangent's rows taken and weighed) and the
        weights' (a row-wise dot, zeros at and past `live`). The rows' cotangent
        holds NaN at and past `live` going in: such a row is not read."""
        x, g, y, index, weight = _operands(TYPES[dtype], seed=1)
        before = (jnp.arange(N) < live)[:, None]
        dy = jnp.where(before, y, jnp.nan)

        got, back = jax.vjp(lambda x: rm.take_rows(x, index, live), x)
        # the plain form's transpose in float32: autodiff's own sums a token's
        # rows in the rows' type, the kernel in float32 whatever the type
        want, plain = jax.vjp(lambda x: rm.plain_take_rows(x, index, live),
                              x.astype(jnp.float32))
        _close(jnp.where(before, got, 0), want)
        _close(back(dy)[0], plain(jnp.where(before, y, 0).astype(jnp.float32))[0],
               TYPES[dtype])

        got, back = jax.vjp(lambda y, w: rm.add_rows(y, index, live, T, w), dy, weight)
        want, plain = jax.vjp(lambda y, w: rm.plain_add_rows(y, index, live, T, w),
                              jnp.where(before, y, 0), weight)
        _close(got, want)
        (got_y, got_w), (want_y, want_w) = back(g), plain(g)
        _close(jnp.where(before, got_y, 0), want_y, TYPES[dtype])
        _close(got_w, want_w)
        assert float(jnp.abs(got_w[live:]).max(initial=0.0)) == 0.0


RULE = {
    # N, D, T -> the tiles, or None for the plain forms
    "the_tests_rows": ((72, 16, 48), None),
    "a_chunk_of_odd_length": ((40968, 2048, 16384), None),
    "a_width_that_is_no_lane_tile": ((12288, 2000, 8192), None),
    "keye": ((32768, 2048, 8192), rm.Tiles(512, 1024)),
    "smallthinker": ((49152, 2560, 16384), rm.Tiles(512, 512)),
    "qwen3_next": ((40960, 2048, 16384), rm.Tiles(512, 512)),
    "laguna": ((10240, 3072, 8192), rm.Tiles(512, 1024)),
    "rows_in_tiles_of_128": ((384, 256, 64), rm.Tiles(128, 256)),
}


@pytest.mark.parametrize("case", RULE)
def test_the_path_is_chosen_from_the_shape(case):
    """A shape that does not tile takes the plain forms and stages no kernel;
    one that does stages the kernels for the TPU and the plain forms for
    anywhere else, both ways and under both transposes."""
    (n, d, t), tiles = RULE[case]
    assert rm.tiling(n, d, t) == tiles
    x, y, index, weight = (jax.ShapeDtypeStruct(*a) for a in (
        ((t, d), jnp.bfloat16), ((n, d), jnp.bfloat16), ((n,), jnp.int32),
        ((n,), jnp.float32)))

    def both(x, y, index, weight):
        rows, back = jax.vjp(lambda x: rm.take_rows(x, index, 5), x)
        out, transposes = jax.vjp(
            lambda y, w: rm.add_rows(y, index, 5, t, w), y, weight)
        return rows, back(y), out, transposes(out)

    staged = primitives(jax.make_jaxpr(both)(x, y, index, weight).jaxpr)
    assert {"gather", "scatter-add"} <= staged
    assert ("pallas_call" in staged) == (tiles is not None)


VISITED = {
    # live, the row tile -> the rows of the tiles visited, by hand
    "nothing_came": (0, 512, 512),
    "one_row": (1, 512, 512),
    "a_tiles_edge": (1024, 512, 1024),
    "one_past_it": (1025, 512, 1536),
    "keye_balanced": (8192, 512, 8192),
    "qwen3_next": (13516, 512, 13824),
}


@pytest.mark.parametrize("case", VISITED)
def test_rows_visited_against_counts_by_hand(case):
    """`rows_visited`, numpy's and jax's, and the grid's extent that the
    kernels are given."""
    live, tm, want = VISITED[case]
    assert int(rm.rows_visited(np.asarray(live), tm)) == want
    assert int(rm.rows_visited(jnp.asarray(live), tm)) == want
    _, count = rm._scalars(jnp.zeros((tm,), jnp.int32), jnp.asarray(live), tm)
    assert int(count) * tm == want
