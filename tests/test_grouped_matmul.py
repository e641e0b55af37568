"""`ops/grouped_matmul.py`: the kernels interpreted against `lax.ragged_dot`
and its transposes, the rule that chooses the path from the shape, and the
count of tile visits against counts made by hand."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from jaxprs import interpret_kernels, primitives
from kungfu_tpu.ops import grouped_matmul as gm
from kungfu_tpu.ops import moe
from kungfu_tpu.ops import row_moves


ROW_TILE = 128


@pytest.fixture(scope="class")
def interpreted():
    """The kernels' path whatever the platform, the kernels interpreted, on
    row tiles of 128 (the rule's are 512 where the rows divide into them).
    For a class: its cases of one shape share the interpreted programs, and
    no trace made under the patches outlives it."""
    jax.clear_caches()
    with pytest.MonkeyPatch.context() as m:
        # `lax.platform_dependent` is one function for every module: the
        # rows' kernels beside these are interpreted too
        interpret_kernels(m, gm, ("_gmm", "_tgmm"))
        interpret_kernels(m, row_moves, ("_take", "_add"))
        rule = gm.tiling
        m.setattr(gm, "tiling", lambda *shape: rule(*shape)._replace(tm=ROW_TILE))
        yield
    jax.clear_caches()


# the sizes of six groups of 512 rows of 128 features on weights (128, 256)
ROWS, K, M = 512, 128, 256
CASES = {
    "groups_that_end_on_tile_edges": [128, 256, 0, 128, 0, 0],
    "groups_that_straddle_tiles": [100, 230, 60, 22, 50, 50],
    "an_empty_group_between_two": [256, 0, 256, 0, 0, 0],
    "empty_groups_at_both_ends_inside_a_tile": [0, 70, 0, 300, 14, 0],
    "rows_past_the_last_group": [100, 0, 141, 0, 0, 0],
    "one_group_of_everything": [0, 512, 0, 0, 0, 0],
    "no_rows_at_all": [0, 0, 0, 0, 0, 0],
}


def _operands(N, K, M, sizes):
    keys = jax.random.split(jax.random.PRNGKey(len(sizes) + N), 3)
    rows = jax.random.normal(keys[0], (N, K), jnp.bfloat16)
    weights = (jax.random.normal(keys[1], (len(sizes), K, M)) / K ** 0.5
               ).astype(jnp.bfloat16)
    dy = jax.random.normal(keys[2], (N, M), jnp.bfloat16)
    return rows, weights, jnp.asarray(sizes, jnp.int32), dy


def _close(got, want, rel=2.0 ** -7):
    """To bfloat16's rounding of the largest entry."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=0, atol=rel * max(1.0, np.abs(want).max()))


@pytest.mark.usefixtures("interpreted")
class TestTheInterpretedKernels:
    @pytest.mark.parametrize("case", CASES)
    def test_are_ragged_dot_and_its_transposes(self, case):
        """Output, rows' gradient and weights' gradient. A row of no group is
        not read (it holds NaN going in) and what comes out of it is left out
        of the comparison, as `ops/moe._chunk_part` selects it away."""
        rows, weights, sizes, dy = _operands(ROWS, K, M, CASES[case])
        live = (jnp.arange(ROWS) < jnp.sum(sizes))[:, None]
        want, transposes = jax.vjp(lambda r, w: lax.ragged_dot(r, w, sizes),
                                   jnp.where(live, rows, 0), weights)
        want_rows, want_weights = transposes(jnp.where(live, dy, 0))
        got, transposes = jax.vjp(lambda r, w: gm.grouped_matmul(r, w, sizes),
                                  jnp.where(live, rows, jnp.nan), weights)
        got_rows, got_weights = transposes(jnp.where(live, dy, jnp.nan))
        _close(jnp.where(live, got, 0), jnp.where(live, want, 0))
        _close(jnp.where(live, got_rows, 0), want_rows)
        _close(got_weights, want_weights)

    def test_take_a_straddling_tile_a_sub_block_at_a_time(self, monkeypatch):
        """Row tiles of 256 over sub-blocks of 128: tiles inside a group,
        tiles that two and three groups share, a sub-block no group of the
        visit touches."""
        monkeypatch.setattr(gm, "tiling", lambda *shape: gm.Tiles(256, 128, 128, 128, 128))
        rows, weights, sizes, dy = _operands(1024, 128, 128, [300, 20, 330, 0, 374])
        want, transposes = jax.vjp(lambda r, w: lax.ragged_dot(r, w, sizes), rows, weights)
        got, mine = jax.vjp(lambda r, w: gm.grouped_matmul(r, w, sizes), rows, weights)
        _close(got, want)
        for g, w in zip(mine(dy), transposes(dy)):
            _close(g, w)

    def test_leave_a_chunk_a_third_full_finite_and_xlas_in_both_passes(self):
        """`_chunk_part` under `jax.vjp` with the interpreted kernels in
        `lax.ragged_dot`'s place and `ops/row_moves.py`'s, interpreted, in
        the place of XLA's gather and scatter-add: what lies in the rows of
        no group is whatever was found there and none of the kernels reads
        it, so output and all three cotangents are finite and XLA's. (The
        class's last case: it clears the traces the others share.)"""
        T, D, F, top_k, held, chunk = 64, 128, 128, 4, 4, 256
        keys = jax.random.split(jax.random.PRNGKey(5), 6)
        x = jax.random.normal(keys[0], (T, D), jnp.bfloat16)
        gate = jax.random.uniform(keys[1], (T, top_k))
        experts = tuple(jax.random.normal(k, shape) * 0.1 for k, shape in zip(
            keys[2:5], ((held, D, F), (held, D, F), (held, F, D))))
        sizes = jnp.asarray([30, 0, 41, 14], jnp.int32)  # 85 of the chunk's 256
        order = jax.random.permutation(keys[5], T * top_k)
        dout = jnp.ones((T, D), jnp.float32)

        def run(product, take=None, add=None):
            with pytest.MonkeyPatch.context() as m:
                m.setattr(moe, "grouped_matmul", product)
                m.setattr(moe, "take_rows", take or moe.take_rows)
                m.setattr(moe, "add_rows", add or moe.add_rows)
                jax.clear_caches()  # `_silu_gate_down` is a checkpoint: its trace is kept
                out, transposes = jax.vjp(
                    lambda *a: moe._chunk_part(moe.swiglu_experts, top_k, chunk,
                                               *a, order, sizes, 0), x, gate, experts)
                return out, transposes(dout)

        want = run(lax.ragged_dot, row_moves.plain_take_rows,
                   row_moves.plain_add_rows)
        got = run(gm.grouped_matmul)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
            _close(g, w, rel=2.0 ** -6)


RULE = {
    # rows, K, M, groups -> the tiles, or None for `lax.ragged_dot`
    "the_tests_rows": ((12, 10, 6, 2), None),
    "a_chunk_of_odd_length": ((40968, 2048, 512, 32), None),
    "a_width_that_is_no_lane_tile": ((1024, 2688, 1856, 8), None),
    "olmoe": ((65536, 2048, 1024, 64), gm.Tiles(512, 1024, 2048, 2048, 1024)),
    "rows_in_tiles_of_128": ((384, 256, 128, 4), gm.Tiles(128, 128, 256, 256, 128)),
    # at row tiles of 512 its blocks pass `GMM_ROOM` and the chip runs `_gmm`
    # at two thirds of its rate there (PERF.md, PR 72)
    "nemotron_3_nano": ((12288, 3072, 2048, 8), gm.Tiles(256, 2048, 3072, 1536, 2048)),
}


@pytest.mark.parametrize("case", RULE)
def test_the_path_is_chosen_from_the_shape(case):
    """A shape that does not tile takes `lax.ragged_dot` and stages no
    kernel; one that does stages the kernels for the TPU and `lax.ragged_dot`
    for anywhere else, forward and backward."""
    (N, K, M, e), tiles = RULE[case]
    got = gm.tiling(N, K, M, e)
    assert got == tiles
    shapes = (jax.ShapeDtypeStruct((N, K), jnp.bfloat16),
              jax.ShapeDtypeStruct((e, K, M), jnp.bfloat16),
              jax.ShapeDtypeStruct((e,), jnp.int32))
    staged = primitives(jax.make_jaxpr(lambda r, w, s: jax.vjp(
        lambda r, w: gm.grouped_matmul(r, w, s), r, w)[1](
            jnp.ones((N, M), jnp.bfloat16)))(*shapes).jaxpr)
    assert "ragged_dot_general" in staged
    assert ("pallas_call" in staged) == (tiles is not None)


VISITS = {
    # the groups' sizes, the row tile -> visits a group, by hand
    "every_tile_once": ([512, 1024, 512], 512, [1, 2, 1]),
    "the_olmoe_shape_at_1_5": ([256] + [1024] * 3 + [768], 512, [1, 3, 3, 3, 2]),
    "a_share_a_quarter_full": ([100, 0, 156], 128, [1, 0, 2]),
    "a_group_inside_one_tile": ([10, 20, 30], 128, [1, 1, 1]),
    "nothing_came": ([0, 0], 128, [0, 0]),
}


@pytest.mark.parametrize("case", VISITS)
def test_tile_visits_against_counts_by_hand(case):
    sizes, tm, want = VISITS[case]
    assert gm.tile_visits(np.asarray(sizes), tm).tolist() == want
    assert gm.tile_visits(jnp.asarray(sizes), tm).tolist() == want
    n_tiles = max(1, -(-sum(sizes) // tm))
    (group, tile, _, _), count = gm._visits(jnp.asarray(sizes, jnp.int32), tm=tm,
                                            n_tiles=n_tiles, empty=False)
    assert int(count) == sum(want)
    by_hand = [(g, t) for g, n in enumerate(sizes) if n
               for t in range(sum(sizes[:g]) // tm, -(-sum(sizes[:g + 1]) // tm))]
    assert list(zip(group.tolist(), tile.tolist()))[:int(count)] == by_hand


def test_the_tile_visit_share_is_a_gauge_of_the_layer(monkeypatch):
    """`kungfu_moe_tile_visit_share` from `routing_stats`: absent where the
    experts' rows take XLA's path (the tiny model's 32 tokens), the visits
    over the buffer's tiles where they take the kernels', every expert held
    and a share alike."""
    from kungfu_tpu.models import transformer
    from kungfu_tpu.models.transformer import TransformerConfig
    from kungfu_tpu.telemetry import metrics

    def shares(cfg, tokens):
        params = transformer.init_transformer(jax.random.PRNGKey(2), cfg)
        stats = jax.jit(lambda p, t: transformer.routing_stats(p, t, cfg))(params, tokens)
        registry = metrics.Registry()
        transformer.record_routing(stats, registry)
        return stats, [float(l.split()[-1]) for l in registry.render().splitlines()
                       if l.startswith("kungfu_moe_tile_visit_share{")]

    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 64), 0, 64)
    cfg = TransformerConfig.tiny_moe(n_layers=1)  # 192 rows: no tiles of 128
    absent = jax.eval_shape(
        lambda p, t: transformer.routing_stats(p, t, cfg),
        jax.eval_shape(lambda: transformer.init_transformer(jax.random.PRNGKey(2), cfg)),
        tokens)
    assert "tile_visits" not in absent and "tiles" not in absent
    wide = TransformerConfig.tiny_moe(n_layers=1, d_model=128, d_ff=128, n_heads=2,
                                      top_k=2)
    stats, got = shares(wide, tokens)  # 64 tokens x top_k rows in tiles of 128
    counts = np.asarray(stats["counts"])
    tm = gm.tiling(counts.sum(axis=-1)[0], 128, 128, counts.shape[-1]).tm
    want = gm.tile_visits(counts, tm).sum(axis=-1) / (counts.sum(axis=-1) // tm)
    assert got == pytest.approx(want.tolist()) and all(1.0 <= g for g in got)
    share = dataclasses.replace(wide, max_seq=128, experts_held=(2, 2))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 128), 0, 64)
    stats, got = shares(share, tokens)
    tm = gm.tiling(256, 128, 128, 2).tm  # the share's chunk is 256 rows
    assert np.asarray(stats["tiles"]).tolist() == (
        np.asarray(stats["chunk_rows"]) // tm).tolist()
    for visits, tiles, rows, g in zip(stats["tile_visits"], stats["tiles"],
                                      stats["held_rows"], got, strict=True):
        assert g == pytest.approx(int(visits) / max(int(tiles), 1))
        assert -(-int(rows) // tm) <= int(visits) <= -(-int(rows) // tm) + 1


def test_the_step_of_a_model_lowers_the_kernels_for_the_tpu():
    """An expert layer's value and gradient lowered for the TPU (which loads
    no libtpu) holds the three kernels and no ragged dot; lowered for the
    CPU, no kernel."""
    N, D, F, e = 1024, 256, 128, 4
    rows = jax.ShapeDtypeStruct((N, D), jnp.bfloat16)
    experts = tuple(jax.ShapeDtypeStruct(s, jnp.float32)
                    for s in ((e, D, F), (e, D, F), (e, F, D)))
    sizes = jax.ShapeDtypeStruct((e,), jnp.int32)

    def loss(rows, experts, sizes):
        return jnp.sum(moe.swiglu_experts(rows, experts, sizes).astype(jnp.float32))

    step = jax.jit(jax.grad(loss, argnums=(0, 1)))
    tpu = step.trace(rows, experts, sizes).lower(lowering_platforms=("tpu",)).as_text()
    # the builders are jitted: a kernel is lowered once a shape and called
    # where it is used (the forward down product is dead under `jax.grad`:
    # the gate's and the up product, and the three products' transposes)
    assert {"grouped_matmul", "grouped_matmul_transposed", "grouped_matmul_outer"} == set(
        re.findall(r'kernel_name = "(\w+)"', tpu))
    calls = re.findall(r"call @(_forward|_transposes)", tpu)
    assert calls.count("_forward") == 2 and calls.count("_transposes") == 3
    assert "ragged_dot" not in tpu
    cpu = step.trace(rows, experts, sizes).lower(lowering_platforms=("cpu",)).as_text()
    assert "tpu_custom_call" not in cpu
