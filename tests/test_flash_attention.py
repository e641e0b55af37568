"""Pallas flash attention vs dense attention (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jaxprs import pallas_calls
from kungfu_tpu.ops.flash_attention import _dense_reference, flash_attention


def _qkv(B=2, H=3, S=64, hd=16, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(
        jax.random.normal(k, (B, H, S, hd), dtype) for k in ks
    )


# blocks of 128 and 256 at head size 128 are as wide as the chip's lanes and
# twice that: the running maximum and sum meet the scores as they do on the
# chip, the tile itself or copies side by side (`_across`), where the narrow
# blocks broadcast column 0
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("blk,S,hd", [(16, 64, 16), (32, 64, 16), (64, 64, 16),
                                      (128, 512, 128), (256, 512, 128)])
def test_flash_matches_dense(causal, blk, S, hd):
    q, k, v = _qkv(S=S, hd=hd)
    out = flash_attention(q, k, v, causal, None, blk, blk, True)
    ref = _dense_reference(q, k, v, causal, 1.0 / np.sqrt(q.shape[-1]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_untileable_shape_raises():
    """A sequence the blocks do not divide is an error in the forward
    and under grad — there is no dense fallback to hide behind."""
    q, k, v = _qkv(S=48, hd=8)  # 48 % 32 != 0
    with pytest.raises(ValueError, match="not a multiple"):
        flash_attention(q, k, v, True, None, 32, 32, True)
    with pytest.raises(ValueError, match="not a multiple"):
        jax.grad(
            lambda q: jnp.sum(flash_attention(q, k, v, True, None, 32, 32, True))
        )(q)


def test_flash_never_interprets_unasked():
    """Without an explicit interpret=True the kernel goes to the Mosaic
    compiler, which the CPU backend does not have: it must raise, not
    quietly run the interpreter."""
    q, k, v = _qkv()
    with pytest.raises(ValueError, match="Only interpret mode is supported"):
        flash_attention(q, k, v, True, None, 32, 32)


def test_flash_bf16():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, True, None, 32, 32, True)
    assert out.dtype == jnp.bfloat16
    ref = _dense_reference(q, k, v, True, 1.0 / np.sqrt(q.shape[-1]))
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_flash_gradients():
    q, k, v = _qkv(B=1, H=2, S=32, hd=8)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None, 16, 16, True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(
            _dense_reference(q, k, v, True, 1.0 / np.sqrt(8)) ** 2
        )

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_flash_as_transformer_core():
    """flash_attention plugs into the transformer's attention core and
    reproduces the dense model's logits."""
    from kungfu_tpu.models.transformer import (
        TransformerConfig,
        _block,
        init_transformer,
        transformer_apply,
    )

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                            d_ff=64, max_seq=32, dtype=jnp.float32)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    ref = transformer_apply(params, tokens, cfg)

    def flash_core(q, k, v):
        return flash_attention(q, k, v, True, None, 16, 16, True)

    x = params["embed"].astype(cfg.dtype)[tokens] + params["pos_embed"].astype(cfg.dtype)[:32]

    def body(x, layer):
        return _block(x, layer, cfg, core=flash_core), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    from kungfu_tpu.models.transformer import _rmsnorm

    x = _rmsnorm(x, params["ln_f_scale"])
    logits = x.astype(jnp.float32) @ params["embed"].astype(jnp.float32).T
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_flash_gradients_non_causal_multiblock():
    q, k, v = _qkv(B=1, H=2, S=64, hd=8)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, False, None, 16, 32, True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(
            _dense_reference(q, k, v, False, 1.0 / np.sqrt(8)) ** 2
        )

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


# --- grouped heads and a window (PR 33) --------------------------------------

def _grouped_qkv(S, g, Hkv=2, hd=8, B=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, Hkv * g, S, hd))
    k = jax.random.normal(ks[1], (B, Hkv, S, hd))
    v = jax.random.normal(ks[2], (B, Hkv, S, hd))
    return q, k, v, jax.random.normal(ks[3], q.shape)


def _flash_and_dense(S, blk_q, blk_k, window, g, hd=8):
    """(loss, gradients) of the flash core and of the dense masked one, on
    a weighted sum of the output so that every row's gradient differs."""
    q, k, v, weigh = _grouped_qkv(S, g, hd=hd)

    def flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None, blk_q, blk_k, True,
                                       window) * weigh)

    def dense(q, k, v):
        return jnp.sum(_dense_reference(q, k, v, True, 1.0 / np.sqrt(q.shape[-1]),
                                        window) * weigh)

    return (jax.value_and_grad(flash, argnums=(0, 1, 2))(q, k, v),
            jax.value_and_grad(dense, argnums=(0, 1, 2))(q, k, v))


# blocks of 16: half a block, one block, three blocks; 80 is no multiple of
# 48 (nor 64 of 24); the last two cases of head size 8 have blocks that
# differ; those of head size 128 have lane-wide blocks, as above
@pytest.mark.parametrize("g", [1, 3])
@pytest.mark.parametrize("S,blk_q,blk_k,window,hd", [
    (64, 16, 16, 8, 8), (64, 16, 16, 16, 8), (80, 16, 16, 48, 8),
    (64, 16, 16, None, 8), (64, 16, 32, 24, 8), (64, 32, 16, 20, 8),
    (512, 128, 256, None, 128), (512, 256, 128, 200, 128), (512, 128, 128, 200, 128)])
def test_windowed_grouped_flash_matches_dense(S, blk_q, blk_k, window, hd, g):
    (loss, grads), (want_loss, want) = _flash_and_dense(S, blk_q, blk_k, window, g, hd)
    assert abs(float(loss) - float(want_loss)) <= 1e-4 * abs(float(want_loss)) + 1e-4
    for got, ref in zip(grads, want):
        assert got.shape == ref.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("window", [1, 1000])
def test_a_window_of_one_and_a_window_past_the_sequence(window):
    """Window 1: every query sees itself alone, the output is v. A window
    longer than the sequence is the causal mask."""
    q, k, v, _ = _grouped_qkv(64, 2)
    out = flash_attention(q, k, v, True, None, 16, 16, True, window)
    want = (jnp.repeat(v, 2, axis=1) if window == 1
            else _dense_reference(q, k, v, True, 1.0 / np.sqrt(8)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_a_wrong_group_or_no_window_is_seen():
    """What the knock-outs of the model's tests rest on: heads read from
    another group, or the band left out, move the output by far more than
    the comparison's tolerance."""
    q, k, v, _ = _grouped_qkv(64, 3)
    out = flash_attention(q, k, v, True, None, 16, 16, True, 16)
    scale = 1.0 / np.sqrt(8)
    for wrong in (_dense_reference(q, k[:, ::-1], v[:, ::-1], True, scale, 16),
                  _dense_reference(q, k, v, True, scale)):
        assert float(jnp.max(jnp.abs(out - wrong))) > 0.1


def test_the_sweeps_cover_the_band_and_no_more():
    """At the Laguna cell's shape, blocks of 512 and window 512 at 8,192
    positions, every kernel's sweep is 2 blocks of 16; without a window 16."""
    import importlib

    fa = importlib.import_module("kungfu_tpu.ops.flash_attention")
    assert fa._kv_steps(8192, 512, 512, 512) == 2 == fa._q_steps(8192, 512, 512, 512)
    assert fa._kv_steps(8192, 512, 512, None) == 16 == fa._q_steps(8192, 512, 512, None)
    assert fa._kv_steps(8192, 512, 512, 513) == 2 == fa._q_steps(8192, 512, 512, 513)
    assert fa._kv_steps(8192, 512, 512, 514) == 3 == fa._q_steps(8192, 512, 512, 514)
    # three blocks of window: four blocks a row; never more than there are
    assert fa._kv_steps(64, 16, 16, 48) == 4 and fa._kv_steps(32, 16, 16, 48) == 2


def test_grouped_shapes_that_do_not_fit_raise():
    q, k, v, _ = _grouped_qkv(32, 3)
    with pytest.raises(ValueError, match="multiple of theirs"):
        flash_attention(q[:, :5], k, v, True, None, 16, 16, True)
    with pytest.raises(ValueError, match="a window is causal"):
        flash_attention(q, k, v, False, None, 16, 16, True, 8)
    with pytest.raises(ValueError, match="a window is causal"):
        flash_attention(q, k, v, True, None, 16, 16, True, 0)


# --- interior and edge blocks (PR 42) ----------------------------------------

def _fa():
    import importlib

    return importlib.import_module("kungfu_tpu.ops.flash_attention")


def _swept(fa, S, blk_q, blk_k, window):
    """The (q-block, k-block) pairs each sweep computes, by the kernels' own
    grid arithmetic: forward and dQ from the q-blocks, dK/dV from the k-blocks."""
    by_q, by_k = set(), set()
    for i in range(S // blk_q):
        first = 0 if window is None else int(fa._first_kv_block(blk_q, blk_k, window, i))
        for step in range(fa._kv_steps(S, blk_q, blk_k, window)):
            if (first + step) * blk_k <= i * blk_q + blk_q - 1:
                by_q.add((i, first + step))
    for j in range(S // blk_k):
        first = 0 if window is None else (j * blk_k) // blk_q
        for step in range(fa._q_steps(S, blk_q, blk_k, window)):
            i = first + step
            live = (i * blk_q + blk_q - 1 >= j * blk_k if window is None
                    else i <= int(fa._last_q_block(blk_q, blk_k, window, S, j)))
            if live:
                by_k.add((i, j))
    return by_q, by_k


@pytest.mark.parametrize("S,blk_q,blk_k,window", [
    (128, 16, 16, None), (128, 16, 16, 1), (128, 16, 16, 16), (128, 16, 16, 17),
    (128, 16, 16, 40), (128, 16, 16, 512), (128, 16, 16, 1000),
    (128, 32, 16, None), (128, 16, 32, None), (128, 32, 16, 24),
    (128, 16, 32, 24), (128, 64, 16, 48), (96, 16, 48, 33), (96, 48, 16, 512),
    (4096, 512, 512, None), (4096, 512, 512, 512), (2048, 256, 512, 1),
    (2048, 512, 256, 700)])
def test_interior_blocks_need_no_mask_and_skipped_ones_are_dead(S, blk_q, blk_k, window):
    """Against brute force: a block the predicate calls interior has an
    all-true mask, a block the sweeps skip an all-false one, both sweeps
    visit the same blocks, and `block_counts` counts them."""
    fa = _fa()
    by_q, by_k = _swept(fa, S, blk_q, blk_k, window)
    assert by_q == by_k
    edge = 0
    for i in range(S // blk_q):
        for j in range(S // blk_k):
            mask = np.asarray(fa._mask(i * blk_q, j * blk_k, blk_q, blk_k, window))
            if (i, j) not in by_q:
                assert not mask.any(), (i, j)
            elif fa._interior(i * blk_q, j * blk_k, blk_q, blk_k, window):
                assert mask.all(), (i, j)
            else:
                assert not mask.all(), (i, j)  # an edge block earns its mask
                edge += 1
    assert fa.block_counts(S, blk_q, blk_k, window) == (len(by_q), edge)


@pytest.mark.parametrize("S,blk,window,visited,edge", [
    (8192, 512, None, 136, 16), (16384, 512, None, 528, 32),
    (4096, 512, None, 36, 8), (8192, 512, 512, 31, 31)])
def test_block_counts_at_the_cells_shapes(S, blk, window, visited, edge):
    assert _fa().block_counts(S, blk, blk, window) == (visited, edge)


# g, blk_q, blk_k, window, S: blocks of both kinds in each (asserted where
# they run). The last three have blocks as wide as the chip's lanes or twice
# that, where the running maximum meets the scores as whole tiles side by
# side (`_across`'s other branch: the narrow blocks broadcast column 0)
_BIT_CALLS = {
    "causal": (1, 16, 16, None, 96), "grouped": (3, 16, 16, None, 96),
    "window": (1, 16, 16, 40, 96), "grouped-window": (2, 16, 16, 56, 96),
    "tall-blocks": (2, 32, 16, None, 96), "wide-blocks-window": (1, 16, 32, 48, 96),
    "lane-blocks": (1, 128, 128, None, 512),
    "lane-blocks-grouped-window": (2, 128, 128, 300, 512),
    "two-lane-blocks": (1, 128, 256, None, 512)}
_BIT_CASES = [(call, hd, dtype) for call in _BIT_CALLS for hd in (64, 128, 256)
              for dtype in ("float32", "bfloat16")]
_BIT_CHILDREN = 3


def _five_of_both_forms(call, hd, dtype):
    """{output: the same bits?} of one interpreted call, the kernels as they
    are against the kernels with every live block called an edge block, the
    parent's behaviour (and the backward kernels' still: dq, dk and dv say
    that the row sums they are given have not moved)."""
    fa = _fa()
    g, blk_q, blk_k, window, S = _BIT_CALLS[call]
    visited, edge = fa.block_counts(S, blk_q, blk_k, window)
    assert visited > edge > 0
    args = _inputs(g, S, hd, dtype) + (blk_q, blk_k, window)
    got = _five(fa, *args)
    interior, fa._interior = fa._interior, lambda q_off, *rest: q_off < 0
    try:
        want = _five(fa, *args)
    finally:
        fa._interior = interior
    return _same_bits(got, want)


# -- dead block pairs (PR 53): no grid step above the diagonal, no body for a
# -- block of other documents --------------------------------------------------

def _as_the_parent_ran_them(fa, *rules):
    """Context: the kernels under the parent commit's rules, the test's local
    reference. `grid`: a causal sweep's grid is square, dead steps clamped
    at the diagonal (`_by_table` false). `documents`: a block of a packed row
    is live whatever its documents (`_meet` true), fetched (its sweep's
    first live k-block is the first, last live q-block the last) and
    computed under its all-false mask."""
    import contextlib

    def fetch_every_block(segments, blk_q, blk_k):
        bounds, numbers = packed(segments, blk_q, blk_k)
        if bounds:
            n_q, n_k = segments.shape[1] // blk_q, segments.shape[1] // blk_k
            rows = bounds[0].reshape(segments.shape[0], 3 * (n_q + n_k))
            rows = rows.at[:, 2 * n_q:3 * n_q].set(0).at[:, 3 * n_q + 2 * n_k:].set(n_q - 1)
            bounds = [rows.reshape(-1)]
        return bounds, numbers

    @contextlib.contextmanager
    def patched():
        if "grid" in rules:
            fa._by_table = lambda causal, window: False
        if "documents" in rules:
            fa._meet, fa._packed = lambda ends: True, fetch_every_block
        try:
            yield
        finally:
            fa._by_table, fa._meet, fa._packed = was

    was = fa._by_table, fa._meet, packed = fa._by_table, fa._meet, fa._packed
    return patched()


def _five(fa, q, k, v, do, blk_q, blk_k, window=None, segments=None, scale=None):
    scale = scale or 1.0 / np.sqrt(q.shape[-1])
    out, lse = fa._forward(q, k, v, True, scale, blk_q, blk_k, True,
                           with_lse=True, window=window, segments=segments)
    return (out, lse) + fa._backward_kernels(
        q, k, v, out, lse, do, True, scale, blk_q, blk_k, True, window=window,
        segments=segments)


def _same_bits(got, want):
    return {name: a.dtype == b.dtype and bool(jnp.array_equal(a, b))
            for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), got, want)}


def _inputs(g, S, hd, dtype, Hkv=2):
    ks = jax.random.split(jax.random.PRNGKey(hd + g + S), 4)
    q = jax.random.normal(ks[0], (1, Hkv * g, S, hd), dtype)
    k = jax.random.normal(ks[1], (1, Hkv, S, hd), dtype)
    v = jax.random.normal(ks[2], (1, Hkv, S, hd), dtype)
    return q, k, v, jax.random.normal(ks[3], q.shape, dtype)


# blocks a side (of 16 positions), query heads to a key/value head, (blk_q,
# blk_k), head size, dtype: 4, 8 and 16 blocks a side, and blocks that differ
_GRID_CASES = [("grid", n, g, blocks, hd, dtype)
               for n in (4, 8, 16)
               for g, blocks, hd, dtype in ((1, "16x16", 16, "float32"),
                                            (3, "16x16", 64, "bfloat16"))
               ] + [("grid", 8, 2, "32x16", 16, "float32"),
                    ("grid", 8, 4, "16x32", 64, "bfloat16")]


def _five_of_both_grids(_, n, g, blocks, hd, dtype):
    """{output: the same bits?} of a causal call without a window over its
    live pairs alone and over the parent's square grid."""
    fa = _fa()
    blk_q, blk_k = map(int, blocks.split("x"))
    S = 16 * n
    visited, _ = fa.block_counts(S, blk_q, blk_k)
    assert fa.grid_steps(S, blk_q, blk_k, True) == (visited, visited)
    args = _inputs(g, S, hd, dtype) + (blk_q, blk_k)
    got = _five(fa, *args)
    with _as_the_parent_ran_them(fa, "grid"):
        assert fa.grid_steps(S, blk_q, blk_k, True) == (
            (S // blk_q) * (S // blk_k),) * 2
        want = _five(fa, *args)
    return _same_bits(got, want)


# documents of a row of 256 positions in 8 blocks of 32
PACKED_ROWS = {
    "one-document": (256,),
    "on-the-blocks-edges": (64, 96, 32, 64),
    "shorter-than-a-block": (100, 10, 146),
    "sixteen-in-eight-blocks": (10, 22, 16, 16, 5, 27, 20, 12, 16, 16, 30, 2,
                                16, 16, 8, 24)}
# the row, query heads to a key/value head, head size, dtype, (blk_q, blk_k),
# window: grouped heads of 64 as the Granite cell's, plain heads of 16, and a
# band and blocks that differ over the rows with the most boundaries
_SKIP_CASES = [("skip", row, g, hd, dtype, "32x32", None)
               for row in PACKED_ROWS
               for g, hd, dtype in ((4, 64, "bfloat16"), (1, 16, "float32"))
               ] + [("skip", "sixteen-in-eight-blocks", 4, 64, "bfloat16", "32x32", 80),
                    ("skip", "shorter-than-a-block", 2, 16, "float32", "64x32", None),
                    ("skip", "on-the-blocks-edges", 2, 16, "float32", "32x64", 100)]


def _documents_of(row):
    return np.repeat(np.arange(len(PACKED_ROWS[row])), PACKED_ROWS[row]).astype(np.int32)


def _five_with_and_without_the_skip(_, row, g, hd, dtype, blocks, window):
    """{output: the same bits?} of a packed call whose blocks of other
    documents run no body, against the parent's kernels on the same inputs:
    every block under the diagonal computed, a square grid."""
    fa = _fa()
    blk_q, blk_k = map(int, blocks.split("x"))
    documents = _documents_of(row)
    S = documents.size
    if len(PACKED_ROWS[row]) > 1 and window is None:  # something is skipped
        assert fa.block_counts(S, blk_q, blk_k, None, documents)[0] < fa.block_counts(
            S, blk_q, blk_k)[0]
    args = _inputs(g, S, hd, dtype) + (blk_q, blk_k, window,
                                       jnp.asarray(documents)[None], 1.0 / 64)
    got = _five(fa, *args)
    with _as_the_parent_ran_them(fa, "grid", "documents"):
        want = _five(fa, *args)
    return _same_bits(got, want)


@pytest.fixture(scope="module")
def both_forms_bits():
    """Every case of `_BIT_CASES` in processes of their own whose compiler
    has no fused multiply-add. With one, XLA's CPU backend contracts
    `x * scale - m` wherever no select stands between the two, so the two
    bodies round differently at a scale that is no power of two (head 128):
    the interpreter's doing, not the kernels' (the chip's agree bit for bit,
    PERF.md, PR 42). Three children at once, every third case each: a case
    is six interpreted kernels to compile, 3 s whatever its shape (PR 47)."""
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root, XLA_FLAGS=(
        os.environ.get("XLA_FLAGS", "") + " --xla_cpu_max_isa=AVX").strip())
    children = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), str(i)], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for i in range(_BIT_CHILDREN)]
    bits = {}
    for child in children:
        out, err = child.communicate(timeout=1200)
        assert child.returncode == 0, err[-2000:]
        bits.update(json.loads(out.splitlines()[-1]))
    return bits


@pytest.mark.parametrize("call,hd,dtype", _BIT_CASES,
                         ids=["-".join(map(str, c)) for c in _BIT_CASES])
def test_unmasked_interior_blocks_change_no_bit(both_forms_bits, call, hd, dtype):
    """On an interior block the select is the identity and the product is
    by 1.0: output, row log-sum-exp, dq, dk and dv keep every bit."""
    same = both_forms_bits[f"{call}-{hd}-{dtype}"]
    assert all(same.values()) and len(same) == 5, same


@pytest.mark.parametrize("case", _GRID_CASES, ids=["-".join(map(str, c)) for c in _GRID_CASES])
def test_a_grid_of_the_live_pairs_alone_changes_no_bit(both_forms_bits, case):
    """A causal sweep without a window runs over its live pairs by a table
    and not over a square: output, row log-sum-exp, dq, dk and dv are the
    square grid's to the bit."""
    same = both_forms_bits["-".join(map(str, case))]
    assert all(same.values()) and len(same) == 5, same


@pytest.mark.parametrize("case", _SKIP_CASES, ids=["-".join(map(str, c)) for c in _SKIP_CASES])
def test_a_skipped_block_of_other_documents_changes_no_bit(both_forms_bits, case):
    """A block whose keys are all of earlier documents than its queries added
    exact zeros under its all-false mask and left the running maximum where
    it was: run for nothing, all five outputs keep the parent's bits."""
    same = both_forms_bits["-".join(map(str, case))]
    assert all(same.values()) and len(same) == 5, same


@pytest.mark.parametrize("g", [1, 3])
@pytest.mark.parametrize("n", [4, 8, 16])
def test_a_sweep_over_its_live_pairs_matches_dense(n, g):
    """4, 8 and 16 blocks a side: loss and the three gradients of the causal
    core whose grids hold the live pairs alone, against the dense masked
    one."""
    (loss, grads), (want_loss, want) = _flash_and_dense(16 * n, 16, 16, None, g)
    assert abs(float(loss) - float(want_loss)) <= 1e-4 * abs(float(want_loss)) + 1e-4
    for got, ref in zip(grads, want):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal,window,g", [
    (True, 40, 1), (True, 24, 3), (False, None, 1), (False, None, 2)],
    ids=["window", "window-grouped", "not-causal", "not-causal-grouped"])
def test_a_band_and_a_core_that_is_not_causal_are_the_programs_they_were(causal, window, g):
    """Neither the table nor the documents' rule is in their traced program:
    its text is what it is under the parent's rules, letter for letter (and,
    read once against the parent commit's own file, the parent's: PERF.md,
    PR 53); three-dimensional grids, no operand more."""
    from jaxprs import pallas_operands

    fa = _fa()
    q, k, v, _ = _grouped_qkv(128, g, hd=16, B=1)

    def text():
        return jax.make_jaxpr(jax.value_and_grad(lambda *a: jnp.sum(
            fa.flash_attention(*a, causal, None, 32, 16, True, window)), (0, 1, 2)))(q, k, v)

    now = text()
    with _as_the_parent_ran_them(fa, "grid", "documents"):
        assert str(text()) == str(now)
    assert pallas_operands(now.jaxpr) == {"_kernel": 3, "_dq_kernel": 6, "_dkv_kernel": 6}
    assert "grid=(" + str(2 * g) + ", 4, " in str(now)  # (heads, q-blocks, steps)


@pytest.mark.parametrize("blocks,window", [((32, 32), None), ((64, 32), None),
                                           ((32, 64), None), ((32, 32), 80)])
@pytest.mark.parametrize("row", list(PACKED_ROWS))
def test_block_counts_of_a_packed_row_against_brute_force(row, blocks, window):
    """With a row's documents `block_counts` leaves out the blocks whose mask
    is false everywhere (exactly those, without a window) and calls interior
    the blocks whose mask is true everywhere; `_meet` and `_interior` on the
    block's `_ends` are the rules the kernels run."""
    fa = _fa()
    blk_q, blk_k = blocks
    documents = _documents_of(row)
    S = documents.size
    visited = edge = 0
    for q_off in range(0, S, blk_q):
        for k_off in range(0, S, blk_k):
            docs = (documents[q_off:q_off + blk_q, None], documents[None, k_off:k_off + blk_k])
            ends = fa._ends(documents, q_off, k_off, blk_q, blk_k)
            mask = np.asarray(fa._mask(q_off, k_off, blk_q, blk_k, window, docs))
            by_shape = np.asarray(fa._mask(q_off, k_off, blk_q, blk_k, window)).any()
            live = by_shape and bool(fa._meet(ends))
            if window is None:
                assert live == mask.any(), (q_off, k_off)
            else:  # a band may hold no pair of a block that the rules keep
                assert live or not mask.any(), (q_off, k_off)
            if live:
                visited += 1
                interior = bool(fa._interior(q_off, k_off, blk_q, blk_k, window, ends))
                assert interior == mask.all(), (q_off, k_off)
                edge += not interior
    assert fa.block_counts(S, blk_q, blk_k, window, documents) == (visited, edge)
    if row == "one-document":
        assert (visited, edge) == fa.block_counts(S, blk_q, blk_k, window)


def test_the_block_counters_reach_the_metrics():
    """Tracing a core raises `kungfu_flash_blocks_visited_total` and
    `kungfu_flash_blocks_masked_total` by `block_counts`' numbers a head,
    for each kernel it builds: the forward kernel masks the edge blocks, the
    two backward kernels every block they visit. `kungfu_flash_grid_steps_total`
    rises by `grid_steps`': the visited blocks of a causal core without a
    window (the square grid had 16 for these 10), the band's width a row
    under a window, dead steps among them."""
    from kungfu_tpu.telemetry import metrics

    fa = _fa()
    visited, edge = fa.block_counts(1024, 256, 256)
    assert (visited, edge) == (10, 4)
    assert fa.grid_steps(1024, 256, 256, True) == (10, 10)
    assert fa.grid_steps(1024, 256, 256, True, 256) == (8, 8)
    assert fa.grid_steps(1024, 256, 256, False) == (16, 16)
    names = ("kungfu_flash_blocks_visited_total", "kungfu_flash_blocks_masked_total",
             "kungfu_flash_grid_steps_total")

    def read():
        return {(name, kernel): metrics.counter(
            name, labelnames=("kernel",)).labels(kernel).value
            for name in names for kernel in ("forward", "dq", "dkv")}

    q = jnp.zeros((1, 3, 1024, 64))
    before = read()
    jax.jit(jax.grad(lambda q: jnp.sum(
        fa.flash_attention(q, q, q, True, None, 256, 256, True)))).lower(q)
    after = read()
    for (name, kernel), was in before.items():
        masked = edge if kernel == "forward" else visited
        want = 3 * (masked if "masked" in name else visited)
        assert after[(name, kernel)] - was == want, (name, kernel)
    # a band core: every visited block is masked, and a row's first step of
    # two is dead in the first row
    before = after
    jax.jit(lambda q: fa.flash_attention(q, q, q, True, None, 256, 256, True, 256)
            ).lower(q)
    after = read()
    for name, want in zip(names, (7, 7, 8)):
        assert after[(name, "forward")] - before[(name, "forward")] == 3 * want, name


# the three kinds of call: (query heads to a key/value head, window)
@pytest.mark.parametrize("g,window", [(1, None), (3, None), (1, 24)],
                         ids=["plain", "grouped", "window"])
def test_a_core_that_is_run_again_runs_its_forward_kernel_once(g, window):
    """Wrapped as `models/transformer._layer_again` wraps a layer, every
    kind of call keeps its output and one row sum a row under the policy's
    two names: the gradient holds the forward kernel once, outside the
    recomputed part, and is the gradient of the bare call bit for bit."""
    q, k, v, weigh = _grouped_qkv(64, g)

    def core(q, k, v):
        return flash_attention(q, k, v, True, None, 16, 16, True, window)

    again = jax.checkpoint(
        core, prevent_cse=False,
        policy=jax.checkpoint_policies.save_only_these_names("flash_out", "flash_lse"))

    def out_and_grads(core):
        def weighed(q, k, v):
            out = core(q, k, v)
            return jnp.sum(out * weigh), out

        return jax.value_and_grad(weighed, argnums=(0, 1, 2), has_aux=True)

    jaxpr = jax.make_jaxpr(out_and_grads(again))(q, k, v).jaxpr
    assert sorted(pallas_calls(jaxpr)) == [
        ("_dkv_kernel", True), ("_dq_kernel", True), ("_kernel", False)]
    kept = {eqn.params["name"]: eqn.outvars[0] for eqn in jaxpr.eqns
            if eqn.primitive.name == "name"}
    assert kept["flash_out"].aval.shape == q.shape
    assert kept["flash_lse"].aval.shape == (q.shape[0] * q.shape[1], q.shape[2])
    (backward,) = [eqn for eqn in jaxpr.eqns if eqn.primitive.name == "remat2"]
    assert set(kept.values()) <= set(backward.invars)
    ((_, out), grads), ((_, want_out), want) = (
        jax.jit(out_and_grads(f))(q, k, v) for f in (again, core))
    for got, ref in zip((out, *grads), (want_out, *want)):
        assert got.dtype == ref.dtype and bool(jnp.array_equal(got, ref))


if __name__ == "__main__":  # one of `both_forms_bits`' own processes
    import json
    import sys

    cases = ([(_five_of_both_forms, c) for c in _BIT_CASES]
             + [(_five_of_both_grids, c) for c in _GRID_CASES]
             + [(_five_with_and_without_the_skip, c) for c in _SKIP_CASES])
    print(json.dumps({"-".join(map(str, case)): five(*case)
                      for five, case in cases[int(sys.argv[1])::_BIT_CHILDREN]}))


# -- packed rows (PR 52): `segments` number each position's document ---------

# five documents in 256 positions: boundaries inside a block of 64 x 32 (40,
# 228), on a key block's edge (64) and on both blocks' edge (128)
PACKED = (40, 24, 64, 100, 28)


@pytest.mark.parametrize("hd,g,window", [(64, 4, None), (64, 4, 48),
                                         (16, 1, None), (16, 3, 24)])
def test_a_packed_row_is_its_documents_run_one_at_a_time(hd, g, window):
    """Values and the three gradients, to float32's rounding, under a scale
    of the scores that is not 1 / sqrt(head size): a query sees the keys of
    its own document, interior blocks of one document go unmasked, and a
    block of two is masked."""
    S, Hkv, scale = sum(PACKED), 2, 1.0 / 64
    edges = np.cumsum((0,) + PACKED)
    segments = jnp.asarray(np.repeat(np.arange(len(PACKED)), PACKED))[None]
    ks = jax.random.split(jax.random.PRNGKey(hd + g), 4)
    q = 4 * jax.random.normal(ks[0], (1, g * Hkv, S, hd))
    k = 4 * jax.random.normal(ks[1], (1, Hkv, S, hd))
    v = jax.random.normal(ks[2], (1, Hkv, S, hd))
    weight = jax.random.normal(ks[3], q.shape)

    def packed(q, k, v):
        return flash_attention(q, k, v, True, scale, 64, 32, True, window, segments)

    def alone(q, k, v):
        return jnp.concatenate([
            flash_attention(q[:, :, a:b], k[:, :, a:b], v[:, :, a:b], True,
                            scale, 4, 4, True, window)
            for a, b in zip(edges[:-1], edges[1:])], axis=2)

    def rel(got, want):
        return float(jnp.linalg.norm((got - want).ravel())
                     / jnp.linalg.norm(want.ravel()))

    assert rel(packed(q, k, v), alone(q, k, v)) < 2e-6
    got = jax.grad(lambda *a: jnp.sum(packed(*a) * weight), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(alone(*a) * weight), (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        assert rel(a, b) < 2e-6, name
    one_document = flash_attention(q, k, v, True, scale, 64, 32, True, window)
    assert rel(one_document, packed(q, k, v)) > 0.1


def test_without_segments_the_kernels_are_the_program_they_were():
    """No operand and no equation more without segments, forward and
    backward (q, k, v and what the backward kernels read, behind the table
    of a causal sweep's live pairs, PR 53); a packed call's three kernels
    take three operands more, the blocks' bounds for scalar memory and the
    documents' numbers for the queries and for the keys; and segments of a
    core that is not causal, or of another shape, are refused."""
    from jaxprs import pallas_operands

    q, k, v = _qkv(B=1, H=2, S=64, hd=16)
    segments = jnp.asarray(np.repeat([0, 1], [40, 24]))[None]

    def both(*extra):
        return jax.make_jaxpr(jax.value_and_grad(
            lambda *a: jnp.sum(flash_attention(*a, True, None, 32, 32, True,
                                               None, *extra)), (0, 1, 2)))(q, k, v)

    assert str(both()) == str(both(None))
    assert pallas_operands(both().jaxpr) == {
        "_kernel": 4, "_dq_kernel": 7, "_dkv_kernel": 7}
    assert pallas_operands(both(segments).jaxpr) == {
        "_kernel": 7, "_dq_kernel": 10, "_dkv_kernel": 10}
    with pytest.raises(ValueError, match="segments"):
        flash_attention(q, k, v, False, None, 32, 32, True, None, segments)
    with pytest.raises(ValueError, match="segments"):
        flash_attention(q, k, v, True, None, 32, 32, True, None, segments[:, :32])


# --- value heads of their own size (PR 69) -----------------------------------

# latent attention without positions: q/k heads of 128 + 64 features on value
# heads of 128. One case a kernel: the forward kernel by the output, dQ by q's
# gradient, dK/dV by k's and v's; 6 query heads on 6 and on 2 key/value heads.
@pytest.mark.parametrize("g", [1, 3])
@pytest.mark.parametrize("kernel", ["forward", "dq", "dkv"])
def test_value_heads_of_128_under_qk_heads_of_192(kernel, g):
    H, Hkv, S, hd, hd_v = 6, 6 // g, 256, 192, 128
    ks = jax.random.split(jax.random.PRNGKey(11 + g), 4)
    q = jax.random.normal(ks[0], (1, H, S, hd))
    k = jax.random.normal(ks[1], (1, Hkv, S, hd))
    v = jax.random.normal(ks[2], (1, Hkv, S, hd_v))
    weight = jax.random.normal(ks[3], (1, H, S, hd_v))
    scale = 1.0 / np.sqrt(hd)

    def flash(q, k, v):
        return flash_attention(q, k, v, True, None, 128, 128, True)

    def dense(q, k, v):
        return _dense_reference(q, k, v, True, scale)

    if kernel == "forward":
        out = flash(q, k, v)
        assert out.shape == (1, H, S, hd_v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense(q, k, v)),
                                   rtol=1e-5, atol=1e-5)
        # a scale of 1 / sqrt(128), the value heads' size, is another core
        wrong = _dense_reference(q, k, v, True, 1.0 / np.sqrt(hd_v))
        assert float(jnp.max(jnp.abs(out - wrong))) > 0.05
        return
    args = {"dq": (0,), "dkv": (1, 2)}[kernel]
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * weight), args)(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * weight), args)(q, k, v)
    for mine, ref, of in zip(got, want, args):
        assert mine.shape == ref.shape == (q, k, v)[of].shape
        np.testing.assert_allclose(np.asarray(mine), np.asarray(ref),
                                   rtol=1e-4, atol=2e-5)
