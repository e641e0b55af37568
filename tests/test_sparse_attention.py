"""`ops.sparse_attention` (PR 61): the indexer's scores, the exact choice, the
core over the chosen keys and the head-mean of its probabilities, the kernels
interpreted against the plain `jnp` forms and against a dense softmax under
the mask written here: values and every gradient, float32 and bfloat16, at 256
positions with 64 keys a query and blocks of 64, so that rows choose, rows do
not, and blocks are crossed. The choice's kernel (PR 62) against its plain
form bit for bit, at 256 to 1,024 positions. The scores at the test's head of
16 and the cell's of 64 (PR 64: their float32 products are six bfloat16 terms
side by side, all six in a pass at 16, two a pass at 64), and on inputs on
which a product that drops a term is wrong in the first digit."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kungfu_tpu.ops import sparse_attention as dsa

B, S, K, BLK = 2, 256, 64, 64
H, HKV, HD = 4, 2, 32  # the core's query heads on key/value heads
HI, DI = 3, 16  # the indexer's heads


def _normal(seed, *shape, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape, np.float32), dtype)


def _error(got, want):
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def _causal(x):
    return jnp.where(np.tril(np.ones(x.shape[-2:], bool)), x, 0.0)


def _scored(qI, kI, w):
    return qI, kI, w, jax.jit(lambda *a: dsa.index_scores(
        *a, BLK, BLK, True))(qI, kI, w)


@pytest.fixture(scope="module")
def scores():
    """The indexer's inputs and their scores under the kernel."""
    return _scored(_normal(1, B, S, HI, DI), _normal(2, B, S, DI),
                   0.3 * _normal(3, B, S, HI))


@pytest.fixture(scope="module", params=[DI, 64], ids="head{}".format)
def scores_at(request, scores):
    """`scores` at the tests' head and at the Keye cell's, two heads."""
    if request.param == DI:
        return scores
    return _scored(_normal(1, B, S, 2, request.param),
                   _normal(2, B, S, request.param), 0.3 * _normal(3, B, S, 2))


def _choice(form, I, k, rows=None, span=dsa.LANES):
    """The choice of I by the `plain` form or by the `kernel`, interpreted,
    its block `rows` rows (the module's own where None) and its loop's turn
    `span` keys."""
    if form == "plain":
        return jax.jit(lambda I: dsa.plain_select(I, k))(I)
    with pytest.MonkeyPatch.context() as m:
        if rows:
            m.setattr(dsa, "SELECT_BLOCK_BYTES", rows * I.shape[-1] * 4)
        m.setattr(dsa, "SELECT_SPAN", span)
        assert dsa._select_rows(I.shape[-1]) == (rows or I.shape[-1])
        return jax.jit(lambda I: dsa.select(I, k, True))(I)


FORMS = pytest.mark.parametrize("form", ["plain", "kernel"])


@pytest.fixture(scope="module")
def chosen(scores):
    return _choice("plain", scores[3], K)


def test_the_scores_are_the_sum_over_the_heads_under_the_diagonal(scores_at):
    qI, kI, w, got = scores_at
    want = _scores64(qI, kI, w)
    assert _error(_causal(got), _causal(want)) <= 1e-6
    assert _error(_causal(dsa.plain_index_scores(qI, kI, w)), _causal(want)) <= 1e-6


def test_the_scores_gradients_are_the_plain_forms(scores_at):
    qI, kI, w, _ = scores_at
    weight = _causal(_normal(4, B, S, S))

    def through(op):
        return jax.jit(jax.grad(lambda *a: jnp.sum(
            _causal(op(*a)) * weight), argnums=(0, 1, 2)))(qI, kI, w)

    got = through(lambda *a: dsa.index_scores(*a, BLK, BLK, True))
    for g, want in zip(got, through(dsa.plain_index_scores), strict=True):
        assert g.shape == want.shape and _error(g, want) <= 1e-5


def _scores64(qI, kI, w, products=None):
    """The scores in float64, from the heads' products (B, Hi, S, S) where
    given."""
    qI, kI, w = (np.asarray(x, np.float64) for x in (qI, kI, w))
    if products is None:
        products = np.einsum("btjd,bsd->bjts", qI, kI)
    return np.einsum("btj,bjts->bts", w, np.maximum(products, 0.0))


def _pieces(x):
    """float32 x as the kernels' three bfloat16 pieces, in float64."""
    return [np.asarray(p, np.float64)
            for p in dsa._pieces(jnp.asarray(x, jnp.float32))]


def _three_scales(seed, rows, directions):
    """(x, its three pieces) with x[..., :] = a h0 + b 2^-10 h1 + c 2^-20 h2,
    a, b, c integers a row (3, 5, 6 or 7, either sign: no power of two, so
    that no sum leaves its binade) and h0, h1, h2 three orthogonal rows of
    +-1: every element's bfloat16 pieces are +-a, +-b 2^-10 and +-c 2^-20 to
    the bit, and float32 holds their sum to the bit."""
    rng = np.random.default_rng(seed)
    pieces = [rng.choice([3.0, 5.0, 6.0, 7.0], rows + (1,))
              * rng.choice([-1.0, 1.0], rows + (1,)) * 2.0 ** (-10 * i) * h
              for i, h in enumerate(directions)]
    x = jnp.asarray(sum(pieces), jnp.float32)
    assert np.array_equal(np.asarray(x, np.float64), sum(pieces))
    for got, want in zip(_pieces(x), pieces, strict=True):
        assert np.array_equal(got, want)
    return x, pieces


# a float32 product at the highest precision keeps these six products of the
# operands' pieces (0 hi, 1 mid, 2 lo), a bfloat16x3 product the first three
KEPT_BY_THREE = ((0, 0), (0, 1), (1, 0))
KEPT_BY_SIX = KEPT_BY_THREE + ((1, 1), (0, 2), (2, 0))


@pytest.mark.parametrize("d", [DI, 64], ids="head{}".format)
def test_no_term_of_the_float32_products_is_left_out(d):
    """Queries along h1 + e h2 + e^2 h3 and keys along h3 + e h2 + e^2 h1 (e =
    2^-10, h three orthogonal rows of +-1): over a head's d features hi.hi,
    hi.mid and mid.hi sum to nothing, mid.mid, hi.lo and lo.hi to d e^2 times
    integers, the three terms below them to nothing. The kernel's scores are
    the float64 product's; a product of the three leading terms, written out
    here from the same pieces, is zero everywhere. The gradients' products
    run over keys and queries, where nothing cancels, so each is read along
    the direction that holds one scale of the right operand: along the e^2
    direction hi.lo is all there is, along the e direction mid.mid is the
    second term, along the whole direction lo.hi the third."""
    heads = 2
    h = [np.array([(-1.0) ** bin(r & c).count("1") for c in range(d)])
         for r in (1, 2, 3)]
    qI, q3 = _three_scales(5, (B, S, heads), h)
    kI, k3 = _three_scales(6, (B, S), h[::-1])
    w = jnp.asarray(np.random.default_rng(7).choice([0.25, 0.5, 1.0], (B, S, heads)),
                    jnp.float32)

    def products(terms):
        return sum(np.einsum("btjd,bsd->bjts", q3[a], k3[b]) for a, b in terms)

    want = _scores64(qI, kI, w)
    assert np.array_equal(_scores64(qI, kI, w, products(KEPT_BY_SIX)), want)
    assert _error(_causal(_scores64(qI, kI, w, products(KEPT_BY_THREE))),
                  _causal(want)) >= 1e-4
    got = jax.jit(lambda *a: dsa.index_scores(*a, BLK, BLK, True))(qI, kI, w)
    assert _error(_causal(got), _causal(want)) <= 1e-6

    # the gradients under a cotangent dI: ds = dI w [s > 0] a head, float32
    dI = _causal(_normal(8, B, S, S))
    dq, dk, dw = jax.jit(jax.grad(lambda *a: jnp.sum(
        dsa.index_scores(*a, BLK, BLK, True) * dI), argnums=(0, 1, 2)))(qI, kI, w)
    s = np.einsum("btjd,bsd->bjts", *(np.asarray(x, np.float64) for x in (qI, kI)))
    ds = jnp.where(s > 0, dI[:, None] * w.transpose(0, 2, 1)[..., None], 0.0)
    ds3 = _pieces(ds)
    assert _error(dw, np.einsum("bts,bjts->btj", dI, np.maximum(s, 0.0))) <= 1e-6
    # along the direction of (the right operand's hi, mid, lo): the error of
    # all six terms at most, of the leading three at least. The kernel reads
    # 6e-8, 3e-5 and 3e-2 (its float32 result holds the smaller scales to
    # that), the three terms 2.3e-6 (no lo.hi), 1.6e-3 (no mid.mid) and 1
    limits = ((5e-7, 1.5e-6), (2e-4, 8e-4), (0.2, 0.5))
    for got, form, right, along in ((dq, "bjts,bsd->btjd", k3, h[::-1]),
                                    (dk, "bjts,btjd->bsd", q3, h)):
        def product(terms):
            return sum(np.einsum(form, ds3[a], right[b]) for a, b in terms)

        want = np.einsum(form, np.asarray(ds, np.float64), sum(right))
        assert _error(got, want) <= 1e-6
        for direction, (six, three) in zip(along, limits, strict=True):
            assert _error(np.asarray(got) @ direction, want @ direction) <= six
            assert _error(product(KEPT_BY_SIX) @ direction, want @ direction) <= six
            assert _error(product(KEPT_BY_THREE) @ direction, want @ direction) >= three


@FORMS
def test_the_choice_is_the_sorted_rows_first_keys(scores, form):
    """Against a stable sort of each row's causal part: row t takes its
    min(t + 1, K) largest, so the first K rows take every earlier key and the
    later ones choose."""
    I, got = np.asarray(scores[3]), np.asarray(_choice(form, scores[3], K, 64))
    assert got.dtype == np.int8 and got.shape == (B, S, S)
    for b in range(B):
        for t in range(0, S, 7):
            order = np.argsort(-I[b, t, :t + 1], kind="stable")
            want = np.zeros(S, np.int8)
            want[order[:min(t + 1, K)]] = 1
            np.testing.assert_array_equal(got[b, t], want, err_msg=f"{b} {t}")
    assert (got.sum(-1) == np.minimum(np.arange(S) + 1, K)).all()
    assert not np.triu(got, 1).any()


@FORMS
def test_a_tie_goes_to_the_lower_position(form):
    """Rows of a few distinct values, so that the K-th largest is shared: of
    the keys that equal it the first are taken, as `lax.top_k` and a stable
    sort take them; a zero of either sign is one value."""
    rng = np.random.default_rng(5)
    I = rng.integers(-2, 3, (1, S, S)).astype(np.float32)
    I[0, 200] = 0.0
    I[0, 200, ::2] = -0.0
    I[0, 201] = np.where(np.arange(S) % 3 == 0, 1.5, -1.0)
    got = np.asarray(_choice(form, jnp.asarray(I), K, 32))
    for t in (63, 64, 100, 200, 201, 255):
        order = np.argsort(-I[0, t, :t + 1], kind="stable")
        want = np.zeros(S, np.int8)
        want[order[:min(t + 1, K)]] = 1
        np.testing.assert_array_equal(got[0, t], want, err_msg=str(t))
        _, top = jax.lax.top_k(jnp.where(jnp.arange(S) <= t, I[0, t] + 0.0,
                                         -jnp.inf), min(t + 1, K))
        assert sorted(np.flatnonzero(got[0, t])) == sorted(np.asarray(top))
    assert np.flatnonzero(got[0, 200]).tolist() == list(range(K))


def _scores_of(kind, S, rng):
    """(S, S) scores of one `kind`, what lies above the diagonal as a block
    that `index_scores` never wrote may hold it."""
    if kind == "random":
        return rng.standard_normal((S, S), np.float32)
    if kind == "few_values":  # ties at the k-th largest in every row
        return rng.integers(-2, 3, (S, S)).astype(np.float32)
    if kind == "zeros_of_both_signs":
        I = np.where(rng.random((S, S)) < 0.5, 0.0, -0.0).astype(np.float32)
        return np.where(rng.random((S, S)) < 0.2, rng.standard_normal(
            (S, S), np.float32), I)
    assert kind == "nan_and_inf_above"
    I = rng.standard_normal((S, S), np.float32)
    above = np.triu(np.ones((S, S), bool), 1)
    return np.where(above, np.where(rng.random((S, S)) < 0.5, np.nan, np.inf), I)


# (S, k, the block's rows, the loop's span) -> the kinds of scores in a batch
SHAPES = {
    (512, 100, 64, 256): ("random", "few_values", "zeros_of_both_signs",
                          "nan_and_inf_above"),
    (1024, 192, 32, 512): ("random", "few_values"),
    (256, 300, None, 128): ("random", "few_values"),  # k >= S, one row block
}


@functools.cache
def _both_forms(shape):
    """(scores, the kernel's choice, the plain form's) of a shape's batch."""
    S, k, rows, span = shape
    rng = np.random.default_rng(S + k)
    I = jnp.asarray(np.stack([_scores_of(kind, S, rng) for kind in SHAPES[shape]]))
    return (np.asarray(I), np.asarray(_choice("kernel", I, k, rows, span)),
            np.asarray(_choice("plain", I, k)))


@pytest.mark.parametrize("shape,kind", [
    pytest.param(shape, kind, id=f"{kind}-{shape[0]}-{shape[1]}")
    for shape, kinds in SHAPES.items() for kind in kinds])
def test_the_kernels_choice_is_the_plain_forms_bit_for_bit(shape, kind):
    """Every byte, whatever the scores: ties at the k-th largest (the
    position passes run), zeros of both signs, NaN and +inf where nothing
    was written, k past S (every row takes all it sees), one row block and
    many, blocks whose counting stops before the row's end."""
    S, k = shape[:2]
    I, got, want = (x[SHAPES[shape].index(kind)] for x in _both_forms(shape))
    assert got.dtype == np.int8 and got.shape == (S, S)
    np.testing.assert_array_equal(got, want)
    assert (got.sum(-1) == np.minimum(np.arange(S) + 1, k)).all()
    assert not np.triu(got, 1).any()
    if kind == "few_values":
        ties = [t for t in range(S) if (np.sort(I[t, :t + 1])[::-1][:k][-1]
                                        == I[t, :t + 1]).sum() > 1]
        assert len(ties) > S // 2  # the k-th largest is shared: a tie is cut


def test_a_row_block_in_which_no_row_chooses_takes_every_earlier_key():
    """Rows 0 to 63 of 512 at 100 keys a query are one block of the kernel
    and see no more than 64 keys each: the block is the causal triangle."""
    got = _both_forms((512, 100, 64, 256))[1]
    np.testing.assert_array_equal(
        got[:, :64], np.broadcast_to(np.tril(np.ones((64, 512), np.int8)),
                                     (got.shape[0], 64, 512)))


def test_the_plain_form_where_no_block_of_rows_tiles_the_scores():
    """96 positions are no whole lane tile, and 36,864 are more than a block
    of 32 rows may hold: `select` is `plain_select` there."""
    assert dsa._select_rows(96) == 0 and dsa._select_rows(36864) == 0
    assert dsa._select_rows(8192) == 128 and dsa._select_rows(384) == 384
    I = _normal(6, 1, 96, 96)
    np.testing.assert_array_equal(jax.jit(lambda I: dsa.select(I, 16))(I),
                                  _choice("plain", I, 16))


def _dense(q, k, v, chosen):
    """The core as a dense softmax under the mask, float64 numpy."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    k, v = (np.repeat(x, H // HKV, axis=1) for x in (k, v))
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(HD)
    s = np.where(np.asarray(chosen)[:, None] != 0, s, -np.inf)
    a = np.exp(s - s.max(-1, keepdims=True))
    a /= a.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", a, v), a


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_the_core_and_its_gradients_see_the_chosen_keys_alone(chosen, dtype, tol):
    q, k, v = (_normal(6 + i, B, h, S, HD, dtype=dtype)
               for i, h in enumerate((H, HKV, HKV)))
    weight = _normal(9, B, H, S, HD)

    def through(op):
        def loss(q, k, v):
            out, lse = op(q, k, v, chosen)
            return jnp.sum(out.astype(jnp.float32) * weight), (out, lse)

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))(
            q, k, v)

    (_, (out, lse)), grads = through(
        lambda *a: dsa.sparse_attention(*a, None, BLK, BLK, True))
    (_, (plain_out, plain_lse)), plain = through(dsa.plain_sparse_attention)
    want, _ = _dense(q, k, v, chosen)
    assert out.dtype == dtype and lse.dtype == jnp.float32
    assert _error(out, want) <= tol and _error(plain_out, want) <= tol
    assert _error(lse, plain_lse) <= 1e-5
    for g, p in zip(grads, plain, strict=True):
        assert g.dtype == dtype and _error(g, p) <= tol
    # a key that is not chosen moves nothing: its value may be anything
    unchosen = np.asarray(chosen)[0].sum(0) == 0
    assert unchosen[:-1].any()
    moved = v.at[0, :, np.flatnonzero(unchosen)[0]].set(1e4)
    again, _ = jax.jit(lambda *a: dsa.sparse_attention(*a, None, BLK, BLK, True))(
        q, k, moved, chosen)
    np.testing.assert_array_equal(np.asarray(again[0], np.float32),
                                  np.asarray(out[0], np.float32))


def test_the_head_mean_is_the_mean_of_the_heads_probabilities(chosen):
    q, k, v = (_normal(6 + i, B, h, S, HD) for i, h in enumerate((H, HKV, HKV)))
    _, lse = dsa.plain_sparse_attention(q, k, v, chosen)
    _, a = _dense(q, k, v, chosen)
    p, entropy = jax.jit(lambda *args: dsa.head_mean_probs(
        *args, None, BLK, BLK, True))(q, k, lse, chosen)
    seen = np.asarray(chosen) != 0
    assert _error(np.where(seen, p, 0.0), a.mean(1)) <= 1e-5
    np.testing.assert_allclose(np.where(seen, p, 0.0).sum(-1), 1.0, rtol=1e-5)
    mean = a.mean(1)
    np.testing.assert_allclose(entropy, np.where(
        seen, mean * np.log(np.where(seen, mean, 1.0)), 0.0).sum(-1), rtol=1e-4)
    plain_p, plain_entropy = dsa.plain_head_mean_probs(q, k, lse, chosen)
    assert _error(plain_p, a.mean(1)) <= 1e-5
    np.testing.assert_allclose(plain_entropy, entropy, rtol=1e-4)
    # a constant: nothing is differentiated through the target
    grads = jax.grad(lambda q, k: jnp.sum(jnp.where(seen, dsa.head_mean_probs(
        q, k, lse, chosen, None, BLK, BLK, True)[0], 0.0)), argnums=(0, 1))(q, k)
    assert not any(np.asarray(g).any() for g in grads)


def test_the_loss_is_the_kl_divergence_and_its_gradient_softmax_less_p(scores, chosen):
    I = scores[3]
    q, k, v = (_normal(6 + i, B, h, S, HD) for i, h in enumerate((H, HKV, HKV)))
    _, lse = dsa.plain_sparse_attention(q, k, v, chosen)
    p, entropy = dsa.plain_head_mean_probs(q, k, lse, chosen)
    seen = np.asarray(chosen) != 0
    loss, dI = jax.value_and_grad(dsa.indexer_kl)(I, chosen, p, entropy)
    logits = np.where(seen, np.asarray(I, np.float64), -np.inf)
    soft = np.exp(logits - logits.max(-1, keepdims=True))
    soft /= soft.sum(-1, keepdims=True)
    pd = np.where(seen, np.asarray(p, np.float64), 0.0)
    kl = np.where(seen, pd * (np.log(np.where(seen, pd, 1.0))
                              - np.log(np.where(seen, soft, 1.0))), 0.0)
    assert float(loss) == pytest.approx(kl.sum(-1).mean(), rel=1e-5)
    assert float(loss) > 0
    assert _error(dI, (soft - pd) / (B * S)) <= 1e-5
    assert not np.asarray(dI)[~seen].any()
    # what lies outside the choice is never read: NaNs there move nothing
    dirty = jnp.where(seen, I, jnp.nan), jnp.where(seen, p, jnp.nan)
    again, dI_again = jax.value_and_grad(dsa.indexer_kl)(
        dirty[0], chosen, dirty[1], entropy)
    assert float(again) == float(loss)
    np.testing.assert_array_equal(np.asarray(dI_again), np.asarray(dI))
