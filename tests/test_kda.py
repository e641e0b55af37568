"""`ops.kda`: the chunked delta rule whose decay is a number a key feature
against the recurrence a position at a time (the definition,
`benchmark/reference/kimi_linear.delta_rule`), outputs and the gradients of
all five inputs, under weak, strong and mixed decays; the pairs' kernel
against the pairs written out, and the running sums it and its transpose
make against `jnp.cumsum`; what a decay a head, a state in bfloat16 and an
exp(G) beside an exp(-G) would cost."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.kimi_linear import delta_rule
from kungfu_tpu.ops import gated_delta, kda
from kungfu_tpu.ops.kda import kda_rule


def _inputs(seed, B, H, S, dk, dv, decay, dtype=jnp.float32):
    """Normalised q and k as the layer hands them over, beta in (0, 1), and
    a log decay a position and key feature of `decay` on average (0: none).
    `decay` "mixed": every other feature forgets by e^-20 a position and
    the ones between by e^-0.001."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, H, S, dk))
    k = jax.random.normal(ks[1], (B, H, S, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, H, S, dv))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (B, H, S)))
    if decay == "mixed":
        g = jnp.broadcast_to(jnp.where(jnp.arange(dk) % 2 == 0, -20.0, -0.001),
                             (B, H, S, dk))
    else:
        g = -decay * jax.random.uniform(ks[4], (B, H, S, dk), minval=0.5,
                                        maxval=1.5)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def _weighted(fn, weight):
    return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weight)


def _running(g, chunk):
    """The running sum of g inside each chunk, XLA's: the reference of the
    kernels' own."""
    B, H, S, dk = g.shape
    return jnp.cumsum(g.reshape(B, H, S // chunk, chunk, dk),
                      axis=3).reshape(g.shape)


CASES = [
    # S, chunk, B, H, decay a position and feature
    (32, 8, 1, 2, 0.05),      # four chunks of one sub-block
    (128, 64, 1, 2, 0.02),    # two chunks of the model's size, four sub-blocks
    (64, 32, 2, 1, 3.0),      # strong: exp(-3) a position, two sub-blocks
    (128, 64, 1, 1, "mixed"),  # e^-20 beside e^-0.001, feature by feature
]
IDS = ["-".join(map(str, case)) for case in CASES]


@functools.cache
def _both_ways(S, chunk, B, H, decay):
    """A case's inputs, then the recurrence's and the kernels' output and
    five gradients under one weight: the tests of a case share them."""
    args = _inputs(S + chunk, B, H, S, 16, 24, decay)
    weight = jax.random.normal(jax.random.PRNGKey(7), (B, H, S, 24))

    def both(fn):
        return fn(*args), jax.grad(_weighted(fn, weight),
                                   argnums=(0, 1, 2, 3, 4))(*args)

    return (args, both(lambda *a: delta_rule(*a, block=16)),
            both(lambda *a: kda_rule(*a, chunk)))


@pytest.mark.parametrize("S,chunk,B,H,decay", CASES, ids=IDS)
def test_outputs_and_all_five_gradients_agree_with_the_recurrence(
        S, chunk, B, H, decay):
    _, (want, want_grads), (got, grads) = _both_ways(S, chunk, B, H, decay)
    assert got.shape == (B, H, S, 24) and got.dtype == jnp.float32
    assert bool(jnp.isfinite(got).all())
    assert _rel(got, want) < 2e-5
    for name, g, w in zip(("q", "k", "v", "g", "beta"), grads, want_grads):
        assert g.shape == w.shape, name
        assert bool(jnp.isfinite(g).all()), name
        assert _rel(g, w) < 5e-5, name


def _pairs_written_out(q, k, G):
    """A and P of one chunk, every pair's decay feature by feature."""
    E = jnp.exp(jnp.minimum(G[:, None, :] - G[None, :, :], 0.0))
    A = jnp.einsum("ic,ijc,jc->ij", k, E, k)
    P = jnp.einsum("ic,ijc,jc->ij", q, E, k)
    return jnp.tril(A, -1), jnp.tril(P)


@pytest.mark.parametrize("decay,atol", [(0.05, 1e-7), (3.0, 2e-6),
                                        ("mixed", 1e-7)])
def test_the_pairs_kernel_against_the_pairs_written_out(decay, atol):
    """One chunk of 64 in sub-blocks of 16: three strips through a
    reference row and sixteen distances inside a sub-block. The reference's
    G is XLA's sum and the kernel's is its own: under e^-3 a position G
    reaches -190, where float32 sums taken in two orders stand 2e-5 apart,
    and the pairs made from them 1e-6 (each 7e-7 from the pairs of a
    float64 G)."""
    q, k, _, g, _ = _inputs(4, 1, 1, 64, 16, 16, decay)
    G = _running(g, 64)
    A, P, _ = kda._pairs_call(q, k, g, chunk=64, interpret=True)
    want_A, want_P = _pairs_written_out(q[0, 0], k[0, 0], G[0, 0])
    assert A.shape == P.shape == (1, 1, 1, 64, 64)
    np.testing.assert_allclose(A[0, 0, 0], want_A, rtol=1e-5, atol=atol)
    np.testing.assert_allclose(P[0, 0, 0], want_P, rtol=1e-5, atol=atol)
    # nothing above the diagonal, and A's diagonal is empty
    assert not np.triu(np.asarray(A[0, 0, 0])).any()
    assert not np.triu(np.asarray(P[0, 0, 0]), 1).any()


@pytest.mark.parametrize("decay", [0.05, 3.0, "mixed"])
def test_the_pairs_kernel_makes_the_running_sum_of_each_chunk(decay):
    """G, the kernel's third output, against `jnp.cumsum` a chunk in
    float32: two heads of three chunks, so that no sum crosses a chunk's or
    a head's edge."""
    q, k, _, g, _ = _inputs(3, 1, 2, 192, 16, 16, decay)
    _, _, G = kda._pairs_call(q, k, g, chunk=64, interpret=True)
    assert G.shape == g.shape and G.dtype == jnp.float32
    np.testing.assert_allclose(G, _running(g, 64), rtol=1e-6, atol=0)
    np.testing.assert_array_equal(G[:, :, ::64], g[:, :, ::64])


def test_the_pairs_backward_kernel_sums_both_halves_of_dG_to_the_chunks_end():
    """dg against XLA's reverse sum a chunk: the half the kernel is handed
    beside the half it makes itself (which a dG of zeros leaves alone)."""
    q, k, _, g, _ = _inputs(12, 1, 2, 128, 16, 16, 0.3)
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    dA = jnp.tril(jax.random.normal(ks[0], (1, 2, 2, 64, 64)), -1)
    dP = jnp.tril(jax.random.normal(ks[1], (1, 2, 2, 64, 64)))
    dG = jax.random.normal(ks[2], g.shape)

    def dg(dG):
        return kda._pairs_back_call(q, k, _running(g, 64), dA, dP, dG,
                                    chunk=64, interpret=True)[2]

    handed = _running(dG[:, :, ::-1], 64)[:, :, ::-1]
    got = dg(dG) - dg(jnp.zeros_like(dG))
    np.testing.assert_allclose(got, handed, rtol=1e-5, atol=1e-5)
    # a chunk's last position keeps its own and nothing of the next chunk's
    np.testing.assert_allclose(got[:, :, 63::64], dG[:, :, 63::64],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("S,chunk,B,H,decay", CASES, ids=IDS)
def test_dg_is_dG_summed_from_each_position_to_its_chunks_end(
        S, chunk, B, H, decay):
    """The reverse sum: the log decays' gradient against the recurrence's
    where what G receives differs from row to row of a chunk (the
    recurrence's dg less the next position's is that row's dG), so a sum
    that ran the wrong way, or over a chunk's edge, would show."""
    _, (_, want_grads), (_, grads) = _both_ways(S, chunk, B, H, decay)
    want, got = want_grads[3], grads[3]
    rows = (want - jnp.roll(want, -1, axis=2)).reshape(B, H, S // chunk,
                                                       chunk, 16)[..., :-1, :]
    assert float(jnp.std(rows, axis=3).min()) > 0
    assert _rel(got, want) < 5e-5
    assert _rel(got[:, :, ::chunk], want[:, :, ::chunk]) < 5e-5  # a chunk's sum


def test_exp_g_and_exp_minus_g_formed_apart_do_not_survive_a_strong_decay():
    """What the sub-blocks are for: under e^-20 a position exp(-G) is
    infinite within five positions and the product a NaN, where the kernel's
    pairs are finite and the recurrence's."""
    q, k, v, g, beta = _inputs(6, 1, 1, 64, 16, 16, "mixed")
    G = _running(g, 64)[0, 0]
    apart = (k[0, 0] * jnp.exp(G)) @ (k[0, 0] * jnp.exp(-G)).T
    assert not bool(jnp.isfinite(apart).all())
    got = kda_rule(q, k, v, g, beta)
    assert bool(jnp.isfinite(got).all())
    assert _rel(got, delta_rule(q, k, v, g, beta, block=16)) < 2e-5


def test_a_decay_a_head_is_another_rule():
    """The fault the family's tests plant: every feature of a head decayed
    by the head's mean log decay, `ops.gated_delta`'s rule, is far from the
    rule with a decay a feature; with one number a head the two agree."""
    q, k, v, g, beta = _inputs(8, 1, 2, 128, 16, 16, 0.3)
    g = g * jnp.linspace(0.1, 3.0, 16)  # features that differ
    want = delta_rule(q, k, v, g, beta, block=16)
    a_head = gated_delta.gated_delta_rule(q, k, v, g.mean(-1), beta)
    assert _rel(a_head, want) > 0.1
    same = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    assert _rel(kda_rule(q, k, v, same, beta), a_head) < 2e-5


def test_bfloat16_operands_float32_state():
    """The model's types: bfloat16 q, k, v, float32 g and beta. The result
    is bfloat16's, a few parts in a thousand; a decay exp(g) rounded to
    bfloat16 (0.99 becomes 0.988) is ten times that away."""
    args = _inputs(5, 1, 2, 256, 32, 32, 0.01, jnp.bfloat16)
    args = args[:3] + (jnp.full_like(args[3], np.log(0.99)), args[4])
    exact = tuple(a.astype(jnp.float32) for a in args)
    want = delta_rule(*exact, block=64)
    got = kda_rule(*args)
    assert got.dtype == jnp.bfloat16
    assert _rel(got, want) < 1e-2
    decay = jnp.exp(args[3]).astype(jnp.bfloat16).astype(jnp.float32)
    rounded = exact[:3] + (jnp.log(decay), exact[4])
    assert _rel(delta_rule(*rounded, block=64), want) > 3e-2


@pytest.mark.parametrize("S,B,H,decay", [(1024, 1, 2, 0.02), (512, 2, 1, "mixed")])
def test_outputs_and_gradients_at_the_models_shapes(S, B, H, decay):
    """Heads of 128, chunks of 64, bfloat16; 1,024 positions are two grid
    blocks of eight chunks, so the scratch state crosses a block's edge."""
    low = _inputs(S, B, H, S, 128, 128, decay, jnp.bfloat16)
    exact = tuple(a.astype(jnp.float32) for a in low)
    got = kda_rule(*low)
    assert got.shape == (B, H, S, 128) and got.dtype == jnp.bfloat16
    assert _rel(got, delta_rule(*exact, block=64)) < 1e-2
    weight = jax.random.normal(jax.random.PRNGKey(9), (B, H, S, 128))
    want = jax.grad(_weighted(lambda *a: delta_rule(*a, block=64), weight),
                    argnums=(0, 1, 2, 3, 4))(*exact)
    got = jax.grad(_weighted(kda_rule, weight), argnums=(0, 1, 2, 3, 4))(*low)
    for name, g, w, a in zip(("q", "k", "v", "g", "beta"), got, want, low):
        assert g.shape == w.shape and g.dtype == a.dtype, name
        assert _rel(g, w) < 2e-2, name


def _state_rounded_to_bfloat16(q, k, v, G, beta, T, S):
    return _CHUNK(q, k, v, G, beta, T,
                  S.astype(jnp.bfloat16).astype(jnp.float32))


_CHUNK = kda._chunk
LONG_MEMORY = 2e-5  # float32 through and through is well under it


@pytest.mark.parametrize("fault", [None, "S"])
def test_a_long_memory_needs_a_float32_state(fault, monkeypatch):
    """A log decay of -0.001 a position and feature over 2,048 positions:
    what the first chunk wrote is still an eighth of itself at the end,
    through 32 states. In float32 the kernels are at the recurrence; with
    the state rounded to bfloat16 at every chunk they are not, which the
    benchmark's cell cannot see at its initial parameters."""
    q, k, v, g, beta = _inputs(11, 1, 2, 2048, 16, 16, 0.0)
    g = jnp.full_like(g, -0.001)
    want = delta_rule(q, k, v, g, beta, block=64)
    if fault:
        monkeypatch.setattr(kda, "_chunk", _state_rounded_to_bfloat16)
    error = _rel(kda_rule(q, k, v, g, beta), want)
    assert error > 10 * LONG_MEMORY if fault else error < LONG_MEMORY, error


def test_the_rule_is_kernels_and_no_loop():
    """Four `pallas_call`s of the rule's own and the inverse's, no `scan`
    and no `while` over the chunks outside a kernel."""
    args = _inputs(2, 1, 2, 256, 16, 24, 0.1)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(kda_rule(*a)),
                                    argnums=(0, 1, 2, 3, 4)))(*args)
    text = str(jaxpr)
    for kernel in ("kda_pairs", "kda_forward", "kda_backward",
                   "kda_pairs_backward", "gated_delta_solve"):
        assert kernel in text, kernel
    assert " scan[" not in text.split("pallas_call")[0]


def _equations(jaxpr):
    """Every equation outside a `pallas_call`, the sub-jaxprs' with it
    (`platform_dependent`'s branches, `custom_vjp`'s calls)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def test_the_running_sums_are_the_kernels_own():
    """Beside the test above: the jaxpr of the rule's gradient holds no
    `cumsum`, `reduce_window` or `rev` outside a `pallas_call` and no
    float32 add of g's shape (q and k are bfloat16 here, so theirs do not
    count), and still names the four kernels and the inverse's."""
    args = _inputs(2, 1, 2, 256, 16, 24, 0.1, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(kda_rule(*a).astype(jnp.float32)),
        argnums=(0, 1, 2, 3, 4)))(*args)
    eqns = list(_equations(jaxpr.jaxpr))
    names = {eqn.primitive.name for eqn in eqns}
    assert not {n for n in names if n.startswith(("cumsum", "reduce_window"))
                or n == "rev"}, names
    g = args[3]
    assert not [e for e in eqns if e.primitive.name == "add"
                and e.outvars[0].aval.shape == g.shape
                and e.outvars[0].aval.dtype == g.dtype]
    kernels = {e.params["name"] for e in eqns
               if e.primitive.name == "pallas_call"}
    assert kernels == {"kda_pairs", "kda_forward", "kda_backward",
                       "kda_pairs_backward", "gated_delta_solve"}


@pytest.mark.parametrize("S,chunk", [(100, 64), (64, 48), (32, 64)])
def test_a_length_the_chunk_does_not_divide_raises(S, chunk):
    args = _inputs(0, 1, 1, S, 8, 8, 0.1)
    with pytest.raises(ValueError, match="multiple of the chunk"):
        kda_rule(*args, chunk)
