"""`ops.gated_delta`: the chunked scan against the recurrence a position at
a time (the definition, `benchmark/reference/qwen3_next.delta_rule`), outputs
and the gradients of all five inputs; the triangular inverse; the causal
convolution against a plain loop."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.qwen3_next import delta_rule
from kungfu_tpu.ops import gated_delta
from kungfu_tpu.ops.gated_delta import causal_conv, gated_delta_rule


def _inputs(seed, B, H, S, dk, dv, decay, dtype=jnp.float32):
    """Normalised q and k as the layer hands them over, beta in (0, 1), and
    a log decay a position of `decay` a head on average (0: none)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, H, S, dk))
    k = jax.random.normal(ks[1], (B, H, S, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, H, S, dv))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (B, H, S)))
    g = -decay * jax.random.uniform(ks[4], (B, H, S), minval=0.5, maxval=1.5)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def _weighted(fn, weight):
    return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weight)


CASES = [
    # S, chunk, B, H, decay a position
    (32, 8, 1, 2, 0.05),    # four chunks
    (64, 64, 2, 1, 0.05),   # one chunk of the model's size: no carried state
    (128, 64, 1, 2, 0.02),  # two chunks of the model's size
    (96, 16, 2, 3, 0.0),    # no decay: the state only grows
    (64, 8, 1, 2, 3.0),     # strong decay: exp(-3) a position, 4e-11 a chunk
    (48, 16, 1, 1, 0.3),    # three chunks, a decay of the middle
]


@pytest.mark.parametrize("S,chunk,B,H,decay", CASES)
def test_outputs_agree_with_the_recurrence(S, chunk, B, H, decay):
    args = _inputs(S + chunk, B, H, S, 16, 24, decay)
    want = delta_rule(*args, block=16)
    got = gated_delta_rule(*args, chunk)
    assert got.shape == (B, H, S, 24) and got.dtype == jnp.float32
    assert _rel(got, want) < 2e-5


@pytest.mark.parametrize("S,chunk,B,H,decay", CASES)
def test_all_five_gradients_agree_with_the_recurrence(S, chunk, B, H, decay):
    args = _inputs(S + chunk + 1, B, H, S, 16, 24, decay)
    weight = jax.random.normal(jax.random.PRNGKey(7), (B, H, S, 24))
    want = jax.grad(_weighted(lambda *a: delta_rule(*a, block=16), weight),
                    argnums=(0, 1, 2, 3, 4))(*args)
    got = jax.grad(_weighted(lambda *a: gated_delta_rule(*a, chunk), weight),
                   argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, w in zip(("q", "k", "v", "g", "beta"), got, want):
        assert g.shape == w.shape, name
        assert _rel(g, w) < 5e-5, name


def test_alike_keys_do_not_cancel():
    """Successive keys nearly the same and beta near 1: I + A is then near a
    matrix of ones below the diagonal, whose Neumann terms grow
    binomially; block substitution stays at float32's rounding."""
    q, k, v, g, beta = _inputs(3, 1, 2, 128, 16, 16, 0.0)
    k = k[:, :, :1] + 0.05 * k
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = jnp.full_like(beta, 0.98)
    want = delta_rule(q, k, v, g, beta, block=16)
    assert _rel(gated_delta_rule(q, k, v, g, beta, 64), want) < 1e-4


def test_bfloat16_operands_float32_state():
    """The model's types: bfloat16 q, k, v, float32 g and beta. The result
    is bfloat16's, a few parts in a thousand; a decay exp(g) rounded to
    bfloat16 (0.99 becomes 0.988) is ten times that away."""
    args = _inputs(5, 1, 2, 256, 32, 32, 0.01, jnp.bfloat16)
    args = args[:3] + (jnp.full_like(args[3], np.log(0.99)), args[4])
    exact = tuple(a.astype(jnp.float32) for a in args)
    want = delta_rule(*exact, block=64)
    got = gated_delta_rule(*args, 64)
    assert got.dtype == jnp.bfloat16
    assert _rel(got, want) < 1e-2
    decay = jnp.exp(args[3]).astype(jnp.bfloat16).astype(jnp.float32)
    rounded = exact[:3] + (jnp.log(decay), exact[4])
    assert _rel(delta_rule(*rounded, block=64), want) > 3e-2


MODEL_CASES = [
    # S, B, H, decay a position; heads of 128, chunks of 64, bfloat16
    (1536, 2, 3, 0.02),  # three grid blocks of eight chunks: the scratch
    # state crosses a block's edge, and B x H > 1 starts it anew a head
    (1024, 1, 2, 0.0),   # two blocks, no decay
    (512, 2, 1, 0.3),    # one block, a decay of the middle
]


def _model_inputs(seed, S, B, H, decay):
    low = _inputs(seed, B, H, S, 128, 128, decay, jnp.bfloat16)
    return low, tuple(a.astype(jnp.float32) for a in low)


@pytest.mark.parametrize("S,B,H,decay", MODEL_CASES)
def test_outputs_at_the_models_shapes(S, B, H, decay):
    low, exact = _model_inputs(S, S, B, H, decay)
    got = gated_delta_rule(*low)
    assert got.shape == (B, H, S, 128) and got.dtype == jnp.bfloat16
    assert _rel(got, delta_rule(*exact, block=64)) < 1e-2


@pytest.mark.parametrize("S,B,H,decay", MODEL_CASES)
def test_gradients_at_the_models_shapes(S, B, H, decay):
    low, exact = _model_inputs(S + 1, S, B, H, decay)
    weight = jax.random.normal(jax.random.PRNGKey(9), (B, H, S, 128))
    want = jax.grad(_weighted(lambda *a: delta_rule(*a, block=64), weight),
                    argnums=(0, 1, 2, 3, 4))(*exact)
    got = jax.grad(_weighted(gated_delta_rule, weight),
                   argnums=(0, 1, 2, 3, 4))(*low)
    for name, g, w, a in zip(("q", "k", "v", "g", "beta"), got, want, low):
        assert g.shape == w.shape and g.dtype == a.dtype, name
        assert _rel(g, w) < 2e-2, name


def _rounded_to_bfloat16(name):
    """`gated_delta._chunk` with one of a chunk's float32 quantities rounded
    to bfloat16: "S", the state a chunk starts from, or "a", the decay over
    the whole chunk."""
    chunk = gated_delta._chunk

    def rounded(t):
        return t.astype(jnp.bfloat16).astype(jnp.float32)

    def planted(q, k, v, g, beta, T, S):
        if name == "S":
            return chunk(q, k, v, g, beta, T, rounded(S))
        x = chunk(q, k, v, g, beta, T, S)
        return {**x, name: rounded(x[name])}

    return planted


LONG_MEMORY = 2e-5  # float32 through and through is well under it (some 1e-6)


@pytest.mark.parametrize("fault", [None, "S", "a"])
def test_a_long_memory_needs_a_float32_state_and_decay(fault, monkeypatch):
    """A log decay of -0.001 a position over 2,048 positions: what the first
    chunk wrote is still an eighth of itself at the end, through 32 chunk
    decays and 32 states. In float32 the kernels are at the recurrence,
    outputs and all five gradients; with the state or a chunk's decay
    rounded to bfloat16 they are not, which the benchmark's cell cannot see
    at its initial parameters (PERF.md, section 7)."""
    q, k, v, g, beta = _inputs(11, 1, 2, 2048, 16, 16, 0.0)
    g = jnp.full_like(g, -0.001)
    weight = jax.random.normal(jax.random.PRNGKey(12), v.shape)
    want = delta_rule(q, k, v, g, beta, block=64)
    want_grads = jax.grad(_weighted(lambda *a: delta_rule(*a, block=64), weight),
                          argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    if fault:
        monkeypatch.setattr(gated_delta, "_chunk", _rounded_to_bfloat16(fault))
    got = gated_delta_rule(q, k, v, g, beta)
    got_grads = jax.grad(_weighted(gated_delta_rule, weight),
                         argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    errors = [_rel(got, want)] + [_rel(a, b) for a, b in zip(got_grads, want_grads)]
    if fault:
        assert errors[0] > 10 * LONG_MEMORY, errors
        assert max(errors[1:]) > 10 * LONG_MEMORY, errors
    else:
        assert max(errors) < LONG_MEMORY, errors


def _primitives(jaxpr, found):
    """Every primitive of a jaxpr and of the jaxprs its equations hold, a
    kernel's own body left closed."""
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _primitives(sub, found)
    return found


@pytest.mark.parametrize("what", ["rule", "gradient"])
def test_the_rule_is_kernels_and_no_loop(what):
    """Both passes are `pallas_call`s, with the solve's pass before them:
    no `scan` and no `while` over the chunks is left outside a kernel."""
    args = _inputs(2, 1, 2, 256, 16, 24, 0.1)
    fn = gated_delta_rule if what == "rule" else jax.grad(
        lambda *a: jnp.sum(gated_delta_rule(*a)), argnums=(0, 1, 2, 3, 4))
    found = _primitives(jax.make_jaxpr(fn)(*args).jaxpr, set())
    assert "pallas_call" in found
    assert not found & {"scan", "while"}, found


def test_unit_lower_inverse_and_its_derivative():
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 16, 16)), -1)
    eye = jnp.eye(16)
    T = gated_delta._unit_lower_inverse(a)
    assert np.allclose(T @ (eye + a), np.broadcast_to(eye, a.shape), atol=1e-4)
    weight = jax.random.normal(jax.random.PRNGKey(1), a.shape)
    got = jax.grad(lambda a: jnp.sum(gated_delta._unit_lower_inverse(a) * weight))(a)
    want = jax.grad(lambda a: jnp.sum(jnp.linalg.inv(eye + a) * weight))(a)
    assert _rel(jnp.tril(got, -1), jnp.tril(want, -1)) < 1e-4


@pytest.mark.parametrize("S,chunk", [(100, 64), (64, 48), (32, 64)])
def test_a_length_the_chunk_does_not_divide_raises(S, chunk):
    args = _inputs(0, 1, 1, S, 8, 8, 0.1)
    with pytest.raises(ValueError, match="multiple of the chunk"):
        gated_delta_rule(*args, chunk)
    with pytest.raises(ValueError, match="multiple of the chunk"):
        jax.grad(lambda q: jnp.sum(gated_delta_rule(q, *args[1:], chunk)))(args[0])


def test_the_default_chunk_is_64():
    args = _inputs(1, 1, 1, 128, 8, 8, 0.1)
    assert gated_delta.CHUNK == 64
    assert np.array_equal(gated_delta_rule(*args), gated_delta_rule(*args, 64))


@pytest.mark.parametrize("taps,dtype", [(4, jnp.float32), (1, jnp.float32),
                                        (3, jnp.bfloat16)])
def test_causal_conv_against_a_plain_loop(taps, dtype):
    B, S, C = 2, 12, 5
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, C)).astype(dtype)
    c = jax.random.normal(jax.random.PRNGKey(1), (taps, C))
    want = np.zeros((B, S, C))
    xs, cs = np.asarray(x, np.float64), np.asarray(c, np.float64)
    for t in range(S):
        for i in range(taps):
            if t - (taps - 1) + i >= 0:
                want[:, t] += cs[i] * xs[:, t - (taps - 1) + i]
    got = causal_conv(x, c)
    assert got.dtype == dtype and got.shape == x.shape
    assert _rel(got, want) < (1e-6 if dtype == jnp.float32 else 1e-2)
    # causal: a change at position 7 moves nothing before it
    moved = causal_conv(x.at[:, 7].add(1.0), c)
    assert np.array_equal(np.asarray(moved[:, :7]), np.asarray(got[:, :7]))
    assert not np.array_equal(np.asarray(moved[:, 7]), np.asarray(got[:, 7]))


@pytest.mark.parametrize("taps,dtype,tol", [(4, jnp.float32, 1e-5),
                                            (2, jnp.float32, 1e-5),
                                            (4, jnp.bfloat16, 2e-2)])
def test_causal_convs_written_out_backward_is_autodiffs(taps, dtype, tol):
    """The backward pass keeps x and the taps alone; its cotangents are
    those autodiff gives the plain sum of shifted copies."""
    B, S, C = 2, 16, 6
    x = jax.random.normal(jax.random.PRNGKey(2), (B, S, C)).astype(dtype)
    c = jax.random.normal(jax.random.PRNGKey(3), (taps, C))
    weight = jax.random.normal(jax.random.PRNGKey(4), (B, S, C))

    def plain(x, c):
        x = x.astype(jnp.float32)
        return sum(jnp.pad(x, ((0, 0), (taps - 1 - i, 0), (0, 0)))[:, :S] * c[i]
                   for i in range(taps))

    want = jax.grad(lambda x, c: jnp.sum(plain(x, c) * weight), (0, 1))(x, c)
    got = jax.grad(lambda x, c: jnp.sum(causal_conv(x, c).astype(jnp.float32)
                                        * weight), (0, 1))(x, c)
    assert got[0].dtype == dtype and got[1].dtype == jnp.float32
    assert _rel(got[0], want[0]) < tol and _rel(got[1], want[1]) < tol
