"""`ops.ssm_scan`: the chunked state-space scan against the recurrence a
position at a time (the definition; the benchmark's reference runs the same
one in Mamba-2's own letters), outputs and the gradients of all four inputs,
with B and C shared by the heads of a group; the causal convolution with a
bias against a plain loop."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.nemotron_h import conv as plain_conv, ssm_recurrence
from kungfu_tpu.ops import ssm_scan as module
from kungfu_tpu.ops.gated_delta import causal_conv
from kungfu_tpu.ops.ssm_scan import ssm_scan


def recurrence(q, k, v, g):
    """S_t = exp(g_t) S_{t-1} + k_t v_t^T, o_t = S_t^T q_t, S_0 = 0, a
    position at a time; q, k (B, G, S, N) a group, v (B, H, S, P), g (B, H,
    S); head j reads group j // (H / G)."""
    (B, G, S, N), (_, H, _, P) = q.shape, v.shape
    q, k = (jnp.repeat(t, H // G, axis=1) for t in (q, k))

    def position(state, at):
        q_t, k_t, v_t, g_t = at
        state = (jnp.exp(g_t)[..., None, None] * state
                 + k_t[..., :, None] * v_t[..., None, :])
        return state, jnp.einsum("bhnp,bhn->bhp", state, q_t)

    _, o = jax.lax.scan(position, jnp.zeros((B, H, N, P), jnp.float32),
                        tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v, g)))
    return jnp.moveaxis(o, 0, 2)


def _inputs(seed, B, H, G, S, N, P, decay, dtype=jnp.float32):
    """C and B of a group as the layer hands them over (nothing norms them),
    v = Delta x, and a log decay a position of `decay` a head on average
    (0: none)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, G, S, N)) / np.sqrt(N)
    k = jax.random.normal(ks[1], (B, G, S, N)) * 0.3
    v = jax.random.normal(ks[2], (B, H, S, P))
    g = -decay * jax.random.uniform(ks[3], (B, H, S), minval=0.5, maxval=1.5)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def _weighted(fn, weight):
    return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weight)


ALL = (0, 1, 2, 3)
CASES = [
    # S, chunk, B, H, G, decay a position
    (32, 8, 1, 4, 2, 0.05),     # four chunks, two heads a group
    (128, 128, 2, 2, 1, 0.05),  # one chunk of the model's size: no carried state
    (256, 128, 1, 8, 1, 0.02),  # two chunks, eight heads on one group's B and C
    (96, 16, 2, 6, 3, 0.0),     # no decay: the state only grows
    (64, 8, 1, 2, 2, 3.0),      # strong decay: exp(-3) a position, 4e-11 a chunk
    (48, 16, 1, 3, 3, 0.3),     # a head a group, a decay of the middle
]


@pytest.mark.parametrize("S,chunk,B,H,G,decay", CASES)
def test_outputs_agree_with_the_recurrence(S, chunk, B, H, G, decay):
    args = _inputs(S + chunk, B, H, G, S, 16, 24, decay)
    got = ssm_scan(*args, chunk)
    assert got.shape == (B, H, S, 24) and got.dtype == jnp.float32
    assert _rel(got, recurrence(*args)) < 2e-5


@pytest.mark.parametrize("S,chunk,B,H,G,decay", CASES)
def test_all_four_gradients_agree_with_the_recurrence(S, chunk, B, H, G, decay):
    args = _inputs(S + chunk + 1, B, H, G, S, 16, 24, decay)
    weight = jax.random.normal(jax.random.PRNGKey(7), (B, H, S, 24))
    want = jax.grad(_weighted(recurrence, weight), argnums=ALL)(*args)
    got = jax.grad(_weighted(lambda *a: ssm_scan(*a, chunk), weight),
                   argnums=ALL)(*args)
    for name, g, w in zip(("q", "k", "v", "g"), got, want):
        assert g.shape == w.shape, name
        assert _rel(g, w) < 5e-5, name


def test_the_recurrence_is_the_references():
    """The benchmark's reference writes the same recurrence in Mamba-2's
    letters, (b, s, ...) arrays and a (P, N) state: H_t = exp(Delta_t A)
    H_{t-1} + Delta_t x_t B_t^T, y_t = H_t C_t. With q = C, k = B, v = Delta
    x and g = Delta A the two agree."""
    B, H, G, S, N, P = 2, 6, 3, 40, 8, 12
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    Bm = jax.random.normal(ks[1], (B, S, G, N))
    Cm = jax.random.normal(ks[2], (B, S, G, N))
    delta = jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[4], (H,)))
    want = ssm_recurrence(x, Bm, Cm, delta, A, block=8)
    got = recurrence(Cm.transpose(0, 2, 1, 3), Bm.transpose(0, 2, 1, 3),
                     (delta[..., None] * x).transpose(0, 2, 1, 3),
                     (delta * A).transpose(0, 2, 1))
    assert _rel(got.transpose(0, 2, 1, 3), want) < 1e-6
    assert _rel(ssm_scan(Cm.transpose(0, 2, 1, 3), Bm.transpose(0, 2, 1, 3),
                         (delta[..., None] * x).transpose(0, 2, 1, 3),
                         (delta * A).transpose(0, 2, 1), 8).transpose(0, 2, 1, 3),
                want) < 2e-5


@pytest.mark.parametrize("S", [256, 1024])
def test_the_result_does_not_depend_on_the_chunk(S):
    """Mamba-2's published `chunk_size` of 128 and half of it: the same
    outputs and gradients, to float32's rounding."""
    args = _inputs(S, 1, 4, 2, S, 16, 16, 0.02)
    weight = jax.random.normal(jax.random.PRNGKey(3), (1, 4, S, 16))
    at = {}
    for chunk in (64, 128):
        at[chunk] = (ssm_scan(*args, chunk),) + jax.grad(
            _weighted(lambda *a: ssm_scan(*a, chunk), weight), argnums=ALL)(*args)
    for a, b in zip(at[64], at[128]):
        assert _rel(a, b) < 1e-5
    assert module.CHUNK == 128
    assert np.array_equal(ssm_scan(*args), at[128][0])


def test_bfloat16_operands_float32_state():
    """The model's types: bfloat16 C, B and Delta x, a float32 log decay.
    The result is bfloat16's, a few parts in a thousand; a decay exp(g)
    rounded to bfloat16 (0.99 becomes 0.988) is ten times that away."""
    args = _inputs(5, 1, 4, 2, 512, 32, 32, 0.01, jnp.bfloat16)
    args = args[:3] + (jnp.full_like(args[3], np.log(0.99)),)
    exact = tuple(a.astype(jnp.float32) for a in args)
    want = recurrence(*exact)
    got = ssm_scan(*args)
    assert got.dtype == jnp.bfloat16
    assert _rel(got, want) < 1e-2
    decay = jnp.exp(args[3]).astype(jnp.bfloat16).astype(jnp.float32)
    assert _rel(recurrence(*exact[:3], jnp.log(decay)), want) > 3e-2


MODEL_CASES = [
    # S, B, H, G, decay a position; a state of (128, 64), chunks of 128,
    # bfloat16: Nemotron-3-Nano's sizes a head
    (3072, 1, 16, 2, 0.02),  # three grid blocks of eight chunks: the scratch
    # state crosses a block's edge; two blocks of four heads a group, whose
    # dq and dk are added up outside the kernel
    (1024, 2, 2, 1, 0.0),    # a block of two heads, no decay, B > 1
    (512, 1, 3, 3, 0.3),     # a head a group, a decay of the middle
]


def _model_inputs(seed, S, B, H, G, decay):
    low = _inputs(seed, B, H, G, S, 128, 64, decay, jnp.bfloat16)
    return low, tuple(a.astype(jnp.float32) for a in low)


@pytest.mark.parametrize("S,B,H,G,decay", MODEL_CASES)
def test_outputs_at_the_models_shapes(S, B, H, G, decay):
    low, exact = _model_inputs(S, S, B, H, G, decay)
    got = ssm_scan(*low)
    assert got.shape == (B, H, S, 64) and got.dtype == jnp.bfloat16
    assert _rel(got, recurrence(*exact)) < 1e-2


@pytest.mark.parametrize("S,B,H,G,decay", MODEL_CASES)
def test_gradients_at_the_models_shapes(S, B, H, G, decay):
    low, exact = _model_inputs(S + 1, S, B, H, G, decay)
    weight = jax.random.normal(jax.random.PRNGKey(9), (B, H, S, 64))
    want = jax.grad(_weighted(recurrence, weight), argnums=ALL)(*exact)
    got = jax.grad(_weighted(ssm_scan, weight), argnums=ALL)(*low)
    for name, g, w, a in zip(("q", "k", "v", "g"), got, want, low):
        assert g.shape == w.shape and g.dtype == a.dtype, name
        assert _rel(g, w) < 2e-2, name


def _rounded(t):
    return t.astype(jnp.bfloat16).astype(jnp.float32)


def _planted(name):
    """`ssm_scan._next_state` with one of its float32 quantities rounded to
    bfloat16: "S", the state a chunk starts from, or "a", the decay over the
    whole chunk."""
    next_state = module._next_state

    def planted(a, S, Kd, v):
        return next_state(_rounded(a) if name == "a" else a,
                          _rounded(S) if name == "S" else S, Kd, v)

    return planted


LONG_MEMORY = 2e-5  # float32 through and through is well under it


@pytest.mark.parametrize("fault", [None, "S", "a"])
def test_a_long_memory_needs_a_float32_state_and_decay(fault, monkeypatch):
    """A log decay of -0.001 a position over 2,048 positions: what the first
    chunk wrote is still an eighth of itself at the end, through 16 chunk
    decays and 16 states. In float32 the kernels are at the recurrence,
    outputs and all four gradients; with the state or a chunk's decay rounded
    to bfloat16 where the next state is made, the outputs (and the gradients
    through them: the backward pass reads the states the forward pass kept)
    are ten times that away, which the benchmark's cell cannot see at its
    initial parameters, where a step of 0.001 to 0.1 times A in [1, 16]
    forgets within a few hundred positions."""
    q, k, v, g = _inputs(11, 1, 2, 1, 2048, 16, 16, 0.0)
    g = jnp.full_like(g, -0.001)
    weight = jax.random.normal(jax.random.PRNGKey(12), v.shape)
    want = recurrence(q, k, v, g)
    want_grads = jax.grad(_weighted(recurrence, weight), argnums=ALL)(q, k, v, g)
    if fault:
        monkeypatch.setattr(module, "_next_state", _planted(fault))
    got = ssm_scan(q, k, v, g)
    got_grads = jax.grad(_weighted(ssm_scan, weight), argnums=ALL)(q, k, v, g)
    errors = [_rel(got, want)] + [_rel(a, b) for a, b in zip(got_grads, want_grads)]
    if fault:
        assert errors[0] > 10 * LONG_MEMORY, errors
        assert max(errors[1:]) > 10 * LONG_MEMORY, errors
    else:
        assert max(errors) < LONG_MEMORY, errors


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold, a
    kernel's own body left closed."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


@pytest.mark.parametrize("what", ["scan", "gradient"])
def test_the_scan_is_kernels_and_no_loop(what):
    """Both passes are `pallas_call`s: no `scan` and no `while` over the
    chunks is left outside a kernel, and B and C go into the kernels as (B,
    groups, S, N), never repeated a head."""
    B, H, G, S, N, P = 1, 8, 2, 256, 16, 24
    args = _inputs(2, B, H, G, S, N, P, 0.1)
    fn = ssm_scan if what == "scan" else jax.grad(
        lambda *a: jnp.sum(ssm_scan(*a)), argnums=ALL)
    eqns = list(_equations(jax.make_jaxpr(fn)(*args).jaxpr))
    found = {e.primitive.name for e in eqns}
    assert "pallas_call" in found
    assert not found & {"scan", "while"}, found
    for call in (e for e in eqns if e.primitive.name == "pallas_call"):
        shapes = [v.aval.shape for v in call.invars]
        assert (B, G, S, N) in shapes and (B, H, S, N) not in shapes, shapes


@pytest.mark.parametrize("S,chunk,H,G", [(100, 64, 2, 1), (64, 48, 2, 1),
                                         (32, 64, 2, 1)])
def test_a_length_the_chunk_does_not_divide_raises(S, chunk, H, G):
    args = _inputs(0, 1, H, G, S, 8, 8, 0.1)
    with pytest.raises(ValueError, match="multiple of the chunk"):
        ssm_scan(*args, chunk)
    with pytest.raises(ValueError, match="multiple of the chunk"):
        jax.grad(lambda q: jnp.sum(ssm_scan(q, *args[1:], chunk)))(args[0])


def test_heads_the_groups_do_not_divide_raise():
    args = _inputs(0, 1, 3, 2, 32, 8, 8, 0.1)
    with pytest.raises(ValueError, match="no multiple of 2 groups"):
        ssm_scan(*args, 16)


def test_the_static_counters_say_what_a_scan_runs_and_keeps():
    """`kungfu_ssm_chunks_total{pass}` counts a traced pass's chunks over
    batch and heads, `kungfu_ssm_kept_state_bytes` the float32 states the
    scan traced last keeps between its passes."""
    from kungfu_tpu.telemetry import metrics

    def chunks(which):
        return metrics.counter("kungfu_ssm_chunks_total", "", ("pass",)).labels(
            which).value

    B, H, G, S, N, P = 2, 4, 2, 128, 16, 24
    args = _inputs(6, B, H, G, S, N, P, 0.1)
    before = chunks("forward"), chunks("backward")
    jax.grad(lambda *a: jnp.sum(ssm_scan(*a, 32)))(*args)
    assert chunks("forward") - before[0] == B * H * (S // 32)
    assert chunks("backward") - before[1] == B * H * (S // 32)
    assert metrics.gauge("kungfu_ssm_kept_state_bytes", "").value == (
        B * H * (S // 32) * N * P * 4)


@pytest.mark.parametrize("taps,dtype", [(4, jnp.float32), (1, jnp.float32),
                                        (3, jnp.bfloat16)])
def test_causal_conv_with_a_bias_against_a_plain_loop(taps, dtype):
    B, S, C = 2, 12, 5
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, C)).astype(dtype)
    c = jax.random.normal(jax.random.PRNGKey(1), (taps, C))
    bias = jax.random.normal(jax.random.PRNGKey(2), (C,))
    xs, cs = np.asarray(x, np.float64), np.asarray(c, np.float64)
    want = np.zeros((B, S, C)) + np.asarray(bias, np.float64)
    for t in range(S):
        for i in range(taps):
            if t - (taps - 1) + i >= 0:
                want[:, t] += cs[i] * xs[:, t - (taps - 1) + i]
    got = causal_conv(x, c, bias)
    tol = 1e-6 if dtype == jnp.float32 else 1e-2
    assert got.dtype == dtype and got.shape == x.shape
    assert _rel(got, want) < tol
    # the reference's own convolution, and the delta rule's plus the bias
    assert _rel(plain_conv(x.astype(jnp.float32), c, bias), want) < 1e-6
    assert _rel(causal_conv(x, c).astype(jnp.float32) + bias, want) < tol
    # causal: a change at position 7 moves nothing before it
    moved = causal_conv(x.at[:, 7].add(1.0), c, bias)
    assert np.array_equal(np.asarray(moved[:, :7]), np.asarray(got[:, :7]))
    assert not np.array_equal(np.asarray(moved[:, 7]), np.asarray(got[:, 7]))


@pytest.mark.parametrize("taps,dtype,tol", [(4, jnp.float32, 1e-5),
                                            (2, jnp.float32, 1e-5),
                                            (4, jnp.bfloat16, 2e-2)])
def test_the_biased_convs_written_out_backward_is_autodiffs(taps, dtype, tol):
    """The backward pass keeps x, the taps and the bias alone; its
    cotangents are those autodiff gives the plain sum of shifted copies."""
    B, S, C = 2, 16, 6
    x = jax.random.normal(jax.random.PRNGKey(2), (B, S, C)).astype(dtype)
    c = jax.random.normal(jax.random.PRNGKey(3), (taps, C))
    bias = jax.random.normal(jax.random.PRNGKey(5), (C,))
    weight = jax.random.normal(jax.random.PRNGKey(4), (B, S, C))

    def plain(x, c, bias):
        x = x.astype(jnp.float32)
        return bias + sum(
            jnp.pad(x, ((0, 0), (taps - 1 - i, 0), (0, 0)))[:, :S] * c[i]
            for i in range(taps))

    want = jax.grad(lambda *a: jnp.sum(plain(*a) * weight), (0, 1, 2))(x, c, bias)
    got = jax.grad(lambda *a: jnp.sum(causal_conv(*a).astype(jnp.float32)
                                      * weight), (0, 1, 2))(x, c, bias)
    assert got[0].dtype == dtype and got[1].dtype == got[2].dtype == jnp.float32
    for g, w in zip(got, want):
        assert _rel(g, w) < tol


# -- packed rows (PR 52): `segments` number each position's document ---------

# five documents in 256 positions: boundaries inside a chunk (40, 228), on the
# edge of a chunk of 64 (64) and of both 64 and 128 (128)
PACKED = (40, 24, 64, 100, 28)


def _documents(lengths=PACKED):
    """(segments (1, S), [(first, end)] a document)."""
    edges = np.cumsum((0,) + tuple(lengths))
    segments = jnp.asarray(np.repeat(np.arange(len(lengths)), lengths))[None]
    return segments, list(zip(edges[:-1], edges[1:]))


def _padded(t, axis, to):
    """t with zeros behind it along `axis`, up to a multiple of `to`."""
    pad = [(0, 0)] * t.ndim
    pad[axis] = (0, -t.shape[axis] % to)
    return jnp.pad(t, pad)


@pytest.mark.parametrize("chunk,H,G", [(64, 8, 1), (128, 8, 1), (64, 4, 2)])
def test_a_packed_row_is_its_documents_run_one_at_a_time(chunk, H, G):
    """Values and all four gradients, to float32's rounding: the state is
    zero before a document's first position wherever in a chunk it stands,
    and nothing of a later document reaches an earlier one's cotangents."""
    S = sum(PACKED)
    segments, documents = _documents()
    args = _inputs(chunk + H, 1, H, G, S, 16, 24, 0.05)
    weight = jax.random.normal(jax.random.PRNGKey(9), (1, H, S, 24))

    def packed(*a):
        return jnp.sum(ssm_scan(*a, chunk, segments) * weight)

    def alone(*a):
        total = 0.0
        for first, end in documents:
            mine = [_padded(t[:, :, first:end], 2, chunk) for t in a]
            total += jnp.sum(ssm_scan(*mine, chunk)[:, :, :end - first]
                             * weight[:, :, first:end])
        return total

    one_at_a_time = jnp.concatenate([
        ssm_scan(*(_padded(t[:, :, first:end], 2, chunk) for t in args),
                 chunk)[:, :, :end - first] for first, end in documents], axis=2)
    assert _rel(ssm_scan(*args, chunk, segments), one_at_a_time) < 2e-6
    got = jax.grad(packed, argnums=ALL)(*args)
    want = jax.grad(alone, argnums=ALL)(*args)
    for name, g, w in zip(("q", "k", "v", "g"), got, want):
        assert _rel(g, w) < 5e-6, name
    # a document's first position gives its log decay no gradient: the state
    # it would decay is zero
    firsts = [first for first, _ in documents]
    dg = np.abs(np.asarray(got[3]))
    assert dg[:, :, firsts].max() < 1e-4 * dg.max()  # sums that cancel
    # and the same row as one document reads otherwise
    assert _rel(ssm_scan(*args, chunk), ssm_scan(*args, chunk, segments)) > 0.05


def test_a_packed_scan_is_the_recurrence_with_the_state_set_to_zero():
    """Against the definition, not against the kernels' own unpacked run:
    the benchmark's reference recurrence a position at a time, its state
    zeroed at every document's first position."""
    from benchmark.reference.granite_hybrid import recurrence as zeroing

    S, H, N, P = sum(PACKED), 4, 16, 24
    segments, documents = _documents()
    q, k, v, g = _inputs(3, 1, H, 1, S, N, P, 0.05)
    first = np.zeros((1, S), bool)
    first[0, [a for a, _ in documents]] = True
    # the reference's decay is exp(delta_t A) with one A a head: delta 1 and
    # A 0, no decay, a head at a time; what is tested is where the state ends
    want = jnp.stack([
        zeroing(v[:, h:h + 1].transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                q.transpose(0, 2, 1, 3), jnp.ones((1, S, 1)), jnp.zeros((1,)),
                jnp.asarray(first), 64)[:, :, 0]
        for h in range(H)], axis=1)
    got = ssm_scan(q, k, v, jnp.zeros_like(g), 64, segments)
    assert _rel(got, want) < 2e-5


def test_without_segments_the_scan_is_the_program_it_was():
    """No operand, no mask and no equation more: the jaxpr of a call without
    segments, forward and backward, is that of a call that has never heard
    of them, and a packed call's kernels take one operand more."""
    from jaxprs import pallas_operands

    args = _inputs(0, 1, 4, 1, 128, 16, 24, 0.05)
    segments = _documents((100, 28))[0]

    def both(*extra):
        return jax.make_jaxpr(jax.value_and_grad(
            lambda *a: jnp.sum(ssm_scan(*a, 64, *extra)), argnums=ALL))(*args)

    assert str(both()) == str(both(None))
    assert pallas_operands(both().jaxpr) == {"ssm_scan_forward": 4,
                                             "ssm_scan_backward": 6}
    assert pallas_operands(both(segments).jaxpr) == {"ssm_scan_forward": 5,
                                                     "ssm_scan_backward": 7}


@pytest.mark.parametrize("taps,bias", [(4, True), (2, True), (4, False)])
def test_a_packed_rows_convolution_is_its_documents_one_at_a_time(taps, bias):
    """A tap that would read a position of an earlier document reads zero,
    forward and in the written-out backward pass."""
    S, C = 64, 6
    segments, documents = _documents((10, 3, 1, 30, 20))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, S, C))
    c = jax.random.normal(jax.random.PRNGKey(3), (taps, C))
    b = (jax.random.normal(jax.random.PRNGKey(5), (C,)),) if bias else ()
    weight = jax.random.normal(jax.random.PRNGKey(4), (1, S, C))

    def packed(x, c, *b):
        return jnp.sum(causal_conv(x, c, *(b or (None,)), segments) * weight)

    def alone(x, c, *b):
        return sum(jnp.sum(causal_conv(x[:, first:end], c, *b)
                           * weight[:, first:end]) for first, end in documents)

    which = tuple(range(2 + len(b)))
    got = jax.grad(packed, which)(x, c, *b)
    want = jax.grad(alone, which)(x, c, *b)
    assert abs(float(packed(x, c, *b)) - float(alone(x, c, *b))) < 1e-5
    for g, w in zip(got, want):
        assert _rel(g, w) < 1e-6
    assert _rel(causal_conv(x, c, *b), causal_conv(x, c, *(b or (None,)), segments)) > 0.05
    # the reference's convolution keeps to the documents the same way
    from benchmark.reference.granite_hybrid import conv as packed_conv
    want = packed_conv(x, c, b[0] if b else jnp.zeros((C,)), segments)
    assert _rel(causal_conv(x, c, *(b or (None,)), segments), want) < 1e-6
    # and without segments the convolution is the program it was
    def both(*extra):
        return str(jax.make_jaxpr(jax.grad(
            lambda x, c: jnp.sum(causal_conv(x, c, *(b or (None,)), *extra)),
            (0, 1)))(x, c))
    assert both() == both(None) != both(segments)
