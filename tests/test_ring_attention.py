"""Ring attention (sequence parallelism) vs full attention — exact
algorithm equivalence on a virtual mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from kungfu_tpu.parallel import make_mesh


def _sp_mesh(sp):
    return make_mesh({"sp": sp}, devices=jax.devices()[:sp])


def _full_causal_attention(q, k, v):
    B, H, S, hd = q.shape
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(hd)
    mask = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


@pytest.mark.parametrize("sp", [2, 4, 8])
def test_ring_matches_full_attention(sp):
    from kungfu_tpu.ops.ring_attention import ring_self_attention

    mesh = _sp_mesh(sp)
    B, H, S, hd = 2, 3, 32, 8
    key = jax.random.PRNGKey(0)
    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(i), (B, H, S, hd), jnp.float32)
        for i in range(3)
    )

    ring = jax.jit(
        shard_map(
            lambda q, k, v: ring_self_attention(q, k, v, "sp", sp),
            mesh=mesh,
            in_specs=(P(None, None, "sp"), P(None, None, "sp"), P(None, None, "sp")),
            out_specs=P(None, None, "sp"),
            check_vma=False,
        )
    )
    out = ring(q, k, v)
    ref = _full_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ring_non_causal():
    from kungfu_tpu.ops.ring_attention import ring_self_attention

    sp = 4
    mesh = _sp_mesh(sp)
    B, H, S, hd = 1, 2, 16, 4
    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(10 + i), (B, H, S, hd), jnp.float32)
        for i in range(3)
    )
    ring = jax.jit(
        shard_map(
            lambda q, k, v: ring_self_attention(q, k, v, "sp", sp, causal=False),
            mesh=mesh,
            in_specs=(P(None, None, "sp"),) * 3,
            out_specs=P(None, None, "sp"),
            check_vma=False,
        )
    )
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(hd)
    ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)
    np.testing.assert_allclose(np.asarray(ring(q, k, v)), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ring_transformer_loss_matches_dense():
    """The whole sequence-parallel LM forward (dp=2 x sp=4) matches the
    dense transformer_loss, and is differentiable."""
    from kungfu_tpu.models.transformer import (
        TransformerConfig,
        init_transformer,
        make_ring_transformer_loss,
        transformer_loss,
    )

    cfg = TransformerConfig(vocab_size=64, d_model=16, n_heads=2, n_layers=2,
                            d_ff=32, max_seq=16, dtype=jnp.float32)
    mesh = make_mesh({"dp": 2, "sp": 4})
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(7)
    tokens = jax.random.randint(key, (4, 16), 0, cfg.vocab_size)
    targets = jax.random.randint(jax.random.PRNGKey(8), (4, 16), 0, cfg.vocab_size)

    ring_loss = make_ring_transformer_loss(cfg, mesh)
    dense = float(transformer_loss(params, (tokens, targets), cfg))
    ring = float(jax.jit(ring_loss)(params, (tokens, targets)))
    assert abs(dense - ring) < 1e-4, (dense, ring)

    # compiled, as a step runs them: an operation at a time the ring's
    # gradient alone took 19 s idle (PR 47)
    g = jax.jit(jax.grad(lambda p: ring_loss(p, (tokens, targets))))(params)
    gd = jax.jit(jax.grad(lambda p: transformer_loss(p, (tokens, targets), cfg)))(params)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_ring_trains():
    """A few optimizer steps through the ring path reduce the loss."""
    from kungfu_tpu.models.transformer import (
        TransformerConfig,
        init_transformer,
        make_ring_transformer_loss,
    )

    cfg = TransformerConfig(vocab_size=32, d_model=16, n_heads=2, n_layers=1,
                            d_ff=32, max_seq=8, dtype=jnp.float32)
    mesh = make_mesh({"dp": 2, "sp": 4})
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    loss_fn = make_ring_transformer_loss(cfg, mesh)
    opt = optax.adam(1e-2)
    state = opt.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 32)
    targets = jnp.roll(tokens, -1, axis=1)

    @jax.jit
    def step(params, state):
        loss, g = jax.value_and_grad(loss_fn)(params, (tokens, targets))
        up, state = opt.update(g, state, params)
        return optax.apply_updates(params, up), state, loss

    params, state, first = step(params, state)
    for _ in range(10):
        params, state, last = step(params, state)
    assert float(last) < float(first), (first, last)


@pytest.mark.parametrize("blk_k", [4, 8])
def test_ring_blockwise_inner_loop(blk_k):
    """blk_k < S_local forces the sub-block streaming path; values AND
    gradients must match full attention."""
    from kungfu_tpu.ops.ring_attention import ring_self_attention

    sp = 2
    mesh = _sp_mesh(sp)
    B, H, S, hd = 1, 2, 32, 8  # S_local = 16 > blk_k
    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(i), (B, H, S, hd), jnp.float32)
        for i in range(3)
    )

    def ring_loss(q, k, v):
        fn = shard_map(
            lambda q, k, v: ring_self_attention(q, k, v, "sp", sp,
                                                blk_k=blk_k),
            mesh=mesh,
            in_specs=(P(None, None, "sp"),) * 3,
            out_specs=P(None, None, "sp"),
            check_vma=False,
        )
        return jnp.sum(fn(q, k, v) ** 2)

    def dense_loss(q, k, v):
        return jnp.sum(_full_causal_attention(q, k, v) ** 2)

    out = jax.jit(
        shard_map(
            lambda q, k, v: ring_self_attention(q, k, v, "sp", sp, blk_k=blk_k),
            mesh=mesh,
            in_specs=(P(None, None, "sp"),) * 3,
            out_specs=P(None, None, "sp"),
            check_vma=False,
        )
    )(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_full_causal_attention(q, k, v)),
        rtol=1e-5, atol=1e-5,
    )
    gr = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.jit(jax.grad(dense_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gr, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
