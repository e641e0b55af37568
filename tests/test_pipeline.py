"""Pipeline parallelism (GPipe over a pp mesh axis) vs the dense path."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from kungfu_tpu.models.transformer import (
    TransformerConfig,
    init_transformer,
    transformer_loss,
)
from kungfu_tpu.parallel import make_mesh
from kungfu_tpu.parallel.pipeline import make_pp_transformer_loss


def _cfg(n_layers=4):
    return TransformerConfig(vocab_size=64, d_model=16, n_heads=2,
                             n_layers=n_layers, d_ff=32, max_seq=12,
                             dtype=jnp.float32)


def _batch(cfg, B=8, seed=7):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (B, cfg.max_seq),
                                0, cfg.vocab_size)
    targets = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                 (B, cfg.max_seq), 0, cfg.vocab_size)
    return tokens, targets


def _pp_mesh(pp):
    return make_mesh({"pp": pp}, devices=jax.devices()[:pp])


@pytest.mark.parametrize("pp,n_micro", [(2, 4), (4, 4), (4, 8), (8, 8)])
def test_pp_loss_matches_dense(pp, n_micro):
    cfg = _cfg(n_layers=8)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    dense = float(transformer_loss(params, batch, cfg))
    loss_fn = make_pp_transformer_loss(cfg, _pp_mesh(pp), n_micro)
    pipe = float(jax.jit(loss_fn)(params, batch))
    assert abs(dense - pipe) < 1e-5, (dense, pipe)


def test_pp_gradients_match_dense():
    cfg = _cfg(n_layers=4)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    loss_fn = make_pp_transformer_loss(cfg, _pp_mesh(4), n_micro=4)
    g_pipe = jax.jit(jax.grad(lambda p: loss_fn(p, batch)))(params)
    g_dense = jax.jit(jax.grad(lambda p: transformer_loss(p, batch, cfg)))(params)
    for a, b in zip(jax.tree.leaves(g_pipe), jax.tree.leaves(g_dense)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_pp_composes_with_dp():
    cfg = _cfg(n_layers=4)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg, B=8)
    mesh = make_mesh({"dp": 2, "pp": 4})
    loss_fn = make_pp_transformer_loss(cfg, mesh, n_micro=2, dp_axis="dp")
    dense = float(transformer_loss(params, batch, cfg))
    pipe = float(jax.jit(loss_fn)(params, batch))
    # dp shards the batch; per-shard micro means averaged = global mean
    assert abs(dense - pipe) < 1e-5, (dense, pipe)
    # gradients too: the subtle transpose path is the dp pmean composed
    # with pp-sharded layer params under shard_map
    g_pipe = jax.jit(jax.grad(lambda p: loss_fn(p, batch)))(params)
    g_dense = jax.jit(jax.grad(lambda p: transformer_loss(p, batch, cfg)))(params)
    for a, b in zip(jax.tree.leaves(g_pipe), jax.tree.leaves(g_dense)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_pp_trains():
    cfg = _cfg(n_layers=4)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    tokens, _ = _batch(cfg)
    targets = jnp.roll(tokens, -1, axis=1)
    loss_fn = make_pp_transformer_loss(cfg, _pp_mesh(4), n_micro=4)
    opt = optax.adam(1e-2)
    state = opt.init(params)

    @jax.jit
    def step(params, state):
        loss, g = jax.value_and_grad(loss_fn)(params, (tokens, targets))
        up, state = opt.update(g, state, params)
        return optax.apply_updates(params, up), state, loss

    params, state, first = step(params, state)
    for _ in range(10):
        params, state, last = step(params, state)
    assert float(last) < float(first), (first, last)


def test_pp_rejects_bad_divisibility():
    cfg = _cfg(n_layers=6)
    with pytest.raises(ValueError, match="not divisible"):
        make_pp_transformer_loss(cfg, _pp_mesh(4), n_micro=2)
