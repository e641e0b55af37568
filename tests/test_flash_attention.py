"""Pallas flash attention vs dense attention (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kungfu_tpu.ops.flash_attention import _dense_reference, flash_attention


def _qkv(B=2, H=3, S=64, hd=16, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(
        jax.random.normal(k, (B, H, S, hd), dtype) for k in ks
    )


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("blk", [16, 32, 64])
def test_flash_matches_dense(causal, blk):
    q, k, v = _qkv()
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
