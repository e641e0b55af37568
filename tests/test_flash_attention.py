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


# --- grouped heads and a window (PR 33) --------------------------------------

def _grouped_qkv(S, g, Hkv=2, hd=8, B=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, Hkv * g, S, hd))
    k = jax.random.normal(ks[1], (B, Hkv, S, hd))
    v = jax.random.normal(ks[2], (B, Hkv, S, hd))
    return q, k, v, jax.random.normal(ks[3], q.shape)


def _flash_and_dense(S, blk_q, blk_k, window, g):
    """(loss, gradients) of the flash core and of the dense masked one, on
    a weighted sum of the output so that every row's gradient differs."""
    q, k, v, weigh = _grouped_qkv(S, g)

    def flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None, blk_q, blk_k, True,
                                       window) * weigh)

    def dense(q, k, v):
        return jnp.sum(_dense_reference(q, k, v, True, 1.0 / np.sqrt(q.shape[-1]),
                                        window) * weigh)

    return (jax.value_and_grad(flash, argnums=(0, 1, 2))(q, k, v),
            jax.value_and_grad(dense, argnums=(0, 1, 2))(q, k, v))


# blocks of 16: half a block, one block, three blocks; 80 is no multiple of
# 48 (nor 64 of 24); the last two cases have blocks that differ
@pytest.mark.parametrize("g", [1, 3])
@pytest.mark.parametrize("S,blk_q,blk_k,window", [
    (64, 16, 16, 8), (64, 16, 16, 16), (80, 16, 16, 48), (64, 16, 16, None),
    (64, 16, 32, 24), (64, 32, 16, 20)])
def test_windowed_grouped_flash_matches_dense(S, blk_q, blk_k, window, g):
    (loss, grads), (want_loss, want) = _flash_and_dense(S, blk_q, blk_k, window, g)
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
