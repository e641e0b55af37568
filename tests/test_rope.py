"""Rotary positions in one pass each way (`models/blocks._rope`, the
kernel of `ops/rotary.py`; PERF.md, PR 35): the values and gradients are
those of the body it replaced, written out here as plain `jax.numpy` and
differentiated by autodiff; the backward pass keeps nothing of q's or k's
size and holds no pad, concatenate or scatter of that size; no float32
array of q's size leaves a fusion of the forward pass; both passes carry
the `rope` scope; and a configuration with learned positions imports
nothing of it. On the CPU the kernel runs interpreted, as the model runs it
there."""

import functools
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from kungfu_tpu.models import blocks, transformer
from kungfu_tpu.models.blocks import _rope
from kungfu_tpu.models.transformer import TransformerConfig

LAGUNA_YARN = (128, 8192, 32, 1, 1.4852030263919618)


def _oracle(q, k, theta, share, yarn):
    """`_rope` as it was before PR 35."""
    S, hd = q.shape[2], q.shape[3]
    rd = int(hd * share)
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    if yarn:
        ramp = blocks._yarn_ramp(rd, theta, yarn)
        inv_freq = inv_freq / yarn[0] * ramp + inv_freq * (1 - ramp)
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if yarn:
        cos, sin = cos * yarn[4], sin * yarn[4]

    def rotate(t):
        t32 = t.astype(jnp.float32)
        if rd < hd:
            t32, rest = t32[..., :rd], t32[..., rd:]
        half = jnp.concatenate([-t32[..., rd // 2:], t32[..., :rd // 2]], axis=-1)
        turned = t32 * cos + half * sin
        if rd < hd:
            turned = jnp.concatenate([turned, rest], axis=-1)
        return turned.astype(t.dtype)

    return rotate(q), rotate(k)


def _inputs(heads, S, hd, dtype, seed=0):
    kq, kk, cq, ck = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(kq, (2, heads[0], S, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (2, heads[1], S, hd), jnp.float32).astype(dtype)
    cts = (jax.random.normal(cq, q.shape, jnp.float32).astype(dtype),
           jax.random.normal(ck, k.shape, jnp.float32).astype(dtype))
    return q, k, cts


def _assert_equal(got, want):
    """float32: equal to 1e-6 (the CPU contracts a multiply and an add where
    it likes); bfloat16: to one unit in the last place, or that 1e-6 where
    the two products cancel to less."""
    assert got.dtype == want.dtype and got.shape == want.shape
    g, w = np.asarray(got.astype(jnp.float32)), np.asarray(want.astype(jnp.float32))
    assert np.all(np.isfinite(g))
    if got.dtype == jnp.float32:
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-6)
    else:  # one unit in the last place of bfloat16's 8 bits
        ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(w), 1e-30))) - 7)
        assert np.all(np.abs(g - w) <= np.maximum(ulp, 1e-6))


@functools.partial(jax.jit, static_argnums=(0, 3))
def _both(rope, q, k, rule, cts):
    out, pull = jax.vjp(lambda q, k: rope(q, k, *rule), q, k)
    return (*out, *pull(cts))


RULES = [(theta, share, yarn) for share in (1.0, 0.5)
         for yarn in ((), LAGUNA_YARN) for theta in (1e4, 5e5)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("rule", RULES, ids=lambda r: (
    f"theta{r[0]:g}-share{r[1]}-{'yarn' if r[2] else 'plain'}"))
def test_values_and_gradients_are_the_old_bodys(rule, dtype):
    q, k, cts = _inputs((9, 3), 24, 64, dtype)
    for got, want in zip(_both(_rope, q, k, rule, cts),
                         _both(_oracle, q, k, rule, cts), strict=True):
        _assert_equal(got, want)


@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("S", [8, 24, 520])
@pytest.mark.parametrize("heads", [(9, 3), (4, 4)], ids=["9on3", "4and4"])
def test_every_shape_of_heads_rows_and_head_size(heads, S, hd):
    """Blocks of all the rows and of a part (520 = 5 x 104), of all the
    heads and of a part, heads of half a lane tile and of a whole one;
    Laguna's full layers' rule, which has every piece."""
    rule = (5e5, 0.5, LAGUNA_YARN)
    dtype = jnp.bfloat16 if heads[0] == 9 else jnp.float32
    q, k, cts = _inputs(heads, S, hd, dtype, seed=S + hd)
    for got, want in zip(_both(_rope, q, k, rule, cts),
                         _both(_oracle, q, k, rule, cts), strict=True):
        _assert_equal(got, want)


@pytest.mark.parametrize("cotangent", ["ones", "zeros", "random"])
def test_the_cotangent_goes_through_the_transposed_rotation(cotangent):
    rule = (1e4, 0.5, ())
    q, k, cts = _inputs((4, 2), 24, 128, jnp.float32, seed=5)
    if cotangent != "random":
        cts = tuple(getattr(jnp, f"{cotangent}_like")(c) for c in cts)
    got = _both(_rope, q, k, rule, cts)[2:]
    want = _both(_oracle, q, k, rule, cts)[2:]
    for g, w in zip(got, want, strict=True):
        _assert_equal(g, w)
    if cotangent == "zeros":
        assert not np.any(np.asarray(got[0])) and not np.any(np.asarray(got[1]))
    if cotangent == "ones":  # the rotation's transpose is no identity
        assert float(jnp.max(jnp.abs(got[0] - 1.0))) > 0.1


def test_the_rotation_is_orthogonal_up_to_yarns_factor():
    """|rotated t| = factor x |t| on the rotated features, and the pass
    back undoes the pass forth up to the factor squared."""
    rule = (5e5, 0.5, LAGUNA_YARN)
    q, k, _ = _inputs((3, 3), 40, 128, jnp.float32, seed=9)
    (rq, _), pull = jax.vjp(lambda q, k: _rope(q, k, *rule), q, k)
    np.testing.assert_allclose(rq[..., 64:], q[..., 64:])
    np.testing.assert_allclose(jnp.linalg.norm(rq[..., :64], axis=-1),
                               LAGUNA_YARN[4] * jnp.linalg.norm(q[..., :64], axis=-1),
                               rtol=1e-5)
    back, _ = pull((rq, jnp.zeros_like(k)))
    np.testing.assert_allclose(back[..., :64], LAGUNA_YARN[4] ** 2 * q[..., :64],
                               rtol=1e-4, atol=1e-5)


# --- what is kept, and what the backward pass is made of ---------------------

SHAPE = dict(heads=(9, 3), S=24, hd=64)
RULE = (1e4, 0.5, LAGUNA_YARN)


def _kept(rope):
    """The avals of what `rope`'s forward pass hands its backward pass: the
    outputs of `jax.vjp`'s jaxpr after the two results."""
    q, k, _ = _inputs(dtype=jnp.bfloat16, **SHAPE)
    jaxpr = jax.make_jaxpr(lambda q, k: jax.vjp(
        lambda q, k: rope(q, k, *RULE), q, k))(q, k).jaxpr
    return [v.aval for v in jaxpr.outvars[2:] if hasattr(v.aval, "shape")], k.size


def test_the_backward_pass_keeps_nothing_of_q_or_k():
    kept, k_size = _kept(_rope)
    assert not [a for a in kept if a.size >= k_size], kept


def test_the_account_sees_a_residual_where_there_is_one():
    kept, k_size = _kept(lambda q, k, *rule: (q * q, k * k))
    assert len([a for a in kept if a.size >= k_size]) == 2, kept


def _moved_in_backward(rope):
    """The pad, concatenate and scatter equations of `rope`'s backward pass
    that touch an array of k's size or larger."""
    q, k, cts = _inputs(dtype=jnp.bfloat16, **SHAPE)
    _, pull = jax.vjp(lambda q, k: rope(q, k, *RULE), q, k)
    jaxpr = jax.make_jaxpr(pull)(cts).jaxpr
    return [e.primitive.name for e in harness.eqns_of(jaxpr)
            if re.match("pad|concatenate|scatter", e.primitive.name)
            and any(getattr(v.aval, "size", 0) >= k.size
                    for v in (*e.invars, *e.outvars) if hasattr(v, "aval"))]


def test_no_pad_concatenate_or_scatter_of_qs_size_in_the_backward_pass():
    assert _moved_in_backward(_rope) == []
    assert "pad" in _moved_in_backward(_oracle)  # the reader is not blind


def _float32_outputs(fn, *args, at_least):
    """(instruction, shape) of every instruction of `fn`'s compiled CPU
    program, fusions' results among them and what is inside a fusion left
    out, that writes a float32 array of `at_least` elements or more."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    found = []
    for comp in re.split(r"\n(?=\S)", text):
        if comp.startswith(("fused_computation", "%fused_computation")):
            continue
        for name, shapes in re.findall(
                r"^\s*(?:ROOT )?(%?[\w.\-]+) = (\(?[^=]*?\)?) [\w\-]+\(", comp, re.M):
            for dims in re.findall(r"f32\[([\d,]*)\]", shapes):
                if int(np.prod([int(d) for d in dims.split(",") if d] or [1])) >= at_least:
                    found.append((name, dims))
    return found


def test_no_float32_array_of_qs_size_leaves_a_fusion_of_the_forward_pass():
    """Rows and heads in several blocks each, so that a block is smaller
    than q: t goes in and out in its own dtype, float32 lives in a block."""
    q, k, _ = _inputs((9, 3), 1040, 128, jnp.bfloat16)
    rule = (1e4, 1.0, ())
    assert _float32_outputs(lambda q, k: _rope(q, k, *rule), q, k,
                            at_least=q.size) == []
    assert _float32_outputs(lambda q: q.astype(jnp.float32) * 2, q,
                            at_least=q.size)  # the reader is not blind


def _tiny(positions):
    return TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                             d_ff=64, max_seq=16, positions=positions)


def test_both_passes_carry_the_rope_scope():
    """Every op of the kernel, forward and backward, has `rope` in its
    `op_name`: `attn_proj_ms`, `fwd_ms` and `bwd_ms` keep their meaning."""
    cfg = _tiny("rope")
    params = transformer.init_transformer(jax.random.PRNGKey(0), cfg)
    batch = jnp.zeros((2, 9), jnp.int32)
    text = jax.jit(jax.grad(lambda p: transformer.transformer_loss(
        p, batch, cfg))).lower(params).compile().as_text()
    names = [n for n in re.findall(r'op_name="([^"]*)"', text) if "rotary" in n]
    assert names
    assert all("rope" in n.split("/") for n in names), names[:3]
    back = [n for n in names if "transpose(" in n]
    assert back and len(back) < len(names)


def test_learned_positions_import_nothing_of_the_rotary_path():
    """On the way from the command to the window of a configuration with
    learned positions nothing is new: no Pallas, and of `kungfu_tpu.ops` the
    collectives alone, which the model offers its gradients to
    (`collective.reduce_in_backward`, PR 47) and which import JAX only."""
    code = """
import sys
import jax, jax.numpy as jnp
from kungfu_tpu.models import transformer as T
cfg = T.TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                          d_ff=64, max_seq=16, positions="learned")
params = jax.eval_shape(lambda: T.init_transformer(jax.random.PRNGKey(0), cfg))
jax.make_jaxpr(jax.grad(lambda p, b: T.transformer_loss(p, b, cfg)))(
    params, jax.ShapeDtypeStruct((2, 9), jnp.int32))
print("MODULES", sorted(m for m in sys.modules
                        if m.startswith(("kungfu_tpu.ops", "jax.experimental.pallas",
                                         "jax._src.pallas"))))
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, check=True,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert "MODULES ['kungfu_tpu.ops', 'kungfu_tpu.ops.collective']" in out.stdout, (
        out.stdout + out.stderr)
