"""Device-collective tests on a virtual 8-device CPU mesh.

Mirrors the reference's operator integration tests
(tests/python/integration/test_operators.py) but over XLA collectives.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from kungfu_tpu.base.ops import ReduceOp
from kungfu_tpu.ops import collective as col
from kungfu_tpu.parallel import DeviceSession, make_mesh


@pytest.fixture(scope="module")
def sess():
    return DeviceSession(make_mesh({"dp": 8}))


def test_mesh_shapes():
    m = make_mesh({"dp": 2, "tp": -1})
    assert dict(zip(m.axis_names, m.devices.shape)) == {"dp": 2, "tp": 4}
    with pytest.raises(ValueError):
        make_mesh({"dp": 3})
    with pytest.raises(ValueError):
        make_mesh({"dp": -1, "tp": -1})


def test_session_metadata(sess):
    assert sess.size == 8
    assert sess.axis_names == ("dp",)
    assert sess.rank == 0
    assert sess.host_count == 1
    assert "8 devices" in sess.describe()


def test_barrier(sess):
    sess.barrier()  # must not deadlock or crash


def test_all_reduce_sum(sess):
    # shard [0..7] over dp; allreduce-sum must give 28 everywhere
    x = jnp.arange(8, dtype=jnp.float32)
    out = sess.all_reduce(x)
    np.testing.assert_allclose(np.asarray(out), 28.0)


@pytest.mark.parametrize("op,expect", [
    (ReduceOp.SUM, 28.0),
    (ReduceOp.MIN, 0.0),
    (ReduceOp.MAX, 7.0),
])
def test_all_reduce_ops(sess, op, expect):
    def f(x):
        return col.all_reduce(x, "dp", op)

    fn = sess.spmd(f, in_specs=P("dp"), out_specs=P())
    out = fn(jnp.arange(8, dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(out), expect)


def test_all_reduce_prod_unsupported(sess):
    with pytest.raises(ValueError):
        fn = sess.spmd(
            lambda x: col.all_reduce(x, "dp", ReduceOp.PROD),
            in_specs=P("dp"), out_specs=P(),
        )
        fn(jnp.arange(8, dtype=jnp.float32))


def test_broadcast(sess):
    # each shard holds its rank; broadcast root=3 -> all get 3
    def f(x):
        return col.broadcast(x, "dp", root=3)

    fn = sess.spmd(f, in_specs=P("dp"), out_specs=P("dp"))
    out = fn(jnp.arange(8, dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(out), np.full(8, 3.0))


def test_all_gather(sess):
    def f(x):
        return col.all_gather(x, "dp", tiled=True)

    fn = sess.spmd(f, in_specs=P("dp"), out_specs=P("dp"))
    out = fn(jnp.arange(8, dtype=jnp.float32))
    # every shard gathered the full vector; result is (8*8,) tiled
    assert out.shape == (64,)
    np.testing.assert_allclose(np.asarray(out)[:8], np.arange(8))


def test_subset_all_reduce(sess):
    mask = jnp.array([1, 1, 1, 1, 0, 0, 0, 0], dtype=jnp.int32)

    def f(x):
        return col.subset_all_reduce(x, mask, "dp")

    fn = sess.spmd(f, in_specs=P("dp"), out_specs=P())
    out = fn(jnp.arange(8, dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(out), 6.0)  # 0+1+2+3


def test_group_all_reduce_pytree(sess):
    tree = {"a": jnp.ones((8, 4)), "b": jnp.arange(8, dtype=jnp.float32)}

    def f(t):
        return col.group_all_reduce(t, "dp")

    fn = sess.spmd(f, in_specs=P("dp"), out_specs=P())
    out = fn(tree)
    np.testing.assert_allclose(np.asarray(out["a"]), np.full((1, 4), 8.0))
    np.testing.assert_allclose(np.asarray(out["b"]), 28.0)


def test_fuse_defuse_roundtrip():
    xs = [jnp.arange(6, dtype=jnp.float32).reshape(2, 3), jnp.ones((4,)), jnp.zeros(())]
    fused = col.fuse(xs)
    assert fused.shape == (11,)
    back = col.defuse(fused, [x.shape for x in xs])
    for a, b in zip(xs, back):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_fuse_pytree_roundtrip():
    tree = {"w": jnp.ones((3, 2)), "b": jnp.arange(2, dtype=jnp.float32)}
    fused, unflatten = col.fuse_pytree(tree)
    assert fused.shape == (8,)
    back = unflatten(fused)
    np.testing.assert_allclose(np.asarray(back["w"]), np.ones((3, 2)))
    np.testing.assert_allclose(np.asarray(back["b"]), np.arange(2))
