"""Gradient-variance monitor + Counter/EMA state helpers.

Parity: optimizers/grad_variance.py (variance monitor) and
ops/cpu/state.cpp:6-46 (Counter / ExponentialMovingAverage).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from kungfu_tpu.monitor.grad_variance import (
    gradient_variance,
    monitor_gradient_variance,
)
from kungfu_tpu.parallel import make_mesh
from kungfu_tpu.utils.state import Counter, ExponentialMovingAverage
from jax.sharding import PartitionSpec as P
from jax import shard_map


def _run_monitored(per_worker_grads, interval=1, steps=1):
    """Run the monitored update on an 8-worker mesh with per-worker grads
    supplied explicitly (leading axis = worker)."""
    mesh = make_mesh({"dp": 8})
    base = optax.sgd(0.1)
    opt = monitor_gradient_variance(base, "dp", interval=interval)
    params = {"w": jnp.zeros((2,), jnp.float32)}

    def one(g, state, params):
        g = jax.tree.map(lambda x: jnp.squeeze(x, 0), g)  # this worker's grad
        updates, state = opt.update(g, state, params)
        return optax.apply_updates(params, updates), state

    fn = jax.jit(
        shard_map(
            one, mesh=mesh,
            in_specs=(P("dp"), P(), P()),
            out_specs=(P(), P()),
            check_vma=False,
        )
    )
    state = opt.init(params)
    for _ in range(steps):
        params, state = fn(per_worker_grads, state, params)
    return params, jax.device_get(state)


def test_variance_zero_when_grads_identical():
    g = {"w": jnp.ones((8, 2), jnp.float32)}  # every worker sends [1,1]
    _, state = _run_monitored(g)
    np.testing.assert_allclose(float(gradient_variance(state)), 0.0, atol=1e-6)


def test_variance_matches_hand_computation():
    # workers split: 4 send [0,0], 4 send [2,0] -> mean 1, E[g^2]=2,
    # var tensor = [1, 0], Frobenius norm = 1
    per = np.zeros((8, 2), np.float32)
    per[4:, 0] = 2.0
    _, state = _run_monitored({"w": jnp.asarray(per)})
    np.testing.assert_allclose(float(gradient_variance(state)), 1.0, rtol=1e-5)


def test_sgd_path_still_applies_mean_gradient():
    per = np.zeros((8, 2), np.float32)
    per[:, 1] = 4.0  # mean grad [0, 4]; lr 0.1 -> params [0, -0.4]
    params, _ = _run_monitored({"w": jnp.asarray(per)})
    np.testing.assert_allclose(
        np.asarray(params["w"]), [0.0, -0.4], rtol=1e-5
    )


def test_interval_thinning_keeps_last_estimate():
    per = np.zeros((8, 2), np.float32)
    per[4:, 0] = 2.0
    _, state = _run_monitored({"w": jnp.asarray(per)}, interval=2, steps=3)
    # steps 0 and 2 update (count%2==0), step 1 holds; count advances always
    assert int(state.grad_var.count) == 3
    np.testing.assert_allclose(float(gradient_variance(state)), 1.0, rtol=1e-5)


class TestStateHelpers:
    def test_counter_starts_at_zero(self):
        c = Counter()
        assert [c(), c(), c()] == [0, 1, 2]
        assert c.value == 3

    def test_ema_seeds_then_blends(self):
        ema = ExponentialMovingAverage(0.5)
        assert ema.value == 0.0
        assert ema.update(4.0) == 4.0  # first sample seeds
        assert ema.update(0.0) == 2.0
        assert ema.update(2.0) == 2.0
        with pytest.raises(ValueError):
            ExponentialMovingAverage(0.0)
