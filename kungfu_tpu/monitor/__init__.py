"""Monitors: gradient noise scale / variance (device plane) + network
rates (host plane), publishing into the shared telemetry registry.

Lazy re-exports (PEP 562): `noise_scale`/`grad_variance` drag in
jax.numpy machinery (~330 ms even with jax itself already imported),
and the TRANSPORT imports this package for `monitor.net` on every Peer
construction — an eager import here put a third of a second inside
every elastic joiner's critical path (measured round 5, on the resize
latency's phase breakdown).
"""

import importlib

# "noise_scale" (the function) is deliberately NOT re-exported: the name
# would shadow the submodule of the same name — import it from
# kungfu_tpu.monitor.noise_scale directly
_NOISE = ("GNSState", "gns_init", "gns_update", "monitor_gradient_noise_scale",
          "publish_noise_scale")
_VARIANCE = ("monitor_gradient_variance", "gradient_variance",
             "publish_gradient_variance")

__all__ = list(_NOISE + _VARIANCE) + ["cluster_health"]


def cluster_health(max_age: float = 5.0) -> dict:
    """Cluster-level health signals for the adaptation layer (ISSUE 2).

    Returns the flattened ``cluster/*`` signal dict derived from the
    runner-side TelemetryAggregator's snapshot: straggler list, per-peer
    straggler scores, step-time skew, RTT outliers, and whether THIS
    worker is flagged. In the runner process it reads the in-process
    aggregator; in a worker it polls the watcher's ``/cluster/health``
    endpoint (``KF_CLUSTER_HEALTH_URL``, injected at spawn) with an
    ``max_age``-second cache. Empty dict when no cluster plane is up.
    """
    mod = importlib.import_module("kungfu_tpu.telemetry.cluster")
    return mod.health_signals(max_age)


def __getattr__(name):
    # importlib (NOT `from ... import`): "noise_scale" names both the
    # submodule and a lazy attribute, and a from-import would re-enter
    # this __getattr__ for it — infinite recursion
    if name in _NOISE:
        mod = importlib.import_module("kungfu_tpu.monitor.noise_scale")
        return getattr(mod, name)
    if name in _VARIANCE:
        mod = importlib.import_module("kungfu_tpu.monitor.grad_variance")
        return getattr(mod, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
