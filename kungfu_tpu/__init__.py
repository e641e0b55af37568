"""kungfu_tpu — a TPU-native adaptive distributed-training framework.

Provides the capabilities of KungFu (OSDI'20: adaptive/elastic decentralized
data-parallel training) re-designed for TPU hardware:

- The collective data plane is XLA: ``psum``/``pmean``/``all_gather`` inside
  jitted programs over a ``jax.sharding.Mesh`` (ICI), replacing the
  reference's NCCL + TCP graph-walk collectives.
- A host-side control plane (runner CLI, config server, heartbeat monitor,
  TCP message channels) supervises worker processes and drives elastic
  membership, replacing the reference's Go runtime.
- Optimizers (SynchronousSGD, SynchronousAveraging, PairAveraging,
  AdaptiveSGD, gradient-noise-scale monitoring) wrap optax gradient
  transformations.

Reference capability map: see SURVEY.md at the repo root.
"""

import time as _time

_import_began = _time.perf_counter()

__version__ = "0.1.0"

from kungfu_tpu import knobs as _knobs

# Debug-mode lock-order detector (ISSUE 7): installed FIRST, before any
# kungfu module creates a lock, so every threading.Lock/RLock below this
# line is instrumented. Unset/falsy knob = lockwatch never imported,
# threading untouched, zero overhead (asserted by tests/test_lockwatch).
if _knobs.get("KF_DEBUG_LOCKS"):
    from kungfu_tpu.devtools import lockwatch as _lockwatch

    _lockwatch.install()

from kungfu_tpu.base.dtype import DType
from kungfu_tpu.base.ops import ReduceOp
from kungfu_tpu.base.strategy import Strategy

__all__ = [
    "DType",
    "ReduceOp",
    "Strategy",
    "telemetry",
    "__version__",
]


# what a restarted worker pays first: this body as a span of the ring
# (`kungfu_tpu.parallel`, which brings jax and optax, records its own)
from kungfu_tpu.telemetry import tracing

tracing.record("worker.import", _time.perf_counter() - _import_began,
               module=__name__)
del tracing
