import time as _time

_import_began = _time.perf_counter()

from kungfu_tpu.parallel.mesh import DeviceSession, make_mesh
from kungfu_tpu.parallel.dp import make_train_step
from kungfu_tpu.parallel.pipeline import make_pp_transformer_loss
from kungfu_tpu.parallel.distributed import (
    device_plane_initialized,
    initialize_device_plane,
    reinitialize_device_plane,
    shutdown_device_plane,
)

__all__ = [
    "DeviceSession",
    "make_mesh",
    "make_pp_transformer_loss",
    "make_train_step",
    "initialize_device_plane",
    "reinitialize_device_plane",
    "shutdown_device_plane",
    "device_plane_initialized",
]

from kungfu_tpu.telemetry import tracing

tracing.record("worker.import", _time.perf_counter() - _import_began,
               module=__name__)
del tracing
