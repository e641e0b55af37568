"""Launcher and placement spans: a kfrun worker joins the one JAX world
(CPU backend), places a small state from rank 0, and prints what the span
ring holds of `worker.`, `device_plane.` and `broadcast.` as one JSON line.
"""

import json
import sys

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

from kungfu_tpu import api  # noqa: E402
from kungfu_tpu.initializer import broadcast_variables  # noqa: E402
from kungfu_tpu.parallel import initialize_device_plane, make_mesh  # noqa: E402
from kungfu_tpu.telemetry import tracing  # noqa: E402

TAG = "SPAN_AGENT "


def main() -> int:
    rank = api.current_rank()
    initialize_device_plane()
    mesh = make_mesh({"dp": jax.device_count()})
    state = {"w": np.full((64, 32), float(rank), np.float32),
             "b": np.full((32,), float(rank), np.float32)}
    placed = broadcast_variables(state, mesh)
    assert float(np.asarray(placed["w"])[0, 0]) == 0.0  # rank 0's values
    spans = [{"name": e.name, "ms": e.duration * 1e3, "args": e.args}
             for prefix in ("worker.", "device_plane.", "broadcast.")
             for e in tracing.full_events(prefix)]
    print(TAG + json.dumps({"rank": rank, "spans": spans}), flush=True)
    api.run_barrier()
    return 0


if __name__ == "__main__":
    sys.exit(main())
