"""The chip under the program: which TPUs the repo knows, and where the
compiled programs are kept.

One table names every `device_kind` the repo has run on, with the
published peaks a benchmark divides by. `chip_smoke.py` and `benchmark/`
both read it; a device that is not in it is an error, never a default.
"""

from __future__ import annotations

import dataclasses
import os

import jax

from kungfu_tpu.telemetry import device, tracing


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    bf16_flops: float  # peak FLOP/s, bf16
    hbm_bytes_per_s: float  # peak HBM bandwidth


# Keyed by `jax.devices()[0].device_kind` exactly as the chip reports it
# (chip run, PR 21). Peaks: Google Cloud documentation, "TPU v5e"
# (197 TFLOP/s bf16, 819 GB/s HBM).
TPU_CHIPS = {
    "TPU v5 lite": ChipSpec(bf16_flops=197e12, hbm_bytes_per_s=819e9),
}


def require_tpu() -> ChipSpec:
    """The spec of the chips JAX found; raises unless every device is a
    TPU of a kind in `TPU_CHIPS`. Measurement paths call this first, so a
    chipless run (or a `JAX_PLATFORMS=cpu` left in the environment)
    fails before anything compiles."""
    devices = jax.devices()
    for d in devices:
        if d.platform != "tpu":
            raise RuntimeError(
                f"no TPU: JAX found {d.platform} device {d.device_kind!r} "
                f"({len(devices)} devices); this path runs on the chip only"
            )
        if d.device_kind not in TPU_CHIPS:
            raise RuntimeError(
                f"unknown TPU kind {d.device_kind!r}; known: "
                f"{sorted(TPU_CHIPS)} — add it to "
                "kungfu_tpu.parallel.chip.TPU_CHIPS with its peaks"
            )
    return TPU_CHIPS[devices[0].device_kind]


_REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a directory that does
    not move, and return it. Call before the first compile.

    `JAX_COMPILATION_CACHE_DIR`, where set, is JAX's own and wins: nothing
    is set in code. Otherwise the cache lives at `<checkout>/.jax_cache` —
    a fixed path, because the path is part of how a later process finds
    the entries. Reload-mode resizes restart every worker; this is what
    lets the restarted workers load their programs instead of compiling
    them again.

    Whether they did is watched from here on, once a process (every
    launcher comes through here before its first compile): each compile
    request with the cache's answer, and the collector's pauses, in the ring.
    """
    device.watch_compiles()
    tracing.watch_gc()
    # by default the key leaves metadata out, so a program cached before a
    # `jax.named_scope` changed is loaded with its old `op_name`s and a
    # profile shows those (chip run, PR 24)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", _REPO_CACHE)
    return _REPO_CACHE
