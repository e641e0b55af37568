"""The chip under the program: which TPUs the repo knows, and where the
compiled programs are kept.

One table names every `device_kind` the repo has run on, with the
published peaks a benchmark divides by. `chip_smoke.py` and `benchmark/`
both read it; a device that is not in it is an error, never a default.
"""

from __future__ import annotations

import dataclasses
import inspect
import os
import time
import uuid

import jax

from kungfu_tpu.telemetry import device, log, tracing


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

    That holds for every worker of a joined world, not process 0 alone:
    JAX writes entries from process 0 only, and from here on any other
    process writes the programs that run on its own devices alone
    (`_let_peers_write_their_own`). So a peer compiles such a program once
    and loads it after every restart; a `miss` on a restarted worker means a
    new program, a new device layout or a new checkout.

    Whether they did is watched from here on, once a process (every
    launcher comes through here before its first compile): each compile
    request with the cache's answer, and the collector's pauses, in the ring.
    """
    device.watch_compiles()
    tracing.watch_gc()
    _let_peers_write_their_own()
    # by default the key leaves metadata out, so a program cached before a
    # `jax.named_scope` changed is loaded with its old `op_name`s and a
    # profile shows those (chip run, PR 24)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", _REPO_CACHE)
    return _REPO_CACHE


# `jax._src.compiler._compile_and_write_cache` as JAX 0.9.0 has it
_COMPILE_AND_WRITE = ("backend", "computation", "executable_devices",
                      "compile_options", "host_callbacks", "module_name",
                      "cache_key")
_seam_tried = False


def _let_peers_write_their_own() -> None:
    """Have a process that is not process 0 of its world write what it
    compiles for its own devices alone to the persistent cache. Once a
    process; the one place that reaches into JAX's compile path.

    `compiler._cache_write` returns at once where
    `distributed.global_state.process_id != 0`, so that the processes of a
    world do not all write the entry of a program they share. But
    `cache_key.get` hashes the device assignment (it strips it for GPUs
    only), so a program for this process's devices alone has a key no other
    process computes: nobody else can write it, and without this the
    process compiles it again after every restart (23.5 s of the kfrun
    cell's 46 s `setup_s`, PERF.md, PR 40). A program with a device of
    another process in it stays process 0's to write, as do JAX's other
    rules: none with a host callback, none that compiled faster than
    `jax_persistent_cache_min_compile_time_secs`.

    JAX has no option for this, so `compiler._compile_and_write_cache` is
    wrapped, a private name, checked here with the others it takes; where
    one is missing nothing is installed, a warning says so once, and peers
    compile again as JAX alone has them."""
    global _seam_tried
    if _seam_tried:
        return
    _seam_tried = True
    try:
        from jax._src import compilation_cache, compiler, distributed, lru_cache
        from jax._src.lib import xla_client

        jax_writes = compiler._compile_and_write_cache
        takes = tuple(inspect.signature(jax_writes).parameters)
        if takes != _COMPILE_AND_WRITE:
            raise AttributeError(f"_compile_and_write_cache takes {takes}")
        for owner, name in (
                (compilation_cache, "put_executable_and_time"),
                (compilation_cache, "_get_cache"),
                (lru_cache, "LRUCache"), (lru_cache, "_CACHE_SUFFIX"),
                (distributed.global_state, "process_id"),
                (xla_client.DeviceList, "is_fully_addressable"),
                (jax.config, "jax_persistent_cache_min_compile_time_secs")):
            getattr(owner, name)
    except (ImportError, AttributeError) as e:
        log.warning(
            "compile cache: jax %s has no place for a peer's writes (%s); "
            "every process but process 0 of a world compiles its own "
            "programs again after each start", jax.__version__, e)
        return

    def write_atomically(cache_key, module_name, executable, backend, seconds):
        """Through JAX's own `put_executable_and_time`, in a way no reader
        can take half a file from. With eviction on, `LRUCache` reads and
        writes under its file lock: that covers it. With it off (the
        default) there is no lock and `put` is `Path.write_bytes`: the
        entry is written under a name of this process's own and renamed to
        the key's, and of two writers of one key the later rename wins
        whole. Returns whether an entry of this process's now stands."""
        cache = compilation_cache._get_cache(backend)
        if not isinstance(cache, lru_cache.LRUCache):
            return False  # no cache, or not one of files in a directory
        entry = cache.path / f"{cache_key}{lru_cache._CACHE_SUFFIX}"
        if entry.exists():
            return False
        if cache.eviction_enabled:
            compilation_cache.put_executable_and_time(
                cache_key, module_name, executable, backend, seconds)
            return entry.exists()
        mine = f"{cache_key}.peer{uuid.uuid4().hex}"
        compilation_cache.put_executable_and_time(
            mine, module_name, executable, backend, seconds)
        written = cache.path / f"{mine}{lru_cache._CACHE_SUFFIX}"
        if not written.exists():  # under `jax_persistent_cache_min_entry_size_bytes`
            return False
        written.replace(entry)
        return True

    def compile_and_write(backend, computation, executable_devices,
                          compile_options, host_callbacks, module_name,
                          cache_key):
        start = time.monotonic()
        executable = jax_writes(backend, computation, executable_devices,
                                compile_options, host_callbacks, module_name,
                                cache_key)
        took = time.monotonic() - start
        if (distributed.global_state.process_id != 0 and not host_callbacks
                and executable_devices.is_fully_addressable
                and took >= jax.config.jax_persistent_cache_min_compile_time_secs):
            try:
                if write_atomically(cache_key, module_name, executable,
                                    backend, int(took)):
                    device.wrote_as_peer()
            except Exception as e:  # the program is compiled: run it
                log.warning("compile cache: entry of %s not written: %s: %s",
                            module_name, type(e).__name__, e)
        return executable

    compiler._compile_and_write_cache = compile_and_write
