"""Test config: an 8-device virtual CPU mesh, set before the backend starts.

Mirrors the reference's multi-process-on-localhost test strategy
(SURVEY.md §4): we get multi-chip semantics on one machine via XLA's
host-platform device partitioning instead of kungfu-run subprocesses
(those are exercised separately in the integration tests).

Note: a pytest plugin imports jax before this file runs, so plain env vars
are too late; jax.config.update works until the backend is initialized.
"""

import os

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# Tests compile for the CPU and must leave no compile cache in the
# checkout: enable_compile_cache() places the directory, this switches the
# cache itself off — here, and through the environment in every agent the
# tests spawn.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
jax.config.update("jax_enable_compilation_cache", False)
