"""Test harness: simulate an 8-chip mesh with virtual CPU devices.

The reference tests spawn one process per GPU (SURVEY.md §4); under JAX's
single-controller model the equivalent is a single process whose mesh spans
8 virtual CPU devices (``xla_force_host_platform_device_count``) — the same
mechanism the driver uses for multi-chip dry runs.
"""

import os

os.environ.setdefault("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# tests assert on compile windows (ledger `compile` class, anomaly skip,
# autotune recompile accounting): a persistent-cache hit left by an earlier
# run would shrink them, so the in-process suite opts out of the cache that
# init_process_group places at <checkout>/.jax_cache
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_global_mesh():
    # isolate tests that set the global mesh
    from bagua_tpu.parallel import mesh as mesh_mod

    yield
    mesh_mod._GLOBAL_MESH = None
    from bagua_tpu import communication

    communication._BACKENDS.clear()


N_DEVICES = 8
