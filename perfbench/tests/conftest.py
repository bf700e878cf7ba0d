"""``pytest perfbench/tests`` — the benchmark's own tests, on the CPU (not
part of the repository's tier-1 suite, whose testpaths is ``tests``)."""

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

# in-process tests (reference, reducers) run on four virtual CPU devices;
# the end-to-end rehearsals are subprocesses and set their own count
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4").strip()
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # the AOT compiles' libtpu

import jax  # noqa: E402

# the AOT compiles for a described chip cannot be read back from the
# persistent cache without one, and would warn on every run
jax.config.update("jax_enable_compilation_cache", False)
