"""Pytest root conftest: force an 8-virtual-device CPU mesh for all tests.

This is the TPU-world upgrade of the reference's test affordances
(SURVEY.md §4: injectable telemetry, mock fleet, dry-run): real mesh/pjit/
FSDP semantics on one host, no TPU required.

Both settings are plain environment: they are read when the backend
initialises, at the first device query, which no import below triggers.
The ``jax.config.update`` pins the platform even where the caller's
environment names another (a TPU host sets ``JAX_PLATFORMS=tpu,cpu``).
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Drop compiled programs between test modules.

    A single-process run of the FULL suite (fast + slow, 430 tests)
    accumulates every module's jitted executables in the CPU client and
    aborts (SIGABRT inside XLA:CPU execution) in the final module —
    reproducible at ~the 420th test, gone when either half runs alone.
    Per-module cache clearing bounds the accumulation; modules recompile
    their own programs anyway (shapes differ across modules), so the
    only cost is losing cross-module cache hits that barely exist."""
    yield
    jax.clear_caches()
