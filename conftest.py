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
import sys
import threading

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


@pytest.fixture(autouse=True, scope="module")
def _stop_scheduler_pumps_per_module():
    """Stop the scheduler pumps a module leaves running.

    A test that builds a ``TPULauncher`` and does not shut its scheduler
    down leaves the ``fleet-scheduler`` thread polling for the rest of the
    worker's life (five modules do), and every pass writes the queue's
    depths into whatever historian is installed process-wide. The twin's
    scale lane installs its own and counts its samples, so
    ``tests/test_twin.py`` failed whenever xdist dealt it to a worker after
    one of those modules (PR 48: three whole runs of four). The backend's
    singleton is left alone: later modules submit through it."""
    yield
    state = sys.modules.get("backend.state")
    keep = getattr(getattr(state, "launcher", None), "scheduler", None)
    for t in threading.enumerate():
        sched = getattr(getattr(t, "_target", None), "__self__", None)
        if t.name == "fleet-scheduler" and sched is not None and sched is not keep:
            sched.shutdown()
