"""Tests of the benchmark's own code, on the CPU, in seconds to a few minutes:

    pytest benchmarks/onchip/tests

They never give a time, a rate or a utilisation of a device."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

# The repository's root conftest asks for 8 virtual CPU devices; a one-chip
# cell's mesh has to fit the device count exactly (the supervisor's elastic
# check), so these tests run on one. Nothing has queried a device yet.
import re  # noqa: E402

os.environ["XLA_FLAGS"] = re.sub(
    r"--xla_force_host_platform_device_count=\d+", "--xla_force_host_platform_device_count=1",
    os.environ.get("XLA_FLAGS", "--xla_force_host_platform_device_count=1"))
os.environ["JAX_PLATFORMS"] = "cpu"
