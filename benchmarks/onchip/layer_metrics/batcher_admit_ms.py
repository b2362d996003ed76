"""Batcher: mean host milliseconds an engine step spends in its ``admit`` phase (the locked admission
pass and the ingestion-cache allocation), from the ``tpu_engine.batcher.admit`` annotations of the
traced window."""

from harness import program_trace


def read(run, name):
    return program_trace.phase_ms_per_step(run, "batcher.admit")
