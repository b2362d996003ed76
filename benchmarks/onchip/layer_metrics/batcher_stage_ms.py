"""Batcher: mean host milliseconds an engine step spends in its ``stage`` phase (host arrays, transfers
and the decode dispatch), from the ``tpu_engine.batcher.stage`` annotations of the traced window."""

from harness import program_trace


def read(run, name):
    return program_trace.phase_ms_per_step(run, "batcher.stage")
