"""Batcher: mean host milliseconds an engine step spends in its ``emit`` phase (the locked emission
loop), from the ``tpu_engine.batcher.emit`` annotations of the traced window."""

from harness import program_trace


def read(run, name):
    return program_trace.phase_ms_per_step(run, "batcher.emit")
