"""Batcher: mean host milliseconds an engine step spends in its ``prefill`` phase (one chunk of one
prompt, which every running slot's next token waits behind), from the ``tpu_engine.batcher.prefill``
annotations of the traced window."""

from harness import program_trace


def read(run, name):
    return program_trace.phase_ms_per_step(run, "batcher.prefill")
