"""Batcher: median over requests of the mean time per output token after the
first."""

from harness.stats import percentile


def read(run, name):
    xs = run.get("tpot_ms")
    return percentile(xs, 50) if xs else None
