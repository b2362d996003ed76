"""Batcher: median time to first token, from due time to the engine's stamp."""

from harness.stats import percentile


def read(run, name):
    xs = run.get("ttft_ms")
    return percentile(xs, 50) if xs else None
