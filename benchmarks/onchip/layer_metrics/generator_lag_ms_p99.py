"""Load generator (benchmark): how late requests were sent against when they
were due, 99th percentile. A starved generator must not read as a fast server."""

from harness.stats import percentile


def read(run, name):
    lag = run.get("lag_s")
    return percentile(lag, 99) * 1e3 if lag else None
