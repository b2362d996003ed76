"""Train program: median boundary-to-boundary step interval in the window."""

import statistics


def read(run, name):
    iv = run.get("intervals_s")
    return statistics.median(iv) * 1e3 if iv else None
