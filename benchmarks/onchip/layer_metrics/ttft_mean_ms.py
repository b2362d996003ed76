"""Batcher: mean time to first token over the requests that got one."""

import math
import statistics


def read(run, name):
    xs = [x for x in run.get("ttft_ms", []) if x != math.inf]
    return statistics.fmean(xs) if xs else None
