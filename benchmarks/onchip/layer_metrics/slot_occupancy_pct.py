"""Batcher: mean share of slots in use, sampled at every dispatch in the
window."""

import statistics


def read(run, name):
    occ = run.get("occupancy")
    return 100.0 * statistics.fmean(occ) / run["slots"] if occ else None
