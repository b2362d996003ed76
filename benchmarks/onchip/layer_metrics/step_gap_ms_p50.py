"""Supervisor loop: median idle time between consecutive step programs on the
chip (device trace, ``XLA Modules`` line)."""

import statistics


def read(run, name):
    gaps = (run.get("trace") or {}).get("module_gaps_s")
    return statistics.median(gaps) * 1e3 if gaps else None
