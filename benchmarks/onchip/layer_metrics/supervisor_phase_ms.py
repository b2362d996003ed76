"""Supervisor loop: median host milliseconds a step spends in one phase of
the loop body (``health``, ``anomaly``, ``monitor``, ``checkpoint``: the
metric name's suffix), from ``describe()["profile"]["phases"]``."""


def read(run, name):
    phases = (run.get("profile") or {}).get("phases") or {}
    phase = name.rsplit(".", 1)[1]
    return phases[phase]["p50_ms"] if phase in phases else None
