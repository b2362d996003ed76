"""Supervisor loop: host milliseconds per step outside the blocking device
read, from ``describe()["profile"]`` (StepProfiler, host clock): data +
dispatch + other."""


def read(run, name):
    phases = (run.get("profile") or {}).get("phases") or {}
    if not phases:
        return None
    return sum(phases[p]["p50_ms"] for p in phases if p != "device")
