"""Supervisor loop: the program's own median step time — ``describe()["profile"]``'s
begin-to-begin total (``StepProfiler``, host clock) — the inside twin of the
harness's ``step_time_ms``."""


def read(run, name):
    total = (run.get("profile") or {}).get("total")
    return total["p50_ms"] if total else None
