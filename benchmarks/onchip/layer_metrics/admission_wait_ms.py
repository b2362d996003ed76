"""Admission: length of the scheduler's own ``admission`` span of this job
or replica (flight recorder)."""


def read(run, name):
    spans = [s for s in run.get("spans", []) if s.get("name") == "admission"]
    if not spans:
        return None
    s = spans[-1]
    return (s["t1"] - s["t0"]) * 1e3
