"""Admission: the estimate it admitted on minus the measured peak (in-use plus
reserved) on the fullest chip. Positive: the estimator is conservative."""


def read(run, name):
    if run.get("estimate_gib") is None or not run.get("peak_bytes"):
        return None
    return run["estimate_gib"] - run["peak_bytes"] / 2**30
