"""Device: ``memory_stats()`` peak on the fullest chip, in-use plus reserved."""


def read(run, name):
    return run["peak_bytes"] / 2**30 if run.get("peak_bytes") else None
