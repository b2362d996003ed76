"""Device: 1 - union of device-op intervals over the traced window, averaged
over the chips used."""


def read(run, name):
    tr = run.get("trace")
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"]) if tr else None
