"""Kernels: share of device busy time inside the Mosaic flash-attention
kernels (``tpu_custom_call`` events of the trace)."""


def read(run, name):
    tr = run.get("trace")
    if not tr or not tr["kernel_seconds"]:
        return None
    return 100.0 * sum(tr["kernel_seconds"].values()) / tr["busy_s"]
