"""Kernels: the bytes the power-retention decode update must move — each
layer's state and normaliser in and out for every slot the program computes
(all of the pool, live or not: static shapes) plus each row's q, k, v, gate and
output (``counts_brumby.power_update_bytes``) — over peak HBM bytes/s, against
the traced device time under the ``power_update`` scope (the kernel
``ops.power_update`` and what lays its operands out). Layer-steps are counted
from the trace: runs of ``jit_decode_chunk`` x the chunk's steps x the layers."""

from harness import counts_brumby as counts
from harness import program_trace
from harness.peaks import peaks


def read(run, name):
    tr, parsed = run.get("trace"), program_trace.of_run(run)
    cfg = run["cell"]["config"]
    if not tr or not parsed or run["device"]["platform"] != "tpu" or not counts.knows(cfg):
        return None
    took = parsed["scopes"]["by_scope"].get("power_update")
    steps = len(counts.decode_chunk_runs(tr)) * run["decode_chunk_steps"]
    if not took or not steps:
        return None
    need = steps * counts.n_layers(cfg) * counts.power_update_bytes(cfg, run["slots"])
    return 100.0 * need / peaks(run["device"]["kind"])["hbm_bytes_per_s"] / took
