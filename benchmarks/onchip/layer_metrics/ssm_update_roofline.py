"""Kernels: the bytes the Mamba-2 decode update must move — each layer's SSM
state in and out for every slot the program computes (all of the pool, live
or not: static shapes) plus its inputs and its output — over peak HBM bytes/s,
against the traced device time under the ``ssm_update`` scope. Layer-steps
are counted from the trace: runs of ``jit_decode_chunk`` x the chunk's steps
x the Mamba-2 layers."""

from harness import counts_hybrid, program_trace
from harness.peaks import peaks


def read(run, name):
    tr, parsed = run.get("trace"), program_trace.of_run(run)
    if not tr or not parsed or run["device"]["platform"] != "tpu":
        return None
    took = parsed["scopes"]["by_scope"].get("ssm_update")
    cfg = run["cell"]["config"]
    steps = len(counts_hybrid.decode_chunk_runs(tr)) * run["decode_chunk_steps"]
    if not took or not steps or "mamba_n_heads" not in cfg:
        return None
    layers = sum(k == "mamba" for k in cfg["layer_types"])
    need = steps * layers * counts_hybrid.ssm_update_bytes(cfg, run["slots"])
    return 100.0 * need / peaks(run["device"]["kind"])["hbm_bytes_per_s"] / took
