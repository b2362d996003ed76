"""Kernels: the bytes the lightning decode update must move — each layer's
state in and out for every slot the program computes (all of the pool, live or
not: static shapes) plus q, k, v and the output — over peak HBM bytes/s,
against the traced device time under the ``lightning_update`` scope (the
state's write-back lies inside it). Layer-steps are counted from the trace:
runs of ``jit_decode_chunk`` x the chunk's steps x the lightning layers."""

from harness import counts_sala, program_trace
from harness.peaks import peaks


def read(run, name):
    tr, parsed = run.get("trace"), program_trace.of_run(run)
    cfg = run["cell"]["config"]
    if not tr or not parsed or run["device"]["platform"] != "tpu" or not counts_sala.has_both_kinds(cfg):
        return None
    took = parsed["scopes"]["by_scope"].get("lightning_update")
    steps = len(counts_sala.decode_chunk_runs(tr)) * run["decode_chunk_steps"]
    if not took or not steps:
        return None
    layers = sum(k == "lightning-attn" for k in cfg["mixer_types"])
    need = steps * layers * counts_sala.lightning_update_bytes(cfg, run["slots"])
    return 100.0 * need / peaks(run["device"]["kind"])["hbm_bytes_per_s"] / took
