"""Kernels: the least time the chip could take for the lightning scans the
traced prefill chunks needed — per lightning layer and chunk the larger of its
FLOPs (the recurrent form's) over peak bf16 FLOP/s and its bytes over peak HBM
bytes/s, at the chunk's own length (the ``tokens=`` of its
``tpu_engine.batcher.prefill`` annotation) — over the traced device time under
the ``lightning_scan`` scope."""

from harness import counts_sala, program_trace
from harness.peaks import peaks


def read(run, name):
    parsed = program_trace.of_run(run)
    cfg = run["cell"]["config"]
    if not parsed or run["device"]["platform"] != "tpu" or not counts_sala.has_both_kinds(cfg):
        return None
    took = parsed["scopes"]["by_scope"].get("lightning_scan")
    chunks = counts_sala.prefill_chunks(parsed)
    if not took or not chunks:
        return None
    pk = peaks(run["device"]["kind"])
    layers = sum(k == "lightning-attn" for k in cfg["mixer_types"])
    need = sum(max(counts_sala.lightning_chunk_flops(cfg, t) / pk["flops_bf16"],
                   counts_sala.lightning_chunk_bytes(cfg, t) / pk["hbm_bytes_per_s"]) for _, t in chunks)
    return 100.0 * layers * need / took
