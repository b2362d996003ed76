"""Kernels: the least time the chip could take for the chunked scans the
traced prefill chunks needed — per Mamba-2 layer and chunk the larger of its
FLOPs over peak bf16 FLOP/s and its bytes over peak HBM bytes/s, at the
chunk's own length (the ``tokens=`` of its ``tpu_engine.batcher.prefill``
annotation) — over the traced device time under the ``ssm_scan`` scope."""

from harness import counts_hybrid, program_trace
from harness.peaks import peaks


def read(run, name):
    parsed = program_trace.of_run(run)
    if not parsed or run["device"]["platform"] != "tpu":
        return None
    took = parsed["scopes"]["by_scope"].get("ssm_scan")
    cfg = run["cell"]["config"]
    chunks = [int(args["tokens"]) for *_, phase, args in parsed["annotations"]
              if phase == "batcher.prefill" and "tokens" in args]
    if not took or not chunks or "mamba_n_heads" not in cfg:
        return None
    pk = peaks(run["device"]["kind"])
    layers = sum(k == "mamba" for k in cfg["layer_types"])
    need = sum(max(counts_hybrid.ssd_chunk_flops(cfg, t) / pk["flops_bf16"],
                   counts_hybrid.ssd_chunk_bytes(cfg, t) / pk["hbm_bytes_per_s"]) for t in chunks)
    return 100.0 * layers * need / took
