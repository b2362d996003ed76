"""Kernels: the least time the chip could take for the power-retention scans
of the traced prefill chunks — per layer and chunk the larger of its FLOPs (the
recurrent form's: a state update and one read per query head a token,
``counts_brumby.power_chunk_flops``) over peak bf16 FLOP/s and its bytes over
peak HBM bytes/s, at the chunk's own length (the ``tokens=`` of its
``tpu_engine.batcher.prefill`` annotation; of the annotated chunks those that
the device's side of the trace holds a run of a prefill program for) — over the
traced device time under the ``power_scan`` scope."""

from harness import counts_brumby as counts
from harness import program_trace
from harness.peaks import peaks


def read(run, name):
    parsed = program_trace.of_run(run)
    cfg = run["cell"]["config"]
    if not parsed or run["device"]["platform"] != "tpu" or not counts.knows(cfg):
        return None
    took = parsed["scopes"]["by_scope"].get("power_scan")
    held = sum(len(v) for k, v in run["trace"]["module_runs"].items() if k.startswith("jit_prefill"))
    chunks = counts.prefill_chunks(parsed)[:held]
    if not took or not chunks:
        return None
    pk = peaks(run["device"]["kind"])
    need = sum(max(counts.power_chunk_flops(cfg, t) / pk["flops_bf16"],
                   counts.power_chunk_bytes(cfg, t) / pk["hbm_bytes_per_s"]) for _, t in chunks)
    return 100.0 * counts.n_layers(cfg) * need / took
