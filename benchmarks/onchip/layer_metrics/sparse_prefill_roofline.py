"""Kernels: the FLOPs the sparse layers of the traced prefill chunks needed —
the indexer's scores against the compressed keys a query sees, and attention
over the lanes of its CHOSEN blocks only (below ``dense_len``: every lane up to
itself) — over peak bf16 FLOP/s, against the traced device time of the PREFILL
program's ops under ``sparse_index`` and ``sparse_attend``. A prefill that
computes more than the chosen lanes reads low here by as much as it computes
in vain: ``generate._sparse_prefill`` attends through the kernel
``sparse_chunk_attn`` (since PR 32), which visits every key tile in which SOME
query of a 128-query tile chose a block and masks per query, and with random
weights that is every tile the queries can see. A chunk's first position is its
index (``chunk=`` of its ``tpu_engine.batcher.prefill`` annotation) x the
configured prefill chunk."""

from harness import counts_sala, program_trace
from harness.peaks import peaks


def read(run, name):
    parsed = program_trace.of_run(run)
    cfg = run["cell"]["config"]
    if not parsed or run["device"]["platform"] != "tpu" or not counts_sala.has_both_kinds(cfg):
        return None
    took = [counts_sala.seconds_under(run, "prefill_chunk", s) for s in ("sparse_index", "sparse_attend")]
    chunks = counts_sala.prefill_chunks(parsed)
    if not all(took) or not chunks:
        return None
    size = cfg["program"]["prefill_chunk"]
    layers = sum(k == "minicpm4" for k in cfg["mixer_types"])
    need = sum(counts_sala.sparse_prefill_flops(cfg, i * size, t) for i, t in chunks)
    return 100.0 * layers * need / peaks(run["device"]["kind"])["flops_bf16"] / sum(took)
