"""Kernels: the bytes a sparse layer's decode step must move — for each row
that decodes its compressed keys at its real length and the keys and values of
its 64 chosen blocks per kv-head, nothing of the rest of the row — over peak HBM
bytes/s, against the traced device time of the DECODE program's ops under the
``sparse_attn`` scope (projections, writes, indexer, gather and attention).
Layer-steps: runs of ``jit_decode_chunk`` x the chunk's steps x the sparse
layers; the rows that decode and their contexts are
``counts_sala.decoding_rows`` / ``decoding_context`` (not the slots held, of
which some still ingest their prompt)."""

from harness import counts_sala
from harness.peaks import peaks


def read(run, name):
    tr = run.get("trace")
    cfg = run["cell"]["config"]
    if not tr or run["device"]["platform"] != "tpu" or not counts_sala.has_both_kinds(cfg):
        return None
    took = counts_sala.seconds_under(run, "decode_chunk", "sparse_attn")
    steps = len(counts_sala.decode_chunk_runs(tr)) * run["decode_chunk_steps"]
    rows, context = counts_sala.decoding_rows(run), counts_sala.decoding_context(run)
    if not took or not steps or not rows or not context:
        return None
    layers = sum(k == "minicpm4" for k in cfg["mixer_types"])
    need = steps * layers * counts_sala.sparse_decode_bytes(cfg, rows, context)
    return 100.0 * need / peaks(run["device"]["kind"])["hbm_bytes_per_s"] / took
