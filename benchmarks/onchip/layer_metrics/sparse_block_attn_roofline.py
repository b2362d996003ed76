"""Kernels: the bytes the decode kernel ``sparse_block_attn``
(``tpu_engine/ops/sparse_block_attention.py``) must move — for each row that
decodes and kv-head the keys and values of its 64 chosen blocks, once — over
peak HBM bytes/s, against the traced device time of the kernel's own events.
The kernel runs for every slot of the pool (static shapes), decoding or not;
the count is the decoding rows' (``counts_sala.decoding_rows``: not the slots
held, of which some still ingest), so an idle pool reads low. Layer-steps:
runs of ``jit_decode_chunk`` x the chunk's steps x the sparse layers."""

from harness import counts_sala
from harness.peaks import peaks


def read(run, name):
    tr = run.get("trace")
    cfg = run["cell"]["config"]
    if not tr or run["device"]["platform"] != "tpu" or not counts_sala.has_both_kinds(cfg):
        return None
    took = sum(s for k, s in tr["kernel_seconds"].items() if "sparse_block_attn" in k)
    steps = len(counts_sala.decode_chunk_runs(tr)) * run["decode_chunk_steps"]
    rows = counts_sala.decoding_rows(run)
    if not took or not steps or not rows:
        return None
    layers = sum(k == "minicpm4" for k in cfg["mixer_types"])
    need = steps * layers * counts_sala.chosen_block_bytes(cfg, rows)
    return 100.0 * need / peaks(run["device"]["kind"])["hbm_bytes_per_s"] / took
