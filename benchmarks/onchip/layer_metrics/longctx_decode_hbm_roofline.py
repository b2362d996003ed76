"""Model step: bytes one decode step of a sparse + lightning stack must move —
every layer's weights once in the serving dtype, the untied head, the
compressed keys and chosen blocks (not the whole rows) of the rows that decode
(``counts_sala.decoding_rows``: not the slots held, of which some still
ingest), and the lightning state of every slot in and out — over peak HBM bytes/s, against the
traced device time of one decode step: the median run of ``jit_decode_chunk``
over the chunk's steps. ``hybrid_decode_hbm_roofline.py`` counts a Mamba-2
hybrid; this is its twin."""

import statistics

from harness import counts_sala
from harness.peaks import peaks


def read(run, name):
    tr = run.get("trace")
    cfg = run["cell"]["config"]
    if not tr or run["device"]["platform"] != "tpu" or not counts_sala.has_both_kinds(cfg):
        return None
    runs = counts_sala.decode_chunk_runs(tr)
    rows, context = counts_sala.decoding_rows(run), counts_sala.decoding_context(run)
    if not runs or not rows or not context:
        return None
    step_s = statistics.median(runs) / run["decode_chunk_steps"]
    need = counts_sala.decode_step_bytes(cfg, run["slots"], rows, context)
    return 100.0 * need / peaks(run["device"]["kind"])["hbm_bytes_per_s"] / step_s
