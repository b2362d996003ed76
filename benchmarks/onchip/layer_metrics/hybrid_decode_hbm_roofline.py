"""Model step: bytes one decode step of a hybrid stack must move — the weights
of both kinds of layer once in the serving dtype, the tied head, keys and
values of the attention layers at the slots' real lengths, and the recurrent
state of every Mamba-2 layer in and out for every slot (the program computes
all of the pool) — over peak HBM bytes/s, against the traced device time of
one decode step: the median run of ``jit_decode_chunk`` over the chunk's steps.
``decode_hbm_roofline.py`` counts Llama layers; this is its twin."""

import statistics

from harness import counts_hybrid
from harness.peaks import peaks


def read(run, name):
    tr = run.get("trace")
    cfg = run["cell"]["config"]
    if not tr or run["device"]["platform"] != "tpu" or "mamba_n_heads" not in cfg:
        return None
    runs = counts_hybrid.decode_chunk_runs(tr)
    if not runs or not run.get("dispatch_context"):
        return None
    step_s = statistics.median(runs) / run["decode_chunk_steps"]
    need = (counts_hybrid.weight_bytes_per_decode_step(cfg)
            + counts_hybrid.kv_bytes_per_decode_step(cfg, statistics.fmean(run["dispatch_context"]))
            + counts_hybrid.recurrent_bytes_per_decode_step(cfg, run["slots"]))
    return 100.0 * need / peaks(run["device"]["kind"])["hbm_bytes_per_s"] / step_s
