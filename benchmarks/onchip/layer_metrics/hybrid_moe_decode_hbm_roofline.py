"""Model step: bytes one decode step of a hybrid stack with a mixture after
every mixer must move — both kinds of mixer and the tied head once, per layer
the router, the shared expert and the experts some row chose (the engine's
counters), keys and values of the attention layers at the slots' real lengths,
the recurrent state of every slot in and out — over peak HBM bytes/s, against
the traced device time of one decode step: the median run of
``jit_decode_chunk`` over the chunk's steps. ``hybrid_decode_hbm_roofline.py``
counts one dense MLP a layer and would under-read here."""

import statistics

from harness import counts_hybrid, counts_hybrid_moe
from harness.peaks import peaks


def read(run, name):
    tr, cfg, st = run.get("trace"), run["cell"]["config"], run.get("engine_stats") or {}
    if not tr or run["device"]["platform"] != "tpu" or not counts_hybrid_moe.is_mixture(cfg):
        return None
    runs = counts_hybrid.decode_chunk_runs(tr)
    hit = counts_hybrid_moe.per_layer_step(st, "decode", "experts_hit")
    if not runs or not hit or not run.get("dispatch_context"):
        return None
    step_s = statistics.median(runs) / run["decode_chunk_steps"]
    need = counts_hybrid_moe.decode_step_bytes(cfg, run["slots"], statistics.fmean(run["dispatch_context"]), hit)
    return 100.0 * need / peaks(run["device"]["kind"])["hbm_bytes_per_s"] / step_s
