"""Model step: bytes one decode step of a DeepSeek-V3-recipe stack must move —
every layer's MLA weights and the live rows' latent at their real lengths, the
leading dense layer's SwiGLU, per mixture layer the router, the shared experts
and the held experts some row chose (the engine's counters), the untied head
(``counts_mla_moe.decode_step_bytes``) — over peak HBM bytes/s, against the
traced device time of one decode step: the median run of ``jit_decode_chunk``
over the chunk's steps. The cell's share of the whole step's peak."""

import statistics

from harness import counts_hybrid, counts_mla_moe, counts_sala
from harness.peaks import peaks


def read(run, name):
    tr, cfg, st = run.get("trace"), run["cell"]["config"], run.get("engine_stats") or {}
    if not tr or run["device"]["platform"] != "tpu" or not counts_mla_moe.is_mla_moe(cfg):
        return None
    runs = counts_hybrid.decode_chunk_runs(tr)
    hit = counts_mla_moe.per_layer_step(st, "decode", "experts_hit")
    context = counts_sala.decoding_context(run)
    if not runs or not hit or not context:
        return None
    step_s = statistics.median(runs) / run["decode_chunk_steps"]
    need = counts_mla_moe.decode_step_bytes(cfg, context, hit)
    return 100.0 * need / peaks(run["device"]["kind"])["hbm_bytes_per_s"] / step_s
