"""Model step: the bytes the held experts' decode contraction must move — the
weights of the experts that some row chose, once per mixture layer-step
(``experts hit`` a decode layer-step from the engine's counters
``moe_decode_experts_hit_total`` / ``moe_decode_layer_steps_total``), over peak
HBM bytes/s — against the traced device time of the DECODE program's ops under
the ``moe_experts`` scope. Layer-steps of the traced window: runs of
``jit_decode_chunk`` x the chunk's steps x the MIXTURE layers (the leading
dense layer has no experts). ``expert_decode_roofline.py`` asks for granite's
keys and reads nothing of this family."""

from harness import counts_hybrid, counts_mla_moe, counts_sala
from harness.peaks import peaks


def read(run, name):
    tr, cfg, st = run.get("trace"), run["cell"]["config"], run.get("engine_stats") or {}
    if not tr or run["device"]["platform"] != "tpu" or not counts_mla_moe.is_mla_moe(cfg):
        return None
    hit = counts_mla_moe.per_layer_step(st, "decode", "experts_hit")
    took = counts_sala.seconds_under(run, "decode_chunk", "moe_experts")
    steps = len(counts_hybrid.decode_chunk_runs(tr)) * run["decode_chunk_steps"]
    if not hit or not took or not steps:
        return None
    need = steps * counts_mla_moe.n_mixture_layers(cfg) * hit * counts_mla_moe.expert_bytes(cfg)
    return 100.0 * need / peaks(run["device"]["kind"])["hbm_bytes_per_s"] / took
