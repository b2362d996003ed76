"""Model step: the bytes the held experts' decode contraction must move — the
weights of the experts that some row chose, once per mixture layer-step
(``experts hit`` a decode layer-step from the engine's counters
``moe_decode_experts_hit_total`` / ``moe_decode_layer_steps_total``), over peak
HBM bytes/s — against the traced device time of the DECODE program's ops under
the ``moe_experts`` scope. Layer-steps of the traced window: runs of
``jit_decode_chunk`` x the chunk's steps x the MIXTURE layers (a leading dense
layer has no experts). The activations (32 rows) are a thousandth of the
weights and are left out. The family's sizes come from its counts module
(``harness/counts_for.py``); a configuration without a mixture reads nothing."""

from harness import counts_hybrid, counts_sala
from harness.counts_for import mixture_counts_for
from harness.peaks import peaks


def read(run, name):
    tr, cfg, st = run.get("trace"), run["cell"]["config"], run.get("engine_stats") or {}
    family = mixture_counts_for(cfg)
    if not tr or run["device"]["platform"] != "tpu" or not family:
        return None
    hit = family.per_layer_step(st, "decode", "experts_hit")
    took = counts_sala.seconds_under(run, "decode_chunk", "moe_experts")
    steps = len(counts_hybrid.decode_chunk_runs(tr)) * run["decode_chunk_steps"]
    if not hit or not took or not steps:
        return None
    need = steps * family.n_mixture_layers(cfg) * hit * family.expert_bytes(cfg)
    return 100.0 * need / peaks(run["device"]["kind"])["hbm_bytes_per_s"] / took
