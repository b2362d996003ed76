"""Model step: the least time the chip could take for the held experts'
contraction of the traced prefill chunks — per mixture layer and chunk the
larger of the routed pairs' FLOPs (the chunk's tokens, the ``tokens=`` of its
``tpu_engine.batcher.prefill`` annotation, x the assignments a token makes on
held experts, from the engine's counters, x six FLOPs a weight) over peak bf16
FLOP/s and the bytes of the experts hit (counters again) over peak HBM bytes/s
— over the traced device time of the PREFILL program's ops under the
``moe_experts`` scope. WHICH BOUNDS: in longctx32 at 2 048 tokens and 1.5 held
assignments a token the pairs are 53 GFLOP (0.27 ms at the peak) and the 16
held experts 138 MB (0.17 ms): the FLOPs bound a full chunk, by half again. A
contraction that computes every held expert for every token (the masked form,
PERF.md §6 PR 40) does seven (batch32) or 16 / 1.5 (longctx32) times the routed
pairs' work and reads low here: that is the number's purpose. The family's
sizes come from its counts module (``harness/counts_for.py``)."""

from harness import counts_sala, program_trace
from harness.counts_for import mixture_counts_for
from harness.peaks import peaks


def read(run, name):
    parsed, cfg, st = program_trace.of_run(run), run["cell"]["config"], run.get("engine_stats") or {}
    family = mixture_counts_for(cfg)
    if not parsed or run["device"]["platform"] != "tpu" or not family:
        return None
    held = family.held_assignments_per_token(st, "prefill", cfg["num_experts_per_tok"])
    hit = family.per_layer_step(st, "prefill", "experts_hit")
    took = counts_sala.seconds_under(run, "prefill_chunk", "moe_experts")
    chunks = counts_sala.prefill_chunks(parsed)
    if not held or not hit or not took or not chunks:
        return None
    pk = peaks(run["device"]["kind"])
    need = sum(max(tokens * held * family.assignment_flops(cfg) / pk["flops_bf16"],
                   hit * family.expert_bytes(cfg) / pk["hbm_bytes_per_s"]) for _, tokens in chunks)
    return 100.0 * family.n_mixture_layers(cfg) * need / took
