"""Model step: the least time the chip could take for the held experts'
contraction of the traced prefill chunks — per layer and chunk the larger of
the routed pairs' FLOPs (the chunk's tokens, the ``tokens=`` of its
``tpu_engine.batcher.prefill`` annotation, x the assignments a token makes on
held experts, from the engine's counters, x six FLOPs a weight) over peak bf16
FLOP/s and the bytes of the experts hit (counters again) over peak HBM bytes/s
— over the traced device time of the PREFILL program's ops under the
``moe_experts`` scope. A contraction that computes every held expert for every
token does seven times the routed pairs' work and reads low here: that is the
number's purpose."""

from harness import counts_hybrid_moe, counts_sala, program_trace
from harness.peaks import peaks


def read(run, name):
    parsed, cfg, st = program_trace.of_run(run), run["cell"]["config"], run.get("engine_stats") or {}
    if not parsed or run["device"]["platform"] != "tpu" or not counts_hybrid_moe.is_mixture(cfg):
        return None
    held = counts_hybrid_moe.held_assignments_per_token(st, "prefill", cfg["num_experts_per_tok"])
    hit = counts_hybrid_moe.per_layer_step(st, "prefill", "experts_hit")
    took = counts_sala.seconds_under(run, "prefill_chunk", "moe_experts")
    chunks = counts_sala.prefill_chunks(parsed)
    if not held or not hit or not took or not chunks:
        return None
    pk = peaks(run["device"]["kind"])
    need = sum(max(tokens * held * counts_hybrid_moe.assignment_flops(cfg) / pk["flops_bf16"],
                   hit * counts_hybrid_moe.expert_bytes(cfg) / pk["hbm_bytes_per_s"]) for _, tokens in chunks)
    return 100.0 * counts_hybrid_moe.n_layers(cfg) * need / took
