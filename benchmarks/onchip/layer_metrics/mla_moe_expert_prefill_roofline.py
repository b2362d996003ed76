"""Model step: the least time the chip could take for the held experts'
contraction of the traced prefill chunks — per mixture layer and chunk the
larger of the routed pairs' FLOPs (the chunk's tokens, from its
``tpu_engine.batcher.prefill`` annotation, x the assignments a token makes on
held experts, from the engine's counters, x six FLOPs a weight) over peak bf16
FLOP/s and the bytes of the experts hit over peak HBM bytes/s — over the traced
device time of the PREFILL program's ops under the ``moe_experts`` scope. WHICH
BOUNDS: at 2 048 tokens and 1.5 held assignments a token the pairs are 53 GFLOP
(0.27 ms at the peak) and the 16 held experts 138 MB (0.17 ms): the FLOPs
bound a full chunk, by half again. A contraction that computes every held
expert for every token (the masked form the program runs, PERF.md §6 PR 40)
does 16 / 1.5 times the pairs' work and reads low: that is the number's
purpose."""

from harness import counts_mla_moe, counts_sala, program_trace
from harness.peaks import peaks


def read(run, name):
    parsed, cfg, st = program_trace.of_run(run), run["cell"]["config"], run.get("engine_stats") or {}
    if not parsed or run["device"]["platform"] != "tpu" or not counts_mla_moe.is_mla_moe(cfg):
        return None
    held = counts_mla_moe.held_assignments_per_token(st, "prefill", cfg["num_experts_per_tok"])
    hit = counts_mla_moe.per_layer_step(st, "prefill", "experts_hit")
    took = counts_sala.seconds_under(run, "prefill_chunk", "moe_experts")
    chunks = counts_sala.prefill_chunks(parsed)
    if not held or not hit or not took or not chunks:
        return None
    pk = peaks(run["device"]["kind"])
    need = sum(max(tokens * held * counts_mla_moe.assignment_flops(cfg) / pk["flops_bf16"],
                   hit * counts_mla_moe.expert_bytes(cfg) / pk["hbm_bytes_per_s"]) for _, tokens in chunks)
    return 100.0 * counts_mla_moe.n_mixture_layers(cfg) * need / took
