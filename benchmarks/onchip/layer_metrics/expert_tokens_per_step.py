"""Model step: tokens a held expert sees in one decode layer-step of the
window, on average — the rows that decode (the window's emitted tokens over its
dispatches and the chunk's steps: not the slots held, of which some ingest or
await their prompt) x the assignments a token makes on held experts (from
``ContinuousBatcher.stats()``: ``moe_decode_assignments_held_total`` over
``moe_decode_assignments_total`` x the experts a token takes) over the experts
held: how near the cell's expert load is to the deployment's, where an expert
sees the rows of every chip that shares it."""

from harness import counts_hybrid_moe, counts_sala


def read(run, name):
    cfg = run["cell"]["config"]
    if not counts_hybrid_moe.is_mixture(cfg):
        return None
    return counts_hybrid_moe.expert_tokens_per_step(run.get("engine_stats") or {}, counts_sala.decoding_rows(run),
                                                    cfg["num_experts_per_tok"])
