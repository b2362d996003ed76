"""Model step: tokens a held expert sees in one decode mixture layer-step of
the window, on average — the rows that decode x the assignments a token makes
on held experts (``moe_decode_assignments_held_total`` over
``moe_decode_assignments_total`` x the experts a token takes) over the experts
held: how near the cell's expert load is to the deployment's, where an expert
sees the rows of all four chips that share it (12 a step there).
``expert_tokens_per_step.py`` asks for granite's keys."""

from harness import counts_mla_moe, counts_sala


def read(run, name):
    cfg = run["cell"]["config"]
    if not counts_mla_moe.is_mla_moe(cfg):
        return None
    return counts_mla_moe.expert_tokens_per_step(run.get("engine_stats") or {}, counts_sala.decoding_rows(run),
                                                 cfg["num_experts_per_tok"])
