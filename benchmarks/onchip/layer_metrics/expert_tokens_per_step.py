"""Model step: tokens a held expert sees in one decode mixture layer-step of
the window, on average — the rows that decode (the window's emitted tokens over
its dispatches and the chunk's steps: not the slots held, of which some ingest
or await their prompt) x the assignments a token makes on held experts (from
``ContinuousBatcher.stats()``: ``moe_decode_assignments_held_total`` over
``moe_decode_assignments_total`` x the experts a token takes) over the experts
held: how near the cell's expert load is to the deployment's, where an expert
sees the rows of every chip that shares it (8.9 a step for batch32's two, 12
for longctx32's four). A configuration whose counts module
(``harness/counts_for.py``) has no mixture reads nothing."""

from harness import counts_sala
from harness.counts_for import mixture_counts_for


def read(run, name):
    cfg = run["cell"]["config"]
    family = mixture_counts_for(cfg)
    if not family:
        return None
    return family.expert_tokens_per_step(run.get("engine_stats") or {}, counts_sala.decoding_rows(run),
                                         cfg["num_experts_per_tok"])
