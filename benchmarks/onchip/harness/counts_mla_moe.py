"""Operations and bytes of a DeepSeek-V3-recipe stack (latent attention, MLA,
before a mixture of experts with shared experts; leading dense layers), from
shapes and from the program's own counters: what the readers of the ``mla_*``
and ``expert_*`` rooflines and ``decode_step_hbm_roofline`` divide by a peak.
Each is the LEAST the mathematics needs, whatever implements it: a latent row
once per layer-step for the live rows at their real lengths, an expert's
weights once per layer-step in which some token chose it, two FLOPs a
multiply-add over the causal triangle and nothing past it. A program that reads
every lane of every slot, or the latent twice, or computes masked lanes, reads
under 100 % by that much.

``cfg`` is a configuration file's dict (Hugging Face keys: ``kv_lora_rank`` C,
``qk_nope_head_dim`` N, ``qk_rope_head_dim`` R, ``v_head_dim`` V, H heads;
``moe_intermediate_size`` one expert's width, ``n_routed_experts`` the experts
HELD, ``published.n_routed_experts`` the router's width, ``n_shared_experts``,
``first_k_dense_replace`` leading dense layers of ``intermediate_size``). The
mixture's counters and ratios are ``counts_hybrid_moe``'s (they know no
family); only its ``knows`` asks for granite's keys.
"""

from __future__ import annotations

from .counts_hybrid import decode_chunk_step_s
from .counts_hybrid_moe import (expert_tokens_per_step, held_assignments_per_token,  # noqa: F401
                                per_layer_step)
from .counts_sala import decoding_context


def is_mla_moe(cfg: dict) -> bool:
    return "kv_lora_rank" in cfg and "n_routed_experts" in cfg.get("published", {})


knows = is_mla_moe


def _dims(cfg: dict) -> dict:
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dict(D=cfg["hidden_size"], H=cfg["num_attention_heads"], C=cfg["kv_lora_rank"],
                N=cfg["qk_nope_head_dim"], R=cfg["qk_rope_head_dim"], V=cfg["v_head_dim"],
                L=cfg["num_hidden_layers"], dense=dense, mix=cfg["num_hidden_layers"] - dense)


def n_layers(cfg: dict) -> int:
    return _dims(cfg)["L"]


def n_mixture_layers(cfg: dict) -> int:
    """Layers whose block is the mixture: all but the leading dense ones."""
    return _dims(cfg)["mix"]


def latent_row_bytes(cfg: dict, itemsize: int = 2) -> int:
    """What one token keeps in one layer: the latent and the shared rotated key."""
    d = _dims(cfg)
    return itemsize * (d["C"] + d["R"])


def expansion_weights(cfg: dict) -> int:
    """W_kvb: the latent to every head's unrotated key and value."""
    d = _dims(cfg)
    return d["C"] * d["H"] * (d["N"] + d["V"])


def mla_weights(cfg: dict) -> int:
    """One layer's mixer: W_q, W_kva, W_kvb, W_o."""
    d = _dims(cfg)
    return d["D"] * d["H"] * (d["N"] + d["R"]) + d["D"] * (d["C"] + d["R"]) + expansion_weights(cfg) \
        + d["H"] * d["V"] * d["D"]


def expert_weights(cfg: dict) -> int:
    """Parameters of one routed expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_bytes(cfg: dict, itemsize: int = 2) -> float:
    return float(itemsize * expert_weights(cfg))


def assignment_flops(cfg: dict) -> float:
    """One token through one expert: 2 FLOPs a multiply-add over its weights."""
    return 2.0 * expert_weights(cfg)


def mixture_fixed_weights(cfg: dict) -> int:
    """What one mixture layer reads whatever the routing: the router over all
    the published experts (and its bias) and the shared experts."""
    D, E = cfg["hidden_size"], cfg["published"]["n_routed_experts"]
    return D * E + E + 3 * D * cfg["moe_intermediate_size"] * cfg["n_shared_experts"]


# -- decode -------------------------------------------------------------------------


def absorbed_decode_bytes(cfg: dict, context_tokens: float, itemsize: int = 2) -> float:
    """One layer's absorbed decode step (scopes ``mla_absorb`` + ``mla_attend``):
    the latent rows of the live slots at their real lengths (``context_tokens``:
    their sum), once, and W_kvb, which absorbs the queries and brings the
    latent output back to the heads' values."""
    return context_tokens * latent_row_bytes(cfg, itemsize) + itemsize * expansion_weights(cfg)


def decode_step_bytes(cfg: dict, context_tokens: float, experts_hit: float, itemsize: int = 2) -> float:
    """One decode step of the whole stack: every layer's mixer weights and the
    live rows' latent, the leading dense layers' SwiGLU, per mixture layer the
    router, the shared experts and the ``experts_hit`` held experts some row
    chose, the untied head (the table's lookup reads a row a slot and is left
    out)."""
    d = _dims(cfg)
    weights = (d["L"] * mla_weights(cfg) + d["dense"] * 3 * d["D"] * cfg["intermediate_size"]
               + d["mix"] * mixture_fixed_weights(cfg) + d["D"] * cfg["vocab_size"])
    return (itemsize * weights + d["mix"] * experts_hit * expert_bytes(cfg, itemsize)
            + d["L"] * context_tokens * latent_row_bytes(cfg, itemsize))


def decode_step(run: dict) -> tuple[float, float] | None:
    """(bytes one decode step must move, traced seconds of one step) of a
    traced serving run, for ``decode_step_hbm_roofline``: ``decode_step_bytes``
    for the rows that decode at their contexts (``counts_sala.decoding_context``)
    with the held experts some row chose a layer-step (the engine's counters)."""
    hit = per_layer_step(run.get("engine_stats") or {}, "decode", "experts_hit")
    context, step_s = decoding_context(run), decode_chunk_step_s(run)
    if not step_s or not hit or not context:
        return None
    return decode_step_bytes(run["cell"]["config"], context, hit), step_s


# -- a prefill chunk ----------------------------------------------------------------


def mla_chunk_flops(cfg: dict, first: int, tokens: int) -> float:
    """One layer's expanded attention over ``tokens`` queries from position
    ``first`` of one row (scopes ``mla_expand`` + ``mla_attend``): the lanes the
    chunk can see (``first + tokens``) through W_kvb once, then per query and
    head the scores (N + R wide) and the weighted values (V wide) over the
    lanes up to itself."""
    d = _dims(cfg)
    visible = first + tokens
    pairs = tokens * first + tokens * (tokens + 1) / 2.0          # sum over queries of lanes seen
    return 2.0 * visible * expansion_weights(cfg) + 2.0 * pairs * d["H"] * (d["N"] + d["R"] + d["V"])


def chunk_flops(cfg: dict, first: int, tokens: int, held_pairs_per_token: float) -> float:
    """A whole prefill chunk of one row, every layer: the mixers' projections,
    the expansion and the attention, the leading dense SwiGLU, the router, the
    shared experts and the routed pairs that fell on held experts."""
    d = _dims(cfg)
    proj = 2.0 * tokens * (mla_weights(cfg) - expansion_weights(cfg))
    mixture = 2.0 * tokens * mixture_fixed_weights(cfg) + tokens * held_pairs_per_token * assignment_flops(cfg)
    return (d["L"] * (proj + mla_chunk_flops(cfg, first, tokens))
            + d["dense"] * 2.0 * tokens * 3 * d["D"] * cfg["intermediate_size"] + d["mix"] * mixture)
