"""Operations and bytes of a hybrid stack whose block after every mixer is a
mixture of experts with a shared expert (granite-4.0-h-small's recipe), from
shapes and from the program's own counters: what the readers of the
``expert_*`` rooflines and ``decode_step_hbm_roofline`` divide by a peak. Each
is the least the mathematics needs, whatever implements it: an expert's weights
once per layer-step in which some token chose it, six FLOPs per weight of an
expert per token it was chosen by. The mixers, the state and the keys and
values are ``counts_hybrid``'s.

``cfg`` is a configuration file's dict (Hugging Face keys; ``intermediate_size``
is one expert's width, ``shared_intermediate_size`` the shared expert's,
``num_local_experts`` the experts HELD, ``published.num_local_experts`` the
router's width). ``stats`` is ``ContinuousBatcher.stats()`` at the window's
end: its ``moe_<program>_*_total`` counters run over the engine's life, so only
their ratios are used, the traced window's layer-steps come from the trace.
"""

from __future__ import annotations

import statistics

from . import counts_hybrid


def is_mixture(cfg: dict) -> bool:
    return bool(cfg.get("num_local_experts")) and "mamba_n_heads" in cfg and \
        "num_local_experts" in cfg.get("published", {})


knows = is_mixture


def n_layers(cfg: dict) -> int:
    return len(cfg["layer_types"])


n_mixture_layers = n_layers  # every layer of the period has the mixture after its mixer


def expert_weights(cfg: dict) -> int:
    """Parameters of one expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_bytes(cfg: dict, itemsize: int = 2) -> float:
    return float(itemsize * expert_weights(cfg))


def assignment_flops(cfg: dict) -> float:
    """One token through one expert: 2 FLOPs a multiply-add over its weights."""
    return 2.0 * expert_weights(cfg)


def per_layer_step(stats: dict, program: str, counter: str) -> float | None:
    """A mixture counter of ``program`` (``decode`` | ``prefill``) per
    layer-step of that program; None where the program counts none."""
    steps = stats.get(f"moe_{program}_layer_steps_total")
    value = stats.get(f"moe_{program}_{counter}_total")
    return value / steps if steps and value is not None else None


def held_assignments_per_token(stats: dict, program: str, top_k: int) -> float | None:
    """Of a real token's ``top_k`` assignments in one layer, those on held experts."""
    made, held = stats.get(f"moe_{program}_assignments_total"), stats.get(f"moe_{program}_assignments_held_total")
    return top_k * held / made if made and held is not None else None


def expert_tokens_per_step(stats: dict, decoding_rows: float | None, top_k: int) -> float | None:
    """Tokens a held expert sees in one decode layer-step of the WINDOW, on
    average: the rows that decode there (``counts_sala.decoding_rows``: the
    window's emitted tokens over its dispatches and the chunk's steps) x the
    assignments a token makes on held experts (the counters' ratio, steady
    over the engine's life, where their sums hold the fill's half-empty
    steps) over the experts held."""
    held = held_assignments_per_token(stats, "decode", top_k)
    if held is None or not decoding_rows or not stats.get("held_experts"):
        return None
    return decoding_rows * held / stats["held_experts"]


def mixture_fixed_bytes(cfg: dict, itemsize: int = 2) -> float:
    """What one layer's mixture reads whatever the routing: the router over
    all the published experts and the shared expert."""
    D = cfg["hidden_size"]
    return float(itemsize * (D * cfg["published"]["num_local_experts"] + 3 * D * cfg["shared_intermediate_size"]))


def mixer_and_head_bytes(cfg: dict, itemsize: int = 2) -> float:
    """Both kinds of mixer and the tied head once: ``counts_hybrid``'s weights
    less the dense MLP it counts after every mixer."""
    dense = n_layers(cfg) * 3 * cfg["hidden_size"] * cfg["intermediate_size"]
    return counts_hybrid.weight_bytes_per_decode_step(cfg, itemsize) - itemsize * dense


def decode_step_bytes(cfg: dict, slots: int, context_tokens: float, experts_hit: float) -> float:
    """One decode step of the whole stack: mixers and head once, per layer the
    router, the shared expert and the ``experts_hit`` experts some row chose,
    keys and values at the live rows' real lengths (``context_tokens``: their
    sum), the recurrent state of every slot in and out."""
    return (mixer_and_head_bytes(cfg)
            + n_layers(cfg) * (mixture_fixed_bytes(cfg) + experts_hit * expert_bytes(cfg))
            + counts_hybrid.kv_bytes_per_decode_step(cfg, context_tokens)
            + counts_hybrid.recurrent_bytes_per_decode_step(cfg, slots))


def decode_step(run: dict) -> tuple[float, float] | None:
    """(bytes one decode step must move, traced seconds of one step) of a
    traced serving run, for ``decode_step_hbm_roofline``: ``decode_step_bytes``
    at the slots' real lengths with the experts some row chose a layer-step
    (the engine's counters); ``counts_hybrid``'s count holds one dense MLP a
    layer and would under-read here."""
    hit = per_layer_step(run.get("engine_stats") or {}, "decode", "experts_hit")
    step_s = counts_hybrid.decode_chunk_step_s(run)
    if not step_s or not hit or not run.get("dispatch_context"):
        return None
    context = statistics.fmean(run["dispatch_context"])
    return decode_step_bytes(run["cell"]["config"], run["slots"], context, hit), step_s
