"""Family ``granitemoehybrid_moe``: the second recipe under the ``model_type``
``granitemoehybrid`` (granite-4.0-h-small's published ``config.json``), which
a configuration file states under ``family``. The mixers, the pattern, the
head and the multipliers are ``granitemoehybrid.fields``; the block after
every mixer is a mixture of ``published.num_local_experts`` SwiGLU experts of
width ``intermediate_size``, of which a router takes ``num_experts_per_tok`` a
token (softmax over the kept logits), plus one shared SwiGLU expert of width
``shared_intermediate_size`` that every token takes.

A file that is ONE CHIP'S SHARE of a deployment whose chips share each layer's
experts keeps ``num_local_experts`` of them (the key is then in its
``reduced``), from index ``first_local_expert`` on (a key of the benchmark's,
0 when absent), and states the published count under ``published``: the
router keeps the published width, and what the absent experts would have
added is left out. What the recipe cannot represent is refused, not dropped.
"""

from __future__ import annotations

from . import granitemoehybrid


def held_experts(config: dict) -> tuple[int, int, int]:
    """(the router's published width, the first expert held, how many)."""
    published = config.get("published", {}).get("num_local_experts")
    if not published:
        raise ValueError("published.num_local_experts is missing: the router's width, whatever share "
                         "of the experts num_local_experts keeps")
    held, first = config.get("num_local_experts") or 0, config.get("first_local_expert", 0)
    if held < 1 or published % held:
        raise ValueError(f"num_local_experts={held} is no whole share of the published {published} experts")
    if first % held or not 0 <= first <= published - held:
        raise ValueError(f"first_local_expert={first} does not start a share of {held} of the "
                         f"published {published} experts")
    return published, first, held


def model_config(config: dict, name: str):
    from tpu_engine.models import transformer as tfm

    published, first, held = held_experts(config)
    top_k = config.get("num_experts_per_tok") or 0
    if not 1 <= top_k <= published:
        raise ValueError(f"num_experts_per_tok={top_k} of the published {published} experts")
    shared = config.get("shared_intermediate_size") or 0
    return tfm.ModelConfig(**granitemoehybrid.fields(config, name), n_experts=published, top_k=top_k,
                           experts_first=first, experts_held=held if held < published else 0,
                           shared_d_ff=shared)
