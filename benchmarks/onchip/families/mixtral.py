"""Family ``mixtral`` (``model_type`` of Mixtral-8x7B's published
``config.json``): the ``mistral`` recipe with the feed-forward replaced by
``num_local_experts`` SwiGLU experts of which a router takes
``num_experts_per_tok`` per token."""

from __future__ import annotations

from . import mistral


def model_config(config: dict, name: str):
    from tpu_engine.models import transformer as tfm

    return tfm.ModelConfig(**mistral.fields(config, name),
                           n_experts=int(config["num_local_experts"]),
                           top_k=int(config["num_experts_per_tok"]))
