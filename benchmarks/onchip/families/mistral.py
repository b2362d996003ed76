"""Family ``mistral`` (``model_type`` of Mistral-7B's published ``config.json``):
the file's published keys become the program's ``ModelConfig``.

The recipe: RMSNorm, rotary embeddings, grouped-query attention with an
optional sliding window, SwiGLU, an untied head. The program runs it as its
``llama`` architecture with ``sliding_window`` set. What the recipe cannot
represent is refused, not dropped: a tied head, scaled rotary frequencies,
experts (those are ``mixtral.py``'s).
"""

from __future__ import annotations


def fields(config: dict, name: str) -> dict:
    """The ``ModelConfig`` fields of the recipe's attention, embedding and head,
    which ``mixtral.py`` shares."""
    if config.get("tie_word_embeddings"):
        raise ValueError("a tied head is not this family's recipe")
    if config.get("rope_scaling"):
        raise ValueError(f"rope_scaling={config['rope_scaling']!r} is not this family's recipe")
    heads = config["num_attention_heads"]
    head_dim = config.get("head_dim") or config["hidden_size"] // heads
    return dict(
        name=name,
        arch="llama",
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=heads,
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        sliding_window=int(config.get("sliding_window") or 0),
        head_dim_override=0 if head_dim * heads == config["hidden_size"] else head_dim,
    )


def model_config(config: dict, name: str):
    from tpu_engine.models import transformer as tfm

    if config.get("num_local_experts"):
        raise ValueError("experts are family mixtral's, not mistral's")
    return tfm.ModelConfig(**fields(config, name))
