"""Family ``granitemoehybrid`` (``model_type`` of granite-4.0-h-micro's published
``config.json``): the file's published keys become the program's ``ModelConfig``.

The recipe: RMSNorm, a stack whose ``layer_types`` name each layer ``mamba``
(a Mamba-2 mixer) or ``attention`` (grouped-query, no positional encoding,
scores scaled by ``attention_multiplier``), a dense SwiGLU MLP after either, a
tied head, and the multipliers on embeddings, residual branches and logits.
The program runs it as its ``llama`` architecture with a layer pattern. What
the recipe cannot represent is refused, not dropped: an untied head, a
positional encoding, more than one B/C group, biases, a pattern that does not
cover the depth (``fields``), and, of the block after the mixer, experts and a
shared expert of another width (``model_config``: those are
granite-4.0-h-small's).

``fields(config, name)`` is everything but the block after the mixer, for a
second recipe under this ``model_type`` to build on, as ``mixtral.py`` builds
on ``mistral.fields``: such a family's configuration file states it under
``family`` (``manifest.family_of``), and what its MLP is, is its own to map
and to refuse.
"""

from __future__ import annotations


def fields(config: dict, name: str) -> dict:
    """The ``ModelConfig`` fields of the recipe's mixers, pattern, embedding,
    head and multipliers. ``d_ff`` is ``intermediate_size`` whatever the MLP
    is (an expert's width in a mixture, as Mixtral's ``d_ff`` is);
    ``shared_intermediate_size``, ``num_local_experts`` and
    ``num_experts_per_tok`` are not read: they are the caller's."""
    if not config.get("tie_word_embeddings"):
        raise ValueError("an untied head is not this family's recipe")
    if config.get("position_embedding_type") != "nope":
        raise ValueError(f"position_embedding_type={config.get('position_embedding_type')!r}: "
                         "only 'nope' (no positional encoding) is this family's recipe")
    if config["mamba_n_groups"] != 1:
        raise ValueError(f"mamba_n_groups={config['mamba_n_groups']}: one B/C group is this family's recipe")
    kinds = tuple(config["layer_types"])
    if len(kinds) != config["num_hidden_layers"] or set(kinds) - {"mamba", "attention"}:
        raise ValueError(f"layer_types has {len(kinds)} entries for num_hidden_layers="
                         f"{config['num_hidden_layers']}, or names a kind other than mamba / attention")
    if config.get("attention_bias") or config.get("mamba_proj_bias") or not config.get("mamba_conv_bias", True):
        raise ValueError("projection biases, or a convolution without its bias, are not this family's recipe")
    if config.get("normalization_function", "rmsnorm") != "rmsnorm" or config.get("hidden_act") != "silu":
        raise ValueError("only rmsnorm and silu are this family's recipe")
    hidden, heads = config["hidden_size"], config["mamba_n_heads"]
    if heads * config["mamba_d_head"] != config["mamba_expand"] * hidden:
        raise ValueError("mamba_n_heads x mamba_d_head must be mamba_expand x hidden_size")
    return dict(
        name=name,
        arch="llama",
        vocab_size=config["vocab_size"],
        d_model=hidden,
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        max_seq_len=config["max_position_embeddings"],
        norm_eps=float(config["rms_norm_eps"]),
        layer_types=kinds,
        ssm_heads=heads,
        ssm_head_dim=config["mamba_d_head"],
        ssm_state=config["mamba_d_state"],
        ssm_groups=config["mamba_n_groups"],
        ssm_conv=config["mamba_d_conv"],
        ssm_chunk=config["mamba_chunk_size"],
        embed_scale=float(config["embedding_multiplier"]),
        residual_scale=float(config["residual_multiplier"]),
        logits_divisor=float(config["logits_scaling"]),
        attn_scale=float(config["attention_multiplier"]),
        rope=False,
        tie_head=True,
    )


def model_config(config: dict, name: str):
    from tpu_engine.models import transformer as tfm

    if config.get("num_local_experts"):
        raise ValueError(f"num_local_experts={config['num_local_experts']}: experts are not this recipe "
                         "(granite-4.0-h-small has them)")
    if config.get("shared_intermediate_size", config["intermediate_size"]) != config["intermediate_size"]:
        raise ValueError("shared_intermediate_size differs from intermediate_size: one dense MLP is the recipe")
    return tfm.ModelConfig(**fields(config, name))
