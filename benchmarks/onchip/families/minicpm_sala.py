"""Family ``minicpm_sala`` (``model_type`` of MiniCPM-SALA's published
``config.json``): the file's published keys become the program's ``ModelConfig``.

The recipe: RMSNorm, a stack whose ``mixer_types`` name each layer ``minicpm4``
(block-sparse attention: grouped-query, no rotation, per-head q/k norm, a
sigmoid output gate; the sizes of its indexer under ``sparse_config``) or
``lightning-attn`` (linear attention with a fixed per-head decay, rotation on q
and k, per-head q/k norm, an output norm and an output gate), a dense SwiGLU
MLP after either, an untied head, and the muP scalars ``scale_emb`` (embeddings),
``scale_depth / sqrt(depth)`` (every residual branch, the PUBLISHED depth) and
``hidden_size / dim_model_base`` (logits). The program runs it as its ``llama``
architecture with a layer pattern. A lightning layer's decay depends on where
the model has the layer, so a file that keeps some of the layers names their
published indices (``kept_layers``) and the published depth
(``published.num_hidden_layers``). What the recipe cannot represent is refused,
not dropped.
"""

from __future__ import annotations

import os

KINDS = {"minicpm4": "sparse_attention", "lightning-attn": "lightning"}


def model_config(config: dict, name: str):
    from tpu_engine.models import transformer as tfm
    from tpu_engine.ops import sparse_block_attention

    if os.environ.get("ONCHIP_REHEARSAL") == "1":
        # The harness's CPU rehearsal: the decode kernel is interpreted there,
        # and the program wants to be told (on any other device it refuses).
        sparse_block_attention.INTERPRET_OFF_TPU = True
    kinds = config["mixer_types"]
    if len(kinds) != config["num_hidden_layers"] or set(kinds) - set(KINDS):
        raise ValueError(f"mixer_types has {len(kinds)} entries for num_hidden_layers="
                         f"{config['num_hidden_layers']}, or names a kind other than {sorted(KINDS)}")
    depth = config.get("published", {}).get("num_hidden_layers", config["num_hidden_layers"])
    kept = config.get("kept_layers", list(range(config["num_hidden_layers"])))
    if len(kept) != len(kinds) or not all(0 <= i < depth for i in kept):
        raise ValueError(f"kept_layers must name the published index (under {depth}) of each of the "
                         f"{len(kinds)} layers kept, got {kept}")
    if config.get("tie_word_embeddings"):
        raise ValueError("a tied head is not this family's recipe")
    if config.get("attention_bias"):
        raise ValueError("attention_bias: projection biases are not this family's recipe")
    if config.get("hidden_act") != "silu":
        raise ValueError(f"hidden_act={config.get('hidden_act')!r}: only silu is this family's recipe")
    if not config.get("qk_norm") or config.get("attn_use_rope") or not config.get("lightning_use_rope"):
        raise ValueError("the recipe norms q and k per head in both kinds, rotates them in the lightning "
                         "layers and not in the sparse ones (qk_norm, attn_use_rope, lightning_use_rope)")
    if not (config.get("use_output_gate") and config.get("use_output_norm") and config.get("attn_use_output_gate")):
        raise ValueError("the recipe gates both kinds' outputs and norms the lightning layers' "
                         "(use_output_gate, use_output_norm, attn_use_output_gate)")
    if config["lightning_nkv"] != config["lightning_nh"]:
        raise ValueError(f"lightning_nkv={config['lightning_nkv']}: a lightning layer has as many key "
                         f"and value heads as query heads ({config['lightning_nh']})")
    if config.get("lightning_scale") != "1/sqrt(d)":
        raise ValueError(f"lightning_scale={config.get('lightning_scale')!r}: only 1/sqrt(d)")
    if config["mup_denominator"] != depth:
        raise ValueError(f"mup_denominator={config['mup_denominator']} is not the published depth {depth}")
    sparse = config["sparse_config"]
    if sparse["window_size"] % sparse["block_size"]:
        raise ValueError("sparse_config: window_size must be whole blocks")
    return tfm.ModelConfig(
        name=name,
        arch="llama",
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim_override=config["head_dim"],
        d_ff=config["intermediate_size"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        layer_types=tuple(KINDS[k] for k in kinds),
        layer_indices=tuple(kept),
        published_layers=depth,
        lightning_heads=config["lightning_nh"],
        lightning_head_dim=config["lightning_head_dim"],
        sparse_kernel_size=sparse["kernel_size"],
        sparse_kernel_stride=sparse["kernel_stride"],
        sparse_block_size=sparse["block_size"],
        sparse_topk=sparse["topk"],
        sparse_init_blocks=sparse["init_blocks"],
        sparse_local_blocks=sparse["window_size"] // sparse["block_size"],
        sparse_dense_len=sparse["dense_len"],
        embed_scale=float(config["scale_emb"]),
        residual_scale=float(config["scale_depth"]) / depth ** 0.5,
        logits_divisor=config["hidden_size"] / config["dim_model_base"],
    )
