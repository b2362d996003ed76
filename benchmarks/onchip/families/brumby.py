"""Family ``brumby`` (``model_type`` of Brumby-14B-Base's published
``config.json``): the file's published keys become the program's ``ModelConfig``.

The recipe: the dense decoder the model was retrained from (RMSNorm, grouped
query and key-value heads with per-head q/k norm and full rotation, a gated SiLU
MLP, an untied head, no bias), with EVERY layer's attention replaced by a gated
power-retention layer of degree 2. The published config has no key for the
retention layer's settings; the file states each beside the published keys
(``power_degree``, ``power_tile``, ``power_norm_eps``, ``gate_half_life_tokens``,
and ``layer_types``, one ``power_retention`` a layer) and argues it under
``assumed``; the program builds exactly those values (they are constants of
``transformer``, not fields) and a file that states another is refused. The
program runs it as its ``llama`` architecture with a layer pattern of
``power_retention`` alone: a stack that keeps no lane. What the recipe cannot
represent is refused, not dropped.
"""

from __future__ import annotations


def model_config(config: dict, name: str):
    from tpu_engine.models import transformer as tfm

    if list(config.get("layer_types", ())) != ["power_retention"] * config["num_hidden_layers"]:
        raise ValueError("layer_types must state the pattern: one 'power_retention' for each of the "
                         f"num_hidden_layers={config['num_hidden_layers']} layers")
    if config.get("sliding_window") or config.get("use_sliding_window"):
        raise ValueError("a sliding window is not this family's recipe: a retention layer has no lanes to window")
    if config.get("attention_bias"):
        raise ValueError("attention_bias: projection biases are not this family's recipe (the gate's alone)")
    if config.get("tie_word_embeddings"):
        raise ValueError("a tied head is not this family's recipe")
    if config.get("rope_scaling"):
        raise ValueError(f"rope_scaling={config['rope_scaling']!r}: the recipe rotates at rope_theta, unscaled")
    if config.get("hidden_act") != "silu":
        raise ValueError(f"hidden_act={config.get('hidden_act')!r}: only silu is this family's recipe")
    built = {"power_degree": 2, "power_tile": tfm.POWER_TILE, "power_norm_eps": tfm.POWER_NORM_EPS,
             "gate_half_life_tokens": list(tfm.POWER_HALF_LIFE)}
    for key, value in built.items():
        if config.get(key) != value:
            raise ValueError(f"{key}={config.get(key)!r}: the program builds {value!r} and nothing else")
    mc = tfm.ModelConfig(
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
        layer_types=("power_retention",) * config["num_hidden_layers"],
    )
    tfm.check_hybrid(mc)  # what the program cannot build is refused here, not at the first cache
    return mc
