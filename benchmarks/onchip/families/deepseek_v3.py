"""Family ``deepseek_v3``: the DeepSeek-V3 recipe as Kimi-VL-A3B-Instruct's
published ``text_config`` states it (a configuration file states the family
under ``family``: the catalog's keys carry no ``model_type``). The file's
published keys become the program's ``ModelConfig``:

- latent attention (MLA) without a query latent (``q_lora_rank`` null): heads
  of ``qk_nope_head_dim`` + ``qk_rope_head_dim``, a latent of ``kv_lora_rank``
  that every head's keys (``qk_nope_head_dim``) and values (``v_head_dim``)
  are expanded from, one rotated key shared by all heads;
- the first ``first_k_dense_replace`` layers ("mla_dense") a dense SwiGLU of
  width ``intermediate_size``; every later one ("mla") a mixture of
  ``published.n_routed_experts`` SwiGLU experts of width
  ``moe_intermediate_size``, ``num_experts_per_tok`` a token, chosen by a
  sigmoid router with a selection bias (``topk_method: noaux_tc``,
  ``scoring_func: sigmoid``), gates renormalised (``norm_topk_prob``) and times
  ``routed_scaling_factor``, plus ``n_shared_experts`` shared experts run as
  one SwiGLU of their summed width;
- RMSNorm, an untied head, rotary embeddings without scaling.

A file that is ONE CHIP'S SHARE of a deployment whose chips share each layer's
experts keeps ``n_routed_experts`` of them (the key is then in its
``reduced``), from index ``first_local_expert`` on (a key of the benchmark's, 0
when absent), and states the published count under ``published``.
``router_bias_std`` is the benchmark's too: what the selection bias is drawn
from (a trained model's is learned). What the recipe cannot represent is
refused, not dropped.
"""

from __future__ import annotations


def held_experts(config: dict) -> tuple[int, int, int]:
    """(the router's published width, the first expert held, how many)."""
    published = config.get("published", {}).get("n_routed_experts")
    if not published:
        raise ValueError("published.n_routed_experts is missing: the router's width, whatever share "
                         "of the experts n_routed_experts keeps")
    held, first = config.get("n_routed_experts") or 0, config.get("first_local_expert", 0)
    if held < 1 or published % held:
        raise ValueError(f"n_routed_experts={held} is no whole share of the published {published} experts")
    if first % held or not 0 <= first <= published - held:
        raise ValueError(f"first_local_expert={first} does not start a share of {held} of the "
                         f"published {published} experts")
    return published, first, held


def model_config(config: dict, name: str):
    from tpu_engine.models import transformer as tfm

    if config.get("q_lora_rank"):
        raise ValueError(f"q_lora_rank={config['q_lora_rank']}: a query latent is not represented (q is one projection)")
    if config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
        raise ValueError(f"n_group={config.get('n_group')}, topk_group={config.get('topk_group')}: "
                         "group-limited routing is not represented (one group only)")
    if config.get("rope_scaling"):
        raise ValueError(f"rope_scaling={config['rope_scaling']!r} is not this family's recipe here")
    for key, want in (("topk_method", "noaux_tc"), ("scoring_func", "sigmoid"), ("norm_topk_prob", True),
                      ("moe_layer_freq", 1), ("hidden_act", "silu"), ("attention_bias", False),
                      ("tie_word_embeddings", False)):
        if config.get(key, want) != want:
            raise ValueError(f"{key}={config[key]!r}: only {want!r} is represented")
    heads = config["num_attention_heads"]
    if config.get("num_key_value_heads", heads) != heads:
        raise ValueError("latent attention expands keys and values for every head: num_key_value_heads "
                         f"({config['num_key_value_heads']}) must equal num_attention_heads ({heads})")
    published, first, held = held_experts(config)
    top_k = config.get("num_experts_per_tok") or 0
    if not 1 <= top_k <= published:
        raise ValueError(f"num_experts_per_tok={top_k} of the published {published} experts")
    layers = config["num_hidden_layers"]
    dense = min(config["first_k_dense_replace"], layers)
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    return tfm.ModelConfig(
        name=name, arch="llama", vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=layers, n_heads=heads, n_kv_heads=heads, head_dim_override=nope + rope,
        d_ff=config["moe_intermediate_size"], dense_d_ff=config["intermediate_size"],
        max_seq_len=config["max_position_embeddings"], rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        layer_types=("mla_dense",) * dense + ("mla",) * (layers - dense),
        published_layers=config.get("published", {}).get("num_hidden_layers", layers),
        kv_latent_dim=config["kv_lora_rank"], qk_nope_dim=nope, qk_rope_dim=rope,
        v_head_dim=config["v_head_dim"],
        n_experts=published, top_k=top_k, experts_first=first,
        experts_held=held if held < published else 0,
        shared_d_ff=config["moe_intermediate_size"] * config["n_shared_experts"],
        router_scoring="sigmoid", routed_scale=float(config["routed_scaling_factor"]),
        router_bias_std=float(config.get("router_bias_std", 0.0)))
