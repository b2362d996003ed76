"""Family ``phi4flash`` (``model_type`` of Phi-4-mini-flash-reasoning's published
``config.json``): the file's published keys become the program's ``ModelConfig``.

The recipe (the decoder-hybrid-decoder "SambaY" stack with differential
attention): LayerNorm with bias, a tied head, no positional encoding, a dense
SwiGLU MLP without bias after every mixer, attention projections with biases,
and a pattern the published modelling code derives from ``num_hidden_layers``
(L, a multiple of 4) and ``mb_per_layer`` (2): layer i is ``mamba`` (a Mamba-1
mixer) for even i <= L/2 and ``gmu`` (a gated memory unit reading layer L/2's
scan output) for even i beyond; ``sliding_attention`` (differential attention
under ``sliding_window``) for odd i < L/2, ``full_attention`` at L/2 + 1 (the
ONE cache) and ``cross_attention`` (queries only, reading that cache) for odd i
beyond. The file states ``layer_types`` and this module checks it against the
rule. What the published config does not carry (the Mamba sizes) the file
states under ``assumed_sizes``. The program runs it as its ``llama``
architecture with a layer pattern. What the recipe cannot represent is
refused, not dropped.
"""

from __future__ import annotations

KINDS = {"mamba": "mamba1", "sliding_attention": "diff_window_attention", "full_attention": "diff_attention",
         "cross_attention": "diff_cross_attention", "gmu": "gmu"}


def derived_layer_types(layers: int, mb_per_layer: int) -> list[str]:
    """The published modelling code's rule."""
    if mb_per_layer != 2:
        raise ValueError(f"mb_per_layer={mb_per_layer}: the recipe has a Mamba mixer on every second layer (2)")
    if layers < 8 or layers % 4:
        raise ValueError(f"num_hidden_layers={layers}: the two decoders halve the depth and each alternates "
                         "two kinds of layer, so the depth is a multiple of 4 (at least 8)")
    half = layers // 2
    out = []
    for i in range(layers):
        if i % 2 == 0:
            out.append("mamba" if i <= half else "gmu")
        else:
            out.append("sliding_attention" if i < half else "full_attention" if i == half + 1 else "cross_attention")
    return out


def model_config(config: dict, name: str):
    from tpu_engine.models import transformer as tfm

    want = derived_layer_types(config["num_hidden_layers"], config["mb_per_layer"])
    if list(config.get("layer_types", ())) != want:
        raise ValueError(f"layer_types must state the pattern the recipe derives from num_hidden_layers="
                         f"{config['num_hidden_layers']}: {want}")
    if not config.get("tie_word_embeddings"):
        raise ValueError("an untied head is not this family's recipe")
    if config.get("mlp_bias") or config.get("lm_head_bias"):
        raise ValueError("mlp_bias / lm_head_bias: only the attention projections carry biases in this recipe")
    if config.get("hidden_act") != "silu":
        raise ValueError(f"hidden_act={config.get('hidden_act')!r}: only silu is this family's recipe")
    if config.get("embd_pdrop") or config.get("resid_pdrop"):
        raise ValueError("dropout is not served")
    if not config.get("sliding_window"):
        raise ValueError("the recipe's window layers need sliding_window")
    hidden, heads, sizes = config["hidden_size"], config["num_attention_heads"], config["assumed_sizes"]
    if hidden % heads:
        raise ValueError(f"hidden_size={hidden} is not a whole number of its {heads} heads")
    mc = tfm.ModelConfig(
        name=name,
        arch="llama",
        vocab_size=config["vocab_size"],
        d_model=hidden,
        n_layers=config["num_hidden_layers"],
        n_heads=heads,
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        max_seq_len=config["max_position_embeddings"],
        norm_eps=float(config["layer_norm_eps"]),
        sliding_window=config["sliding_window"],
        layer_types=tuple(KINDS[k] for k in want),
        mamba1_inner=sizes["mamba_expand"] * hidden,
        mamba1_state=sizes["mamba_d_state"],
        mamba1_dt_rank=sizes["mamba_dt_rank"],
        ssm_conv=sizes["mamba_d_conv"],
        layer_norm=True,
        attn_bias=True,
        rope=False,
        tie_head=True,
    )
    tfm.check_hybrid(mc)  # what the program cannot pair or place is refused here, not at the first cache
    return mc
