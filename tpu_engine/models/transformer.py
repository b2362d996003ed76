"""Decoder-only Llama-style transformer, TPU-first.

Pure-functional: parameters are a pytree of ``jnp`` arrays; the forward pass
is a plain function, jit/pjit-friendly (static shapes, ``lax.scan`` over
layers, no Python control flow on traced values). Every parameter carries
*logical axis names* (see ``tpu_engine/sharding.py``) so the same model runs
replicated, FSDP-sharded, tensor-parallel, or both, purely via sharding
annotations.

Design choices for the MXU/HBM (see SURVEY.md §7 and the task's TPU notes):

- all heavy math is einsum/matmul in bfloat16 (MXU-friendly), softmax and
  norms accumulate in float32;
- layers are **stacked** on a leading ``layers`` axis and iterated with
  ``lax.scan`` — one compiled block regardless of depth (fast compiles at
  70B scale);
- activation checkpointing is ``jax.checkpoint`` around the scanned block,
  policy-selectable (reference activation-checkpointing config:
  ``deepspeed_launcher.py:215-223``);
- attention is the Pallas flash-attention kernel (``tpu_engine/ops``) or
  plain XLA attention, chosen once by the caller (``build_train_program``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from tpu_engine import layer_state
from tpu_engine.quant import QuantWeight, dequantize_weight
from tpu_engine.quant_train import int8_einsum


# A pattern's names for its layers -> the kind each is stacked, scanned and
# cached under (``params["layers"][kind]``, ``layer_state.LAYER_KINDS[kind]``).
LAYER_TYPE_KINDS = {"attention": "attn", "mamba": "ssm", "lightning": "lightning",
                    "sparse_attention": "sparse_attn", "mla": "mla", "mla_dense": "mla_dense",
                    # a decoder-hybrid-decoder stack (Phi-4-mini-flash: SambaY with
                    # differential attention), see ``ModelConfig.layer_types``
                    "mamba1": "mamba1", "diff_window_attention": "window_attn",
                    "diff_attention": "full_attn", "diff_cross_attention": "cross_attn",
                    "gmu": "gmu",
                    # gated power retention (linear attention through the symmetric
                    # power of q and k): a whole state per kv-head and no lane
                    "power_retention": "power"}

# Longest period of unlike layers :meth:`ModelConfig.layer_periods` looks for.
_MAX_PERIOD = 4

# A power-retention state lays the key's symmetric square out in tiles: the
# head cut in tiles of this many values, one block of its square for each pair
# of tiles a <= b (256 products: two of the chip's 128-lane groups).
POWER_TILE = 16
# ... and adds this to the sum of a position's weights before it divides by it.
POWER_NORM_EPS = 1e-6


@dataclass(frozen=True)
class ModelConfig:
    name: str = "gpt-125m"
    # Architecture family:
    #   "llama" — RMSNorm, RoPE, SwiGLU, untied head (also Mistral via
    #             sliding_window + GQA);
    #   "gpt2"  — LayerNorm+bias, learned positions, GELU, biases, tied head;
    #   "gemma" — zero-centred RMSNorm (output = x·(1+w)), RoPE, GeGLU,
    #             sqrt(d_model)-scaled embeddings, tied head, decoupled
    #             head_dim (256), MQA/GQA;
    #   "qwen"  — Qwen3 family: the llama recipe plus per-head RMSNorm on
    #             q and k before RoPE (qk-norm — the bf16 attention-logit
    #             stabiliser), decoupled head_dim, untied head.
    arch: str = "llama"
    vocab_size: int = 32_000
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    n_kv_heads: int = 12
    d_ff: int = 2048
    max_seq_len: int = 2048
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    # Attention implementation: "xla" or "flash" (Pallas kernel).
    attention_impl: str = "xla"
    # Sliding-window (Mistral-style) attention: each query sees only the
    # trailing `sliding_window` keys. 0 = full causal. The flash kernel
    # skips out-of-window blocks entirely (O(S·W) cost); the XLA path masks.
    sliding_window: int = 0
    # Mixture-of-Experts (0 experts = dense MLP). Experts ride the "expert"
    # logical axis → "model" mesh axis (expert parallelism). Routing is
    # top-k with a fixed per-expert capacity (static shapes for XLA).
    n_experts: int = 0
    top_k: int = 2
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    # MoE dispatch implementation:
    # - "dense": Switch/MTF-style capacity-factor dense dispatch — all
    #   routing work is einsum on the MXU, tokens over capacity DROP,
    #   [B,S,E,C] dispatch/combine tensors cost ~O(S²) FLOPs at long
    #   seq (measured 33% tax at seq 2048; ragged WINS at seq 8192 —
    #   benchmarks/RESULTS.md §MoE, pre-ledger). The only
    #   choice under expert parallelism (GSPMD partitions einsums).
    # - "ragged": sort-by-expert + lax.ragged_dot grouped matmuls — no
    #   capacity, no drops, dispatch/combine become gathers/scatters.
    #   Single-shard experts only (ragged_dot is not GSPMD-partitionable
    #   over the expert dim; validated at build).
    # Training's alone: the cached walks that SERVE a mixture
    # (generate._moe_mlp_decode) read neither value; they run the held
    # experts masked or grouped by what the trace sees
    # (generate.experts_grouped_engages).
    moe_impl: str = "dense"
    # Which of the ``n_experts`` THIS tree holds, for a chip that is one of
    # several sharing each layer's experts: ``experts_held`` of them from
    # index ``experts_first`` on (0 held = all). The router keeps its width
    # ``n_experts`` and its ``top_k``; only the terms of held experts are
    # computed, and what the absent ones would have added is left out.
    # ``shared_d_ff``: the width of a shared expert, a SwiGLU every token
    # takes beside its routed ones (0 = none). Both are a hybrid stack's,
    # served only (:func:`check_hybrid`).
    experts_first: int = 0
    experts_held: int = 0
    shared_d_ff: int = 0
    # MXU int8 quantized training (tpu_engine/quant_train.py): "none" or
    # "int8". Routes the listed matmul groups through the channel-scaled
    # int8 einsum primitive — "attn" (Q/K/V/O projections), "mlp" (dense
    # MLP), "moe" (per-expert einsums). Router/dispatch/embed/unembed
    # always stay full precision. Resolved onto this config by
    # build_train_program from TPUTrainConfig (like attention_impl).
    quant_training: str = "none"
    quant_train_targets: tuple = ("attn", "mlp", "moe")

    # Per-head dim decoupled from d_model // n_heads (Gemma: 256). 0 = derived.
    head_dim_override: int = 0

    # Layer pattern: one entry per layer, a key of :data:`LAYER_TYPE_KINDS`:
    # "attention", "mamba" (a Mamba-2 mixer in the attention's place),
    # "lightning" (linear attention with a fixed per-head decay) or
    # "sparse_attention" (block-sparse attention that chooses the blocks it
    # reads); the block after the mixer (a dense MLP, or with ``n_experts`` a
    # mixture and its shared expert) follows every kind. Empty = every layer
    # attends. A pattern with any other entry than "attention" is a HYBRID
    # stack: parameters are stacked per kind, the stack is scanned by runs of
    # like layers (:meth:`layer_runs`), and it is served only (llama recipe,
    # no window; see :func:`check_hybrid`).
    #
    # A DECODER-HYBRID-DECODER stack holds five more: "mamba1" (a Mamba-1
    # selective scan, decay per channel and state; its scan output BEFORE the
    # gate is the memory later "gmu" layers read), "diff_window_attention"
    # (differential attention under ``sliding_window``), "diff_attention" (the
    # same with no window; the LAST one's keys and values are the one cache
    # that every later "diff_cross_attention" layer reads, with queries of its
    # own and no keys or values), and "gmu" (a gated memory unit: the memory
    # gated by a projection of the layer's input; nothing cached). Such a
    # stack's ``sliding_window`` is its window layers' alone.
    layer_types: tuple = ()
    # The PUBLISHED index of each kept layer and the published depth: a
    # lightning layer's decay depends on where the model has it, whatever
    # depth a configuration keeps. Empty = the layers are 0 .. n_layers - 1.
    layer_indices: tuple = ()
    published_layers: int = 0
    # Mamba-2 widths: heads x head size is the mixer's inner width; B and C
    # are ``ssm_state`` wide and shared by all heads (one group); the causal
    # depthwise convolution is ``ssm_conv`` taps over x|B|C; prefill runs the
    # chunked (SSD) form ``ssm_chunk`` tokens at a time (a lightning layer's
    # prefill too: it is the same scan with B and C per head).
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 256
    # Mamba-1 widths ("mamba1" layers): the mixer's inner width, its state per
    # channel, and the rank of the projection its step size comes through
    # (0 = ceil(d_model / 16)); the convolution has ``ssm_conv`` taps.
    mamba1_inner: int = 0
    mamba1_state: int = 16
    mamba1_dt_rank: int = 0
    # Lightning attention: ``lightning_heads`` heads of ``lightning_head_dim``
    # (keys and values have as many), rotation on q and k, per-head q/k norm,
    # a norm over the inner width and a sigmoid gate on the output; head h
    # (1-based) of the layer published at index l decays its state by
    # ``exp(-2^(-8h/H) (1 - l/(L-1) + 1e-5))`` a token.
    lightning_heads: int = 0
    lightning_head_dim: int = 0
    # Gated power retention ("power_retention" layers) has no field of its
    # own: ``n_heads`` query heads over ``n_kv_heads`` states, per-head q/k norm
    # and rotation as the qwen recipe; the score of a query and a key is ``(q .
    # k)^2 / head_dim`` (degree 2), decayed by a sigmoid gate per kv-head and
    # token and divided by the sum of the scores. The state holds the key's
    # symmetric square in tiles of :data:`POWER_TILE` (:meth:`power_tile_pairs`).
    # Block-sparse attention (``n_heads`` query heads over ``n_kv_heads``, no
    # rotation, per-head q/k norm, a sigmoid output gate). A query past
    # ``sparse_dense_len`` scores the compressed keys (the mean of
    # ``sparse_kernel_size`` keys every ``sparse_kernel_stride``), pools them
    # to blocks of ``sparse_block_size`` lanes and attends the ``sparse_topk``
    # best, the first ``sparse_init_blocks`` and the last
    # ``sparse_local_blocks`` (its own among them) always counted in; up to
    # ``sparse_dense_len`` it attends everything.
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_local_blocks: int = 32
    sparse_dense_len: int = 8192
    # Scalar multipliers, each absent at its default: embeddings x
    # ``embed_scale``, every mixer and MLP output x ``residual_scale`` before
    # it joins the residual stream, logits / ``logits_divisor``; attention
    # scores x ``attn_scale`` (0 = 1/sqrt(head_dim)).
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logits_divisor: float = 1.0
    attn_scale: float = 0.0
    # Latent attention (MLA; layer types "mla" and "mla_dense"): ``n_heads``
    # query heads of ``qk_nope_dim`` unrotated + ``qk_rope_dim`` rotated values
    # (``head_dim_override`` is their sum, which scales the scores); a token's
    # keys and values are ONE latent of ``kv_latent_dim`` (RMS-normed) beside
    # one rotated key of ``qk_rope_dim`` shared by all heads, which is all the
    # cache holds; ``kv_b`` expands the latent to every head's
    # ``qk_nope_dim`` key and ``v_head_dim`` value. An "mla" layer's block is
    # the stack's (the mixture, with ``n_experts``); an "mla_dense" layer's is
    # one dense SwiGLU of width ``dense_d_ff`` (the published recipe's leading
    # layers).
    kv_latent_dim: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    dense_d_ff: int = 0
    # The router's scores: "softmax" (over all experts, the kept renormalised
    # to 1) or "sigmoid" (per expert; the ``top_k`` are chosen by score PLUS
    # the per-expert ``router_bias`` leaf, the gates are the chosen scores
    # WITHOUT it, renormalised to 1 and times ``routed_scale``).
    router_scoring: str = "softmax"
    routed_scale: float = 1.0
    router_bias_std: float = 0.0  # what init_params draws the bias from (training moves it)
    # ``rope=False``: no positional rotation of q and k (position comes from
    # causality, or from the recurrent layers). ``tie_head``: the LM head is
    # the token embedding, outside the gpt2/gemma families too.
    rope: bool = True
    tie_head: bool = False
    # ``layer_norm``: every norm of a llama-recipe stack is a mean-subtracting
    # LayerNorm with a bias (gpt2's norm without gpt2's positions, GELU or MLP
    # biases). ``attn_bias``: the differential-attention kinds' projections
    # (q, k, v, o) carry biases.
    layer_norm: bool = False
    attn_bias: bool = False

    @property
    def head_dim(self) -> int:
        return self.head_dim_override or self.d_model // self.n_heads

    @property
    def tied_head(self) -> bool:
        return self.tie_head or self.arch in ("gpt2", "gemma")

    @property
    def is_hybrid(self) -> bool:
        return bool(set(self.layer_types) - {"attention"})

    def n_layers_of(self, layer_type: str) -> int:
        if not self.layer_types:
            return self.n_layers if layer_type == "attention" else 0
        return sum(t == layer_type for t in self.layer_types)

    @property
    def n_ssm_layers(self) -> int:
        return self.n_layers_of("mamba")

    @property
    def n_attn_layers(self) -> int:
        return self.n_layers_of("attention")

    @property
    def lightning_inner(self) -> int:
        return self.lightning_heads * self.lightning_head_dim

    @property
    def power_tile_pairs(self) -> tuple:
        """The pairs of tiles ``(a, b)``, a <= b, in the order a power-retention
        state lays their blocks of products out."""
        return layer_state.power_tile_pairs(self.head_dim, POWER_TILE)

    @property
    def power_state_width(self) -> int:
        """Coordinates of the key's symmetric square as the state holds it:
        ``POWER_TILE^2`` for each pair of tiles (128 in tiles of 16: 36 x 256 =
        9 216; the least layout is 8 256, the full outer product 16 384)."""
        return len(self.power_tile_pairs) * POWER_TILE ** 2

    def published_indices(self, layer_type: str) -> tuple:
        """The published index of each layer of ``layer_type``, in order."""
        idx = self.layer_indices or tuple(range(self.n_layers))
        return tuple(i for i, t in zip(idx, self.layer_types) if t == layer_type)

    @property
    def n_latent_layers(self) -> int:
        """Latent-attention (MLA) layers, whichever block follows them."""
        return self.n_layers_of("mla") + self.n_layers_of("mla_dense")

    @property
    def latent_width(self) -> int:
        """What a latent-attention layer caches per token: latent | rotated key."""
        return self.kv_latent_dim + self.qk_rope_dim

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the convolution runs over: x | B | C."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    def layer_runs(self) -> tuple:
        """The pattern as runs of like layers: ``(kind, first, count)`` with
        ``kind`` the per-kind stack (:data:`LAYER_TYPE_KINDS`) and ``first`` the run's
        first index WITHIN that stack. No pattern (every layer attends) is the
        one run ``("attn", 0, n_layers)``."""
        runs: list = []
        seen: dict = {}
        for t in self.layer_types or ("attention",) * self.n_layers:
            kind = LAYER_TYPE_KINDS[t]
            if runs and runs[-1][0] == kind:
                runs[-1][2] += 1
            else:
                runs.append([kind, seen.get(kind, 0), 1])
            seen[kind] = seen.get(kind, 0) + 1
        return tuple(tuple(r) for r in runs)

    def layer_periods(self) -> tuple:
        """The pattern as :meth:`layer_runs` has it, with a stretch that
        alternates (``a b a b ...``: up to :data:`_MAX_PERIOD` unlike layers,
        repeated at least twice) as ONE entry: ``(kinds, firsts, count)``,
        ``count`` repeats of a period whose j-th layer is of kind ``kinds[j]``
        and first stands at ``firsts[j]`` of that kind's stack. A run of like
        layers is a period of one kind; 8 x (mamba1, window) is one loop of
        eight, not sixteen runs of one."""
        kinds = [LAYER_TYPE_KINDS[t] for t in self.layer_types or ("attention",) * self.n_layers]
        out: list = []
        seen: dict = {}
        i = 0
        while i < len(kinds):
            run = 1
            while i + run < len(kinds) and kinds[i + run] == kinds[i]:
                run += 1
            p, count = 1, run
            if run == 1:
                for q in range(2, _MAX_PERIOD + 1):
                    unit = kinds[i:i + q]
                    if len(set(unit)) < q:
                        continue
                    r = 1
                    while kinds[i + r * q:i + (r + 1) * q] == unit:
                        r += 1
                    if r >= 2 and q * r > p * count:
                        p, count = q, r
            unit = tuple(kinds[i:i + p])
            out.append((unit, tuple(seen.get(k, 0) for k in unit), count))
            for k in unit:
                seen[k] = seen.get(k, 0) + count
            i += p * count
        return tuple(out)

    @property
    def cross_decoder_start(self) -> Optional[int]:
        """Index of the layer whose keys and values the cross-attention layers
        read (the last "diff_attention" before the first of them): from its
        attention on, a prompt needs the stack at its LAST position only.
        None for a stack without cross-attention layers."""
        if "diff_cross_attention" not in self.layer_types:
            return None
        first = self.layer_types.index("diff_cross_attention")
        return max(i for i, t in enumerate(self.layer_types[:first]) if t == "diff_attention")

    @property
    def mamba1_rank(self) -> int:
        return self.mamba1_dt_rank or -(-self.d_model // 16)

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def n_mixture_layers(self) -> int:
        """Layers whose block is the mixture: all but the "mla_dense" ones."""
        return (self.n_layers - self.n_layers_of("mla_dense")) if self.is_moe else 0

    @property
    def n_experts_held(self) -> int:
        return self.experts_held or self.n_experts

    def expert_capacity(self, seq_len: int) -> int:
        """Tokens each expert accepts per sequence (static)."""
        cap = int(self.capacity_factor * self.top_k * seq_len / self.n_experts)
        return max(cap, 1)

    def with_(self, **kw) -> "ModelConfig":
        return replace(self, **kw)


class RecurrentLayersUnsupported(NotImplementedError):
    """A feature that assumes every layer's per-request state is keys and
    values was asked of a model with recurrent (Mamba-2, Mamba-1, lightning or
    power-retention) layers.
    Raised by name, never worked around: a recurrent state has no lanes to
    slice, mask or rewind."""

    def __init__(self, feature: str, cfg: "ModelConfig"):
        self.feature = feature
        whole: dict = {}
        for kind, _, count in cfg.layer_runs():
            if layer_state.keeps_whole_state([kind]):
                whole[kind] = whole.get(kind, 0) + count
        super().__init__(
            f"{feature} does not support model {cfg.name!r}: "
            f"{sum(whole.values())} of its {cfg.n_layers} layers are recurrent "
            f"({', '.join(whole)}), and their per-request state is not keys "
            "and values"
        )


def refuse_recurrent(cfg: "ModelConfig", feature: str) -> None:
    """Raise :class:`RecurrentLayersUnsupported` when some kind of layer in
    ``cfg``'s stack keeps a WHOLE state (``layer_state.LAYER_KINDS``: no lanes
    to slice, mask or rewind). An object without a layer pattern is a geometry
    stand-in, never such a stack."""
    runs = cfg.layer_runs() if hasattr(cfg, "layer_runs") else ()
    if layer_state.keeps_whole_state(kind for kind, _, _ in runs):
        raise RecurrentLayersUnsupported(feature, cfg)


class LatentCacheUnsupported(NotImplementedError):
    """A feature whose wire or rewind carries keys and values only was asked
    of a model whose attention layers cache a latent (MLA). Raised by name:
    the latent has a lane per position, and neither per-head keys nor values."""

    def __init__(self, feature: str, cfg: "ModelConfig"):
        self.feature = feature
        super().__init__(
            f"{feature} does not support model {cfg.name!r}: its latent-attention (MLA) "
            f"layers cache one latent of {cfg.latent_width} values a token, not keys and values")


def refuse_latent(cfg: "ModelConfig", feature: str) -> None:
    """Raise :class:`LatentCacheUnsupported` when ``cfg``'s stack has
    latent-attention layers (a geometry stand-in without a pattern has none)."""
    if getattr(cfg, "n_latent_layers", 0):
        raise LatentCacheUnsupported(feature, cfg)


def refuse_beyond_kv(cfg: "ModelConfig", feature: str) -> None:
    """For a feature whose wire or rewind carries keys and values and nothing
    else: :func:`refuse_recurrent`, then :func:`refuse_latent`."""
    refuse_recurrent(cfg, feature)
    refuse_latent(cfg, feature)


def refuse_hybrid_mixture(cfg: "ModelConfig", feature: str) -> None:
    """A mixture of experts after a hybrid stack's mixers is served only,
    whether or not its mixers keep a whole state (:func:`refuse_recurrent`
    speaks first where they do)."""
    if cfg.is_hybrid and cfg.is_moe:
        raise NotImplementedError(
            f"{feature} does not support model {cfg.name!r}: a mixture of experts after "
            "a hybrid stack's mixers is served only")


def refuse_recurrent_model(model_name: str, feature: str) -> None:
    """:func:`refuse_beyond_kv` (every caller's wire carries keys and values
    only) for a registered model's name (fleet-level
    planes know a spec's ``model_name``, not its config); an unknown name
    passes, as it does everywhere a fleet degrades to capacity-only."""
    cfg = MODEL_CONFIGS.get(model_name)
    if cfg is not None:
        refuse_beyond_kv(cfg, feature)


def check_hybrid(cfg: "ModelConfig") -> None:
    """What a hybrid pattern can be today, checked where parameters or a
    cache are built (trace time, free)."""
    if cfg.experts_first or cfg.experts_held or cfg.shared_d_ff \
            or cfg.router_scoring != "softmax" or cfg.routed_scale != 1.0:
        if not (cfg.is_hybrid and cfg.is_moe):
            raise ValueError(
                "a share of the experts (experts_first, experts_held), a shared expert "
                "(shared_d_ff) and a sigmoid router (router_scoring, routed_scale) are a "
                "hybrid mixture's: they need layer_types and n_experts "
                f"(n_experts={cfg.n_experts}, layer_types={cfg.layer_types!r})")
        if cfg.experts_first < 0 or cfg.experts_first + cfg.n_experts_held > cfg.n_experts:
            raise ValueError(
                f"experts {cfg.experts_first}..{cfg.experts_first + cfg.n_experts_held - 1} "
                f"are not among the router's n_experts={cfg.n_experts}")
    if not cfg.layer_types:
        return
    if len(cfg.layer_types) != cfg.n_layers or \
            set(cfg.layer_types) - set(LAYER_TYPE_KINDS):
        raise ValueError(
            f"layer_types must hold n_layers={cfg.n_layers} entries of "
            f"{sorted(LAYER_TYPE_KINDS)}, got {cfg.layer_types!r}"
        )
    if not cfg.is_hybrid:
        if cfg.layer_norm or cfg.attn_bias:
            raise ValueError("layer_norm and attn_bias are a hybrid stack's (the gpt2 recipe has its own)")
        return
    windowed = "diff_window_attention" in cfg.layer_types
    if cfg.arch != "llama" or bool(cfg.sliding_window) != windowed \
            or (windowed and "attention" in cfg.layer_types):
        raise ValueError(
            "a hybrid layer pattern needs the llama recipe, and a sliding window exactly where it "
            "has 'diff_window_attention' layers (whose window it is) and no plain 'attention' "
            f"layer (arch={cfg.arch!r}, sliding_window={cfg.sliding_window})"
        )
    _check_decoder_hybrid_decoder(cfg)
    if cfg.is_moe and not 1 <= cfg.top_k <= cfg.n_experts:
        raise ValueError(f"top_k={cfg.top_k} experts a token of n_experts={cfg.n_experts}")
    if cfg.router_scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"router_scoring={cfg.router_scoring!r}: 'softmax' or 'sigmoid'")
    if cfg.n_latent_layers:
        dims = (cfg.kv_latent_dim, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim)
        if min(dims) < 1 or cfg.qk_rope_dim % 2 or cfg.head_dim != cfg.qk_nope_dim + cfg.qk_rope_dim:
            raise ValueError(
                "a latent-attention layer needs kv_latent_dim, qk_nope_dim, v_head_dim >= 1, an even "
                f"qk_rope_dim and head_dim = qk_nope_dim + qk_rope_dim (got {dims}, head_dim={cfg.head_dim})")
        if "mla_dense" in cfg.layer_types and cfg.dense_d_ff < 1:
            raise ValueError("an 'mla_dense' layer needs dense_d_ff, its dense SwiGLU's width")
    depth = cfg.published_layers or cfg.n_layers
    if cfg.layer_indices and (len(cfg.layer_indices) != cfg.n_layers
                              or not all(0 <= i < depth for i in cfg.layer_indices)):
        raise ValueError(f"layer_indices must hold the published index (under {depth}) of each "
                         f"of the n_layers={cfg.n_layers} layers, got {cfg.layer_indices!r}")
    if "mamba" in cfg.layer_types:
        if cfg.ssm_groups != 1:
            raise ValueError(f"ssm_groups={cfg.ssm_groups}: only one B/C group is supported")
        if min(cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state) < 1 or cfg.ssm_conv < 2:
            raise ValueError("a 'mamba' layer needs ssm_heads, ssm_head_dim, ssm_state >= 1 and ssm_conv >= 2")
    if "lightning" in cfg.layer_types:
        if cfg.lightning_heads < 1 or cfg.lightning_head_dim < 2 or cfg.lightning_head_dim % 2:
            raise ValueError("a 'lightning' layer needs lightning_heads >= 1 "
                             "and an even lightning_head_dim >= 2 (q and k are rotated)")
        if depth < 2:
            raise ValueError(f"a 'lightning' layer's decay needs a published depth >= 2, got {depth}")
    if "power_retention" in cfg.layer_types:
        if set(cfg.layer_types) != {"power_retention"} or cfg.is_moe:
            raise ValueError("'power_retention' layers stand in a stack of their own, with a dense MLP "
                             f"(layer_types={sorted(set(cfg.layer_types))}, n_experts={cfg.n_experts})")
        if cfg.head_dim % POWER_TILE or cfg.n_kv_heads < 1 or cfg.n_heads % cfg.n_kv_heads:
            raise ValueError(
                f"a 'power_retention' layer needs head_dim={cfg.head_dim} in whole tiles of {POWER_TILE} "
                f"and n_heads={cfg.n_heads} a multiple of n_kv_heads={cfg.n_kv_heads}")
    if "sparse_attention" in cfg.layer_types:
        size, stride, block = cfg.sparse_kernel_size, cfg.sparse_kernel_stride, cfg.sparse_block_size
        if stride < 1 or size % stride or block % stride or size > block:
            raise ValueError(
                f"sparse_kernel_size={size} and sparse_block_size={block} must be multiples of "
                f"sparse_kernel_stride={stride}, and a window no longer than a block")
        if cfg.sparse_init_blocks + cfg.sparse_local_blocks > cfg.sparse_topk \
                or cfg.sparse_dense_len < cfg.sparse_topk * block:
            raise ValueError(
                f"sparse_topk={cfg.sparse_topk} must hold the {cfg.sparse_init_blocks} first and "
                f"{cfg.sparse_local_blocks} last blocks, and sparse_dense_len={cfg.sparse_dense_len} "
                f"at least sparse_topk blocks of {block} (a position that selects sees that many)")


DIFF_ATTENTION_TYPES = ("diff_window_attention", "diff_attention", "diff_cross_attention")


def _check_decoder_hybrid_decoder(cfg: "ModelConfig") -> None:
    """What the five decoder-hybrid-decoder layer types need of a pattern and
    of the widths (:func:`check_hybrid`'s part for them)."""
    types = cfg.layer_types
    mine = set(types) & ({"mamba1", "gmu"} | set(DIFF_ATTENTION_TYPES))
    if not mine:
        if cfg.layer_norm or cfg.attn_bias:
            raise ValueError("layer_norm and attn_bias are a decoder-hybrid-decoder stack's "
                             "('mamba1', 'diff_*attention', 'gmu' layers)")
        return
    if cfg.is_moe:
        raise ValueError(f"a mixture of experts after {sorted(mine)} layers is not supported")
    if "mamba1" in types and (cfg.mamba1_inner < 1 or cfg.mamba1_state < 1 or cfg.ssm_conv < 2):
        raise ValueError("a 'mamba1' layer needs mamba1_inner, mamba1_state >= 1 and ssm_conv >= 2")
    if "gmu" in types and "mamba1" not in types[:types.index("gmu")]:
        raise ValueError("a 'gmu' layer reads the scan output of a 'mamba1' layer before it: the pattern has none")
    if mine & set(DIFF_ATTENTION_TYPES):
        pairs, kv_pairs = divmod(cfg.n_heads, 2), divmod(cfg.n_kv_heads, 2)
        if pairs[1] or kv_pairs[1] or kv_pairs[0] < 1 or pairs[0] % kv_pairs[0]:
            raise ValueError(
                f"differential attention pairs its heads: n_heads={cfg.n_heads} and n_kv_heads="
                f"{cfg.n_kv_heads} must be even, the query pairs a multiple of the kv pairs")
    if "diff_cross_attention" in types and "diff_attention" not in types[:types.index("diff_cross_attention")]:
        raise ValueError("a 'diff_cross_attention' layer reads the keys and values of a 'diff_attention' "
                         "layer before it: the pattern has none")


# Model scales matching the reference's preset names (7b/13b/70b at
# ``deepspeed_launcher.py:369-407``) plus small smoke/bench configs.
MODEL_CONFIGS: dict[str, ModelConfig] = {
    "gpt-tiny": ModelConfig(
        name="gpt-tiny", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=4, d_ff=128, max_seq_len=256,
    ),
    "qwen-tiny": ModelConfig(
        # Decoupled head_dim (32 != 64/4) exercises the Qwen3 layout.
        name="qwen-tiny", arch="qwen", vocab_size=512, d_model=64, n_layers=2,
        n_heads=4, n_kv_heads=2, head_dim_override=32, d_ff=128, max_seq_len=256,
        rope_theta=1_000_000.0,
    ),
    "qwen3-4b": ModelConfig(
        name="qwen3-4b", arch="qwen", vocab_size=151_936, d_model=2560,
        n_layers=36, n_heads=32, n_kv_heads=8, head_dim_override=128, d_ff=9728,
        max_seq_len=32_768, rope_theta=1_000_000.0, norm_eps=1e-6,
    ),
    "gpt-125m": ModelConfig(
        name="gpt-125m", vocab_size=32_000, d_model=768, n_layers=12, n_heads=12,
        n_kv_heads=12, d_ff=2048, max_seq_len=2048,
    ),
    "llama-1b": ModelConfig(
        name="llama-1b", vocab_size=32_000, d_model=2048, n_layers=16, n_heads=16,
        n_kv_heads=16, d_ff=5504, max_seq_len=4096,
    ),
    "llama-7b": ModelConfig(
        name="llama-7b", vocab_size=32_000, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=32, d_ff=11_008, max_seq_len=4096,
    ),
    "llama-13b": ModelConfig(
        name="llama-13b", vocab_size=32_000, d_model=5120, n_layers=40, n_heads=40,
        n_kv_heads=40, d_ff=13_824, max_seq_len=4096,
    ),
    "llama-70b": ModelConfig(
        name="llama-70b", vocab_size=32_000, d_model=8192, n_layers=80, n_heads=64,
        n_kv_heads=8, d_ff=28_672, max_seq_len=4096,
    ),
    # Sliding-window (Mistral) family: GQA + windowed attention.
    "mistral-7b": ModelConfig(
        name="mistral-7b", vocab_size=32_000, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, d_ff=14_336, max_seq_len=32_768, sliding_window=4096,
    ),
    # GPT-2 family: LayerNorm + learned positions + GELU + tied embeddings.
    "gpt2-tiny": ModelConfig(
        name="gpt2-tiny", arch="gpt2", vocab_size=512, d_model=64, n_layers=2,
        n_heads=4, n_kv_heads=4, d_ff=256, max_seq_len=256,
    ),
    "gpt2-124m": ModelConfig(
        name="gpt2-124m", arch="gpt2", vocab_size=50_257, d_model=768, n_layers=12,
        n_heads=12, n_kv_heads=12, d_ff=3072, max_seq_len=1024,
    ),
    "gpt2-xl": ModelConfig(
        name="gpt2-xl", arch="gpt2", vocab_size=50_257, d_model=1600, n_layers=48,
        n_heads=25, n_kv_heads=25, d_ff=6400, max_seq_len=1024,
    ),
    # Gemma family: zero-centred RMSNorm, GeGLU, scaled embeddings, tied
    # head, decoupled head_dim, MQA (2b) / MHA (7b).
    "gemma-tiny": ModelConfig(
        name="gemma-tiny", arch="gemma", vocab_size=512, d_model=64, n_layers=2,
        n_heads=4, n_kv_heads=1, d_ff=256, max_seq_len=256,
        head_dim_override=32, norm_eps=1e-6,
    ),
    "gemma-2b": ModelConfig(
        name="gemma-2b", arch="gemma", vocab_size=256_000, d_model=2048,
        n_layers=18, n_heads=8, n_kv_heads=1, d_ff=16_384, max_seq_len=8192,
        head_dim_override=256, norm_eps=1e-6,
    ),
    "gemma-7b": ModelConfig(
        name="gemma-7b", arch="gemma", vocab_size=256_000, d_model=3072,
        n_layers=28, n_heads=16, n_kv_heads=16, d_ff=24_576, max_seq_len=8192,
        head_dim_override=256, norm_eps=1e-6,
    ),
    # Mixture-of-Experts family (expert parallelism over the "model" axis).
    "moe-tiny": ModelConfig(
        name="moe-tiny", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=4, d_ff=128, max_seq_len=256, n_experts=4, top_k=2,
    ),
    "moe-8x7b": ModelConfig(  # Mixtral-8x7B shape
        name="moe-8x7b", vocab_size=32_000, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, d_ff=14_336, max_seq_len=4096, n_experts=8, top_k=2,
    ),
}


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames="dtype")
def _scaled(x, s, dtype):
    """``(x * s).astype(dtype)`` as one program: a leaf drawn narrower than
    float32 never stands as a second float32 array beside its draw (leaf by
    leaf, that pair was a serving replica's peak memory). The draw itself
    stays its own program, so the values are those of the ops written out."""
    return (x * s).astype(dtype)


def _drawn(deferred: bool, draw, *args, **kw):
    """A drawn kernel, or with ``deferred`` the call that draws it."""
    return partial(draw, *args, **kw) if deferred else draw(*args, **kw)


def draw_deferred(params: dict[str, Any]) -> dict[str, Any]:
    """A tree of :func:`init_params` (``deferred=True``) with every kernel
    still to be drawn now drawn."""
    return jax.tree.map(lambda a: a() if callable(a) else a, params)


def init_params(rng: jax.Array, cfg: ModelConfig, dtype=jnp.float32,
                deferred: bool = False) -> dict[str, Any]:
    """Initialise parameters (normal(0.02); residual-out projections scaled
    by 1/sqrt(2·n_layers), GPT-2 style).

    ``deferred``: every drawn kernel (and table) is left as a call that draws
    it, the programs and values those of the tree drawn at once; a caller that
    turns each leaf into something smaller as it is made (the int8 build:
    ``quant.quantize_params``, then :func:`draw_deferred` for what is left)
    never holds the float32 tree, which for a model that fills a chip in bf16
    does not fit."""
    check_hybrid(cfg)
    if cfg.is_hybrid:
        return _init_hybrid_params(rng, cfg, dtype, deferred)
    k_embed, k_q, k_k, k_v, k_o, k_gate, k_up, k_down, k_head = jax.random.split(rng, 9)
    L, D, V, F = cfg.n_layers, cfg.d_model, cfg.vocab_size, cfg.d_ff
    H, KV, HD = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    std = 0.02
    res_std = std / (2 * L) ** 0.5

    def norm(key, shape, s):
        return _drawn(deferred, lambda: _scaled(jax.random.normal(key, shape, jnp.float32), s, dtype))

    if cfg.arch == "gpt2":
        return {
            "embed": {"embedding": norm(k_embed, (V, D), std)},
            "pos_embed": {"embedding": norm(k_head, (cfg.max_seq_len, D), 0.01)},
            "layers": {
                "attn_norm": {"scale": jnp.ones((L, D), dtype),
                              "bias": jnp.zeros((L, D), dtype)},
                "q": {"kernel": norm(k_q, (L, D, H * HD), std),
                      "bias": jnp.zeros((L, H * HD), dtype)},
                "k": {"kernel": norm(k_k, (L, D, H * HD), std),
                      "bias": jnp.zeros((L, H * HD), dtype)},
                "v": {"kernel": norm(k_v, (L, D, H * HD), std),
                      "bias": jnp.zeros((L, H * HD), dtype)},
                "o": {"kernel": norm(k_o, (L, H * HD, D), res_std),
                      "bias": jnp.zeros((L, D), dtype)},
                "mlp_norm": {"scale": jnp.ones((L, D), dtype),
                             "bias": jnp.zeros((L, D), dtype)},
                "fc": {"kernel": norm(k_up, (L, D, F), std),
                       "bias": jnp.zeros((L, F), dtype)},
                "proj": {"kernel": norm(k_down, (L, F, D), res_std),
                         "bias": jnp.zeros((L, D), dtype)},
            },
            "final_norm": {"scale": jnp.ones((D,), dtype),
                           "bias": jnp.zeros((D,), dtype)},
            # LM head is tied to the token embedding (no separate weight).
        }

    # Gemma stores norm scales as offsets from 1 (zero init = identity) and
    # ties the LM head to the token embedding.
    gemma = cfg.arch == "gemma"
    norm_init = jnp.zeros if gemma else jnp.ones
    layers: dict[str, Any] = {
        "attn_norm": {"scale": norm_init((L, D), dtype)},
        "q": {"kernel": norm(k_q, (L, D, H * HD), std)},
        "k": {"kernel": norm(k_k, (L, D, KV * HD), std)},
        "v": {"kernel": norm(k_v, (L, D, KV * HD), std)},
        "o": {"kernel": norm(k_o, (L, H * HD, D), res_std)},
        "mlp_norm": {"scale": norm_init((L, D), dtype)},
    }
    if cfg.arch == "qwen":
        # Per-head q/k RMSNorm scales, applied before RoPE.
        layers["q_norm"] = {"scale": jnp.ones((L, HD), dtype)}
        layers["k_norm"] = {"scale": jnp.ones((L, HD), dtype)}
    if cfg.is_moe:
        E = cfg.n_experts
        k_router = jax.random.fold_in(k_gate, 1)
        layers["router"] = {"kernel": norm(k_router, (L, D, E), std)}
        layers["gate"] = {"kernel": norm(k_gate, (L, E, D, F), std)}
        layers["up"] = {"kernel": norm(k_up, (L, E, D, F), std)}
        layers["down"] = {"kernel": norm(k_down, (L, E, F, D), res_std)}
    else:
        layers["gate"] = {"kernel": norm(k_gate, (L, D, F), std)}
        layers["up"] = {"kernel": norm(k_up, (L, D, F), std)}
        layers["down"] = {"kernel": norm(k_down, (L, F, D), res_std)}

    out = {
        "embed": {"embedding": norm(k_embed, (V, D), std)},
        "layers": layers,
        "final_norm": {"scale": norm_init((D,), dtype)},
    }
    if not cfg.tied_head:
        out["lm_head"] = {"kernel": norm(k_head, (D, V), std)}
    return out


def _init_hybrid_params(rng: jax.Array, cfg: ModelConfig, dtype, deferred: bool = False) -> dict[str, Any]:
    """A hybrid stack's parameters, stacked PER KIND, for the kinds the
    pattern has: ``layers["attn"]`` holds the ``n_attn_layers`` attention
    layers (the llama leaves), ``layers["ssm"]`` the ``n_ssm_layers`` Mamba-2
    layers, ``layers["sparse_attn"]`` and ``layers["lightning"]`` the
    block-sparse and the lightning attention layers
    (:func:`_init_sparse_attn_stack`, :func:`_init_lightning_stack`), each
    with its own block after the mixer (:func:`_mixer_mlp_stack`), in the
    order the pattern meets them.

    Projection kernels as every family (normal(0.02), outputs / sqrt(2 L));
    the embedding is drawn ``embed_scale`` times smaller, so that the scaled
    ``x0`` has the 0.02 of the other families' (a tied table of 0.02 x 12
    would outvote every layer at the head). The recurrence takes Mamba-2's
    published ranges, so that a state really outlives a chunk: ``A_log =
    log U(1, 16)``, ``dt_bias = softplus^-1(dt)`` with ``dt`` log-uniform in
    [1e-3, 1e-1], ``D = 1``, convolution taps ``U(+-1/sqrt(taps))`` and zero
    bias. The small recurrence leaves stay float32 whatever ``dtype`` is.

    A dense hybrid's attention and Mamba-2 kernels are each one draw of the
    whole stack (keys 1-7 and 8-15 of ``split(rng, 16)``). With a mixture
    after the mixers every kernel's layer i comes from ``split(key, n)[i]``
    alone (:func:`_draw_layers`; the mixture's own keys are
    :func:`_mixer_mlp_stack`'s), so that a reader holds one layer at a time."""
    ks = jax.random.split(rng, 16)
    L, D, V = cfg.n_layers, cfg.d_model, cfg.vocab_size
    La, Ls = cfg.n_attn_layers, cfg.n_ssm_layers
    H, KV, HD = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    SH, I, C, K = cfg.ssm_heads, cfg.ssm_inner, cfg.ssm_conv_dim, cfg.ssm_conv
    std = 0.02
    res_std = std / (2 * L) ** 0.5

    def norm(key, shape, s):
        return _drawn(deferred, lambda: _scaled(jax.random.normal(key, shape, jnp.float32), s, dtype))

    def stack_draw(n):
        """``draw(key, s, shape=)`` of ``n`` stacked layers of a kernel."""
        if cfg.is_moe:
            return partial(_drawn, deferred, _draw_layers, n=n, dtype=dtype)
        return lambda key, s, shape: norm(key, (n, *shape), s)

    def attn():
        draw = stack_draw(La)
        return {
            "attn_norm": {"scale": jnp.ones((La, D), dtype)},
            "q": {"kernel": draw(ks[1], std, shape=(D, H * HD))},
            "k": {"kernel": draw(ks[2], std, shape=(D, KV * HD))},
            "v": {"kernel": draw(ks[3], std, shape=(D, KV * HD))},
            "o": {"kernel": draw(ks[4], res_std, shape=(H * HD, D))},
            **_mixer_mlp_stack(draw, ks[5:8], La, cfg, dtype, deferred),
        }

    def ssm():
        draw = stack_draw(Ls)
        dt = jnp.exp(jax.random.uniform(ks[11], (Ls, SH), jnp.float32,
                                        jnp.log(1e-3), jnp.log(1e-1)))
        bound = 1.0 / K ** 0.5
        return {
            "ssm_norm": {"scale": jnp.ones((Ls, D), dtype)},
            "in_proj": {"kernel": draw(ks[8], std, shape=(D, I + C + SH))},
            "conv": {"kernel": jax.random.uniform(ks[9], (Ls, K, C), jnp.float32,
                                                  -bound, bound).astype(dtype),
                     "bias": jnp.zeros((Ls, C), dtype)},
            "A_log": jnp.log(jax.random.uniform(ks[10], (Ls, SH), jnp.float32, 1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1
            "D": jnp.ones((Ls, SH), jnp.float32),
            "gate_norm": {"scale": jnp.ones((Ls, I), dtype)},
            "out_proj": {"kernel": draw(ks[12], res_std, shape=(I, D))},
            **_mixer_mlp_stack(draw, ks[13:16], Ls, cfg, dtype, deferred),
        }

    stacks = {"attn": attn, "ssm": ssm,
              "sparse_attn": partial(_init_sparse_attn_stack, rng, cfg, dtype, deferred),
              "lightning": partial(_init_lightning_stack, rng, cfg, dtype, deferred),
              "power": partial(_init_power_stack, rng, cfg, dtype, deferred),
              "mla": partial(_init_mla_stack, rng, cfg, dtype, deferred, "mla"),
              "mla_dense": partial(_init_mla_stack, rng, cfg, dtype, deferred, "mla_dense"),
              **{kind: partial(_init_dhd_stack, rng, cfg, dtype, deferred, kind) for kind in DHD_FOLD}}
    kinds = dict.fromkeys(kind for kind, _, _ in cfg.layer_runs())
    out = {
        "embed": {"embedding": norm(ks[0], (V, D), std / cfg.embed_scale)},
        "layers": {kind: stacks[kind]() for kind in kinds},
        "final_norm": _norm_leaves(cfg, (D,), dtype),
    }
    if not cfg.tied_head:
        out["lm_head"] = {"kernel": norm(jax.random.fold_in(ks[0], 1), (D, V), std)}
    return out


@partial(jax.jit, static_argnames=("n", "shape", "dtype"))
def _draw_layers(key, s, *, n: int, shape: tuple, dtype):
    """``n`` layers' worth of normal(0, s) kernels, stacked: layer i is drawn
    from ``split(key, n)[i]`` alone, so that a reader who wants one layer
    (the benchmark's reference, which holds one at a time) draws that layer
    and no other."""
    draw = lambda k: jax.random.normal(k, shape, jnp.float32)  # noqa: E731
    return (jax.vmap(draw)(jax.random.split(key, n)) * s).astype(dtype)


@partial(jax.jit, static_argnames=("n", "n_experts", "first", "held", "shape", "dtype"))
def _draw_experts(key, s, *, n: int, n_experts: int, first: int, held: int, shape: tuple, dtype):
    """``[n, held, *shape]``: the kernels of experts ``first .. first + held -
    1`` of ``n`` layers. Expert e of layer i is drawn from ``split(split(key,
    n)[i], n_experts)[e]`` alone: a share of the experts holds exactly the
    uncut model's, and an absent expert is never drawn. One layer at a time
    (``lax.map``), so that a narrower ``dtype`` never has more than one
    layer's float32 beside it."""
    draw = lambda k: jax.random.normal(k, shape, jnp.float32)  # noqa: E731

    def layer(k):
        return (jax.vmap(draw)(jax.random.split(k, n_experts)[first:first + held]) * s).astype(dtype)

    return lax.map(layer, jax.random.split(key, n))


def _mixer_mlp_stack(draw, keys, n: int, cfg: ModelConfig, dtype, deferred: bool = False,
                     depth: int = 0) -> dict:
    """What every kind of layer has after its mixer, ``n`` layers stacked
    (keys: gate, up, down; ``draw`` as its caller's): the norm and the SwiGLU
    MLP, or with ``cfg.n_experts`` the mixture — ``router`` ``[n, D, E]``
    over ALL the experts, the HELD experts' ``gate`` / ``up`` ``[n, held, D,
    F]`` and ``down`` ``[n, held, F, D]`` (:func:`_draw_experts`, from the
    same three keys), and where ``cfg.shared_d_ff`` the shared expert's
    ``shared_gate`` / ``shared_up`` / ``shared_down``. The router's key is
    ``fold_in(keys[0], 1)``, the shared expert's ``fold_in(keys[j], 2)``; a
    sigmoid router's ``router_bias`` ``[n, E]`` (float32, normal of
    ``router_bias_std``) comes from ``fold_in(keys[0], 3)``. Output
    projections are drawn / sqrt(2 ``depth``) (0: the layers kept)."""
    D, F = cfg.d_model, cfg.d_ff
    res_std = 0.02 / (2 * (depth or cfg.n_layers)) ** 0.5
    out = {"mlp_norm": {"scale": jnp.ones((n, D), dtype)}}
    if not cfg.is_moe:
        return {**out,
                "gate": {"kernel": draw(keys[0], 0.02, shape=(D, F))},
                "up": {"kernel": draw(keys[1], 0.02, shape=(D, F))},
                "down": {"kernel": draw(keys[2], res_std, shape=(F, D))}}
    experts = partial(_drawn, deferred, _draw_experts, n=n, n_experts=cfg.n_experts,
                      first=cfg.experts_first, held=cfg.n_experts_held, dtype=dtype)
    out.update({
        "router": {"kernel": draw(jax.random.fold_in(keys[0], 1), 0.02, shape=(D, cfg.n_experts))},
        "gate": {"kernel": experts(keys[0], 0.02, shape=(D, F))},
        "up": {"kernel": experts(keys[1], 0.02, shape=(D, F))},
        "down": {"kernel": experts(keys[2], res_std, shape=(F, D))},
    })
    if cfg.router_scoring == "sigmoid":
        out["router_bias"] = _drawn(deferred, _draw_layers, jax.random.fold_in(keys[0], 3),
                                    cfg.router_bias_std, n=n, shape=(cfg.n_experts,), dtype=jnp.float32)
    if cfg.shared_d_ff:
        S = cfg.shared_d_ff
        shared = [jax.random.fold_in(k, 2) for k in keys[:3]]
        out.update({
            "shared_gate": {"kernel": draw(shared[0], 0.02, shape=(D, S))},
            "shared_up": {"kernel": draw(shared[1], 0.02, shape=(D, S))},
            "shared_down": {"kernel": draw(shared[2], res_std, shape=(S, D))},
        })
    return out


def _init_sparse_attn_stack(rng, cfg: ModelConfig, dtype, deferred: bool = False) -> dict:
    """The block-sparse attention layers: the llama projections, per-head q/k
    norm scales and the output gate's projection. Keys: ``split(fold_in(rng,
    101), 8)`` in the order q, k, v, o_gate, o, gate, up, down; each leaf's
    layer i from its own ``split(key, n)[i]`` (:func:`_draw_layers`)."""
    n = cfg.n_layers_of("sparse_attention")
    D, H, KV, HD = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(jax.random.fold_in(rng, 101), 8)
    res_std = 0.02 / (2 * cfg.n_layers) ** 0.5
    draw = partial(_drawn, deferred, _draw_layers, n=n, dtype=dtype)
    return {
        "attn_norm": {"scale": jnp.ones((n, D), dtype)},
        "q": {"kernel": draw(ks[0], 0.02, shape=(D, H * HD))},
        "k": {"kernel": draw(ks[1], 0.02, shape=(D, KV * HD))},
        "v": {"kernel": draw(ks[2], 0.02, shape=(D, KV * HD))},
        "o_gate": {"kernel": draw(ks[3], 0.02, shape=(D, H * HD))},
        "o": {"kernel": draw(ks[4], res_std, shape=(H * HD, D))},
        "q_norm": {"scale": jnp.ones((n, HD), dtype)},
        "k_norm": {"scale": jnp.ones((n, HD), dtype)},
        **_mixer_mlp_stack(draw, ks[5:], n, cfg, dtype, deferred),
    }


def lightning_decay_rates(cfg: ModelConfig) -> jax.Array:
    """[L_lightning, H] float32: what head h (1-based) of each lightning layer
    multiplies its state's exponent by, ``2^(-8h/H) (1 - l/(L-1) + 1e-5)``
    with l the layer's PUBLISHED index and L the published depth; the state
    decays by ``exp(-rate)`` a token."""
    H = cfg.lightning_heads
    depth = cfg.published_layers or cfg.n_layers
    slopes = 2.0 ** (-8.0 * jnp.arange(1, H + 1, dtype=jnp.float32) / H)
    at = jnp.asarray(cfg.published_indices("lightning"), jnp.float32)
    return slopes[None, :] * (1.0 - at / (depth - 1) + 1e-5)[:, None]


def _init_lightning_stack(rng, cfg: ModelConfig, dtype, deferred: bool = False) -> dict:
    """The lightning-attention layers: q, k, v, the output gate and o, per-head
    q/k norm scales, the output norm over the inner width, and ``decay``, the
    per-head rates (:func:`lightning_decay_rates`: fixed, not learned, and
    float32 wherever the tree goes). Keys: ``split(fold_in(rng, 102), 8)`` in
    the order q, k, v, o_gate, o, gate, up, down, as the sparse kind's."""
    n = cfg.n_layers_of("lightning")
    D, I, HD = cfg.d_model, cfg.lightning_inner, cfg.lightning_head_dim
    ks = jax.random.split(jax.random.fold_in(rng, 102), 8)
    res_std = 0.02 / (2 * cfg.n_layers) ** 0.5
    draw = partial(_drawn, deferred, _draw_layers, n=n, dtype=dtype)
    return {
        "attn_norm": {"scale": jnp.ones((n, D), dtype)},
        "q": {"kernel": draw(ks[0], 0.02, shape=(D, I))},
        "k": {"kernel": draw(ks[1], 0.02, shape=(D, I))},
        "v": {"kernel": draw(ks[2], 0.02, shape=(D, I))},
        "o_gate": {"kernel": draw(ks[3], 0.02, shape=(D, I))},
        "o": {"kernel": draw(ks[4], res_std, shape=(I, D))},
        "q_norm": {"scale": jnp.ones((n, HD), dtype)},
        "k_norm": {"scale": jnp.ones((n, HD), dtype)},
        "out_norm": {"scale": jnp.ones((n, I), dtype)},
        "decay": lightning_decay_rates(cfg),
        **_mixer_mlp_stack(draw, ks[5:], n, cfg, dtype, deferred),
    }


# Half-lives a power-retention gate's bias is drawn for, in tokens.
POWER_HALF_LIFE = (64.0, 16384.0)


def _init_power_stack(rng, cfg: ModelConfig, dtype, deferred: bool = False) -> dict:
    """The power-retention layers: q over ``n_heads``, k and v over
    ``n_kv_heads``, o, per-head q/k norm scales, and the gate: ``g_proj``
    ``[D, KV]`` (normal(0.02) as the others: a standard deviation of about 1.4
    in the gate's logit) and ``g_bias`` ``[KV]`` float32 ``= logit(2^(-1/tau))``
    with the half-life ``tau`` of each head of each layer log-uniform over
    :data:`POWER_HALF_LIFE` tokens, so that a seeded state remembers as a
    trained one does. Keys: ``split(fold_in(rng, 110), 9)`` in the order q, k,
    v, g_proj, o, gate, up, down, g_bias; each leaf's layer i from its own
    ``split(key, n)[i]`` (:func:`_draw_layers`); outputs / sqrt(2 x layers
    kept)."""
    n = cfg.n_layers_of("power_retention")
    D, H, KV, HD = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(jax.random.fold_in(rng, 110), 9)
    res_std = 0.02 / (2 * cfg.n_layers) ** 0.5
    draw = partial(_drawn, deferred, _draw_layers, n=n, dtype=dtype)
    lo, hi = (math.log(t) for t in POWER_HALF_LIFE)
    tau = jnp.exp(jax.vmap(lambda k: jax.random.uniform(k, (KV,), jnp.float32, lo, hi))(
        jax.random.split(ks[8], n)))
    return {
        "attn_norm": {"scale": jnp.ones((n, D), dtype)},
        "q": {"kernel": draw(ks[0], 0.02, shape=(D, H * HD))},
        "k": {"kernel": draw(ks[1], 0.02, shape=(D, KV * HD))},
        "v": {"kernel": draw(ks[2], 0.02, shape=(D, KV * HD))},
        "g_proj": {"kernel": draw(ks[3], 0.02, shape=(D, KV))},
        "g_bias": -jnp.log(jnp.expm1(math.log(2.0) / tau)),        # logit(2^(-1/tau)), as 1 / (2^(1/tau) - 1)
        "o": {"kernel": draw(ks[4], res_std, shape=(H * HD, D))},
        "q_norm": {"scale": jnp.ones((n, HD), dtype)},
        "k_norm": {"scale": jnp.ones((n, HD), dtype)},
        **_mixer_mlp_stack(draw, ks[5:8], n, cfg, dtype, deferred),
    }


def _init_mla_stack(rng, cfg: ModelConfig, dtype, deferred: bool, layer_type: str) -> dict:
    """The latent-attention layers of ``layer_type`` ("mla": the stack's block
    after the mixer, a mixture where it has experts; "mla_dense": one dense
    SwiGLU of width ``dense_d_ff``): ``q`` ``[D, H (nope + rope)]``, ``kv_a``
    ``[D, latent + rope]`` (the latent and the one shared rotary key),
    ``kv_norm`` over the latent, ``kv_b`` ``[latent, H (nope + v)]`` (per head
    its key's unrotated part, then its value), ``o`` ``[H v, D]``. Keys:
    ``split(fold_in(rng, 103 | 104), 7)`` in the order q, kv_a, kv_b, o, gate,
    up, down; each leaf's layer i from its own ``split(key, n)[i]``
    (:func:`_draw_layers`); output projections / sqrt(2 x published depth)."""
    n = cfg.n_layers_of(layer_type)
    D, H, C, R = cfg.d_model, cfg.n_heads, cfg.kv_latent_dim, cfg.qk_rope_dim
    ks = jax.random.split(jax.random.fold_in(rng, 103 if layer_type == "mla" else 104), 7)
    depth = cfg.published_layers or cfg.n_layers
    res_std = 0.02 / (2 * depth) ** 0.5
    draw = partial(_drawn, deferred, _draw_layers, n=n, dtype=dtype)
    block = cfg if layer_type == "mla" else cfg.with_(n_experts=0, d_ff=cfg.dense_d_ff)
    return {
        "attn_norm": {"scale": jnp.ones((n, D), dtype)},
        "q": {"kernel": draw(ks[0], 0.02, shape=(D, H * (cfg.qk_nope_dim + R)))},
        "kv_a": {"kernel": draw(ks[1], 0.02, shape=(D, C + R))},
        "kv_norm": {"scale": jnp.ones((n, C), dtype)},
        "kv_b": {"kernel": draw(ks[2], 0.02, shape=(C, H * (cfg.qk_nope_dim + cfg.v_head_dim)))},
        "o": {"kernel": draw(ks[3], res_std, shape=(H * cfg.v_head_dim, D))},
        **_mixer_mlp_stack(draw, ks[4:], n, block, dtype, deferred, depth),
    }


# A decoder-hybrid-decoder kind's keys: ``split(fold_in(rng, DHD_FOLD[kind]), 16)``,
# a leaf's at :data:`DHD_KEYS`; layer i of a leaf from ``split(key, n)[i]`` alone.
DHD_FOLD = {"mamba1": 105, "window_attn": 106, "full_attn": 107, "cross_attn": 108, "gmu": 109}
DHD_KEYS = {"q": 0, "in_proj": 0, "k": 1, "conv": 1, "v": 2, "x_proj": 2, "o": 3, "dt_proj": 3,
            "lambdas": 4, "dt_bias": 4, "out_proj": 5, "gate": 6, "up": 7, "down": 8,
            "q_bias": 9, "k_bias": 10, "v_bias": 11, "o_bias": 12}
DHD_LAYER_TYPE = {kind: t for t, kind in LAYER_TYPE_KINDS.items() if kind in DHD_FOLD}


def _norm_leaves(cfg: ModelConfig, shape: tuple, dtype) -> dict:
    """A norm's leaves at init: unit scale, and with ``cfg.layer_norm`` a zero bias."""
    out = {"scale": jnp.ones(shape, dtype)}
    if cfg.layer_norm:
        out["bias"] = jnp.zeros(shape, dtype)
    return out


def diff_lambda_init(cfg: ModelConfig, layer_type: str) -> jax.Array:
    """[n] float32: ``0.8 - 0.6 exp(-0.3 l)`` for each layer of ``layer_type``,
    l its PUBLISHED index (differential attention's fixed part of lambda)."""
    at = jnp.asarray(cfg.published_indices(layer_type), jnp.float32)
    return 0.8 - 0.6 * jnp.exp(-0.3 * at)


def _init_dhd_stack(rng, cfg: ModelConfig, dtype, deferred: bool, kind: str) -> dict:
    """One kind's stack of a decoder-hybrid-decoder pattern (keys:
    :data:`DHD_FOLD`, :data:`DHD_KEYS`), then the block every layer has.

    - ``mamba1``: ``in_proj`` ``[D, 2 I]`` (x | z), the depthwise ``conv``
      (taps ``U(+-1/sqrt(taps))``, zero bias), ``x_proj`` ``[I, R + 2 N]``
      (step | B | C), ``dt_proj`` ``[R, I]`` (``U(+-R^-1/2)``) with ``dt_bias =
      softplus^-1(dt)``, ``dt`` log-uniform in [1e-3, 1e-1], ``A_log`` ``[N, I]``
      ``= log(1..N)`` for every channel (channels minor, as the state lies),
      ``D = 1``, ``out_proj`` ``[I, D]``: Mamba's published ranges;
    - ``window_attn`` / ``full_attn``: ``q`` / ``k`` / ``v`` / ``o`` with biases
      (``cfg.attn_bias``; normal(0.02), so that a dropped bias shows), the four
      lambda vectors ``lambdas`` ``[4, HD]`` (q1, k1, q2, k2; normal(0.1)),
      ``lambda_init`` (:func:`diff_lambda_init`) and the sub-norm's scale
      ``[2 HD]``; ``cross_attn`` the same without k and v;
    - ``gmu``: ``in_proj`` ``[D, I]`` and ``out_proj`` ``[I, D]``.

    Projections normal(0.02), outputs (``o``, ``out_proj``, ``down``) / sqrt(2 x
    depth). The recurrence's small leaves, the lambdas and ``lambda_init`` stay
    float32 wherever the tree goes (:data:`FLOAT32_LEAVES`)."""
    layer_type = DHD_LAYER_TYPE[kind]
    n = cfg.n_layers_of(layer_type)
    D, H, KV, HD = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    I, N, R, K = cfg.mamba1_inner, cfg.mamba1_state, cfg.mamba1_rank, cfg.ssm_conv
    ks = jax.random.split(jax.random.fold_in(rng, DHD_FOLD[kind]), 16)
    key = lambda name: ks[DHD_KEYS[name]]  # noqa: E731
    res_std = 0.02 / (2 * (cfg.published_layers or cfg.n_layers)) ** 0.5
    draw = partial(_drawn, deferred, _draw_layers, n=n, dtype=dtype)
    f32 = jnp.float32

    def uniform(name, shape, lo, hi):
        return jax.vmap(lambda k: jax.random.uniform(k, shape, f32, lo, hi))(jax.random.split(key(name), n))

    def proj(name, shape, s=0.02, bias=False):
        out = {"kernel": draw(key(name), s, shape=shape)}
        if bias:
            out["bias"] = _draw_layers(key(name + "_bias"), 0.02, n=n, shape=shape[-1:], dtype=dtype)
        return out

    out = {"mixer_norm": _norm_leaves(cfg, (n, D), dtype)}
    if kind == "mamba1":
        dt = jnp.exp(uniform("dt_bias", (I,), jnp.log(1e-3), jnp.log(1e-1)))
        out.update({
            "in_proj": proj("in_proj", (D, 2 * I)),
            "conv": {"kernel": uniform("conv", (K, I), -1.0 / K ** 0.5, 1.0 / K ** 0.5).astype(dtype),
                     "bias": jnp.zeros((n, I), dtype)},
            "x_proj": proj("x_proj", (I, R + 2 * N)),
            "dt_proj": {"kernel": uniform("dt_proj", (R, I), -1.0 / R ** 0.5, 1.0 / R ** 0.5).astype(dtype)},
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1
            "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=f32))[None, :, None], (n, N, I)),
            "D": jnp.ones((n, I), f32),
            "out_proj": proj("out_proj", (I, D), res_std),
        })
    elif kind == "gmu":
        out.update({"in_proj": proj("in_proj", (D, I)), "out_proj": proj("out_proj", (I, D), res_std)})
    else:
        out["q"] = proj("q", (D, H * HD), bias=cfg.attn_bias)
        if kind != "cross_attn":
            out["k"] = proj("k", (D, KV * HD), bias=cfg.attn_bias)
            out["v"] = proj("v", (D, KV * HD), bias=cfg.attn_bias)
        out.update({
            "o": proj("o", (H * HD, D), res_std, bias=cfg.attn_bias),
            "lambdas": _draw_layers(key("lambdas"), 0.1, n=n, shape=(4, HD), dtype=f32),
            "lambda_init": diff_lambda_init(cfg, layer_type),
            "sub_norm": {"scale": jnp.ones((n, 2 * HD), dtype)},
        })
    mlp = _mixer_mlp_stack(draw, [key("gate"), key("up"), key("down")], n, cfg, dtype, deferred,
                           cfg.published_layers or cfg.n_layers)
    mlp["mlp_norm"] = _norm_leaves(cfg, (n, D), dtype)
    return {**out, **mlp}


# Recurrence leaves of a Mamba-2 layer, and with a lightning layer's per-head
# rates all that stays float32 wherever the rest of the tree goes to a compute
# dtype (:func:`served_format`, :func:`cast_layer_stack`): a bf16 ``A_log``
# moves every decay, and a sigmoid router's selection bias is added to
# float32 scores.
SSM_FLOAT32_LEAVES = ("A_log", "dt_bias", "D")
FLOAT32_LEAVES = SSM_FLOAT32_LEAVES + ("decay", "router_bias", "lambdas", "lambda_init", "g_bias")


def _mlp_axes(cfg: ModelConfig) -> dict[str, Any]:
    """Logical axes of the block after the mixer (:func:`_mixer_mlp_stack`'s
    leaves, and the uniform llama stack's): the norm and a dense MLP, or a
    mixture's router, experts and shared expert."""
    dense = {"gate": {"kernel": ("layers", "embed", "mlp")},
             "up": {"kernel": ("layers", "embed", "mlp")},
             "down": {"kernel": ("layers", "mlp", "embed")}}
    out: dict[str, Any] = {"mlp_norm": {"scale": ("layers", "embed")}}
    if not cfg.is_moe:
        return {**out, **dense}
    out.update({
        "router": {"kernel": ("layers", "embed", None)},
        "gate": {"kernel": ("layers", "expert", "embed", "mlp")},
        "up": {"kernel": ("layers", "expert", "embed", "mlp")},
        "down": {"kernel": ("layers", "expert", "mlp", "embed")},
    })
    if cfg.router_scoring == "sigmoid":
        out["router_bias"] = ("layers", None)
    if cfg.shared_d_ff:
        out.update({"shared_" + name: axes for name, axes in dense.items()})
    return out


def logical_axes(cfg: ModelConfig) -> dict[str, Any]:
    """Logical-axis tree matching :func:`init_params`' structure exactly."""
    if cfg.is_hybrid:
        mlp_axes = _mlp_axes(cfg)
        mixer_axes = {
            "attn_norm": {"scale": ("layers", "embed")},
            "q": {"kernel": ("layers", "embed", "heads")},
            "o_gate": {"kernel": ("layers", "embed", "heads")},
            "o": {"kernel": ("layers", "heads", "embed")},
            "q_norm": {"scale": ("layers", None)},
            "k_norm": {"scale": ("layers", None)},
            **mlp_axes,
        }
        mla_axes = {
            "attn_norm": {"scale": ("layers", "embed")},
            "q": {"kernel": ("layers", "embed", "heads")},
            # the latent is one for all heads: its projection and norm stay whole
            "kv_a": {"kernel": ("layers", "embed", None)},
            "kv_norm": {"scale": ("layers", None)},
            "kv_b": {"kernel": ("layers", None, "heads")},
            "o": {"kernel": ("layers", "heads", "embed")},
        }
        stacks = {
            "mla": {**mla_axes, **mlp_axes},
            "mla_dense": {**mla_axes, **_mlp_axes(cfg.with_(n_experts=0))},
            "sparse_attn": {
                "k": {"kernel": ("layers", "embed", "kv_heads")},
                "v": {"kernel": ("layers", "embed", "kv_heads")},
                **mixer_axes,
            },
            "lightning": {
                "k": {"kernel": ("layers", "embed", "heads")},
                "v": {"kernel": ("layers", "embed", "heads")},
                "out_norm": {"scale": ("layers", None)},
                "decay": ("layers", None),
                **mixer_axes,
            },
            "attn": {
                "attn_norm": {"scale": ("layers", "embed")},
                "q": {"kernel": ("layers", "embed", "heads")},
                "k": {"kernel": ("layers", "embed", "kv_heads")},
                "v": {"kernel": ("layers", "embed", "kv_heads")},
                "o": {"kernel": ("layers", "heads", "embed")},
                **mlp_axes,
            },
            "power": {
                "k": {"kernel": ("layers", "embed", "kv_heads")},
                "v": {"kernel": ("layers", "embed", "kv_heads")},
                "g_proj": {"kernel": ("layers", "embed", None)},
                "g_bias": ("layers", None),
                **{name: axes for name, axes in mixer_axes.items() if name != "o_gate"},
            },
            # The mixer's fused projection (z | x | B | C | dt) has no
            # head-aligned split to shard: its width stays whole.
            "ssm": {
                "ssm_norm": {"scale": ("layers", "embed")},
                "in_proj": {"kernel": ("layers", "embed", None)},
                "conv": {"kernel": ("layers", None, None),
                         "bias": ("layers", None)},
                "A_log": ("layers", None),
                "dt_bias": ("layers", None),
                "D": ("layers", None),
                "gate_norm": {"scale": ("layers", None)},
                "out_proj": {"kernel": ("layers", None, "embed")},
                **mlp_axes,
            },
        }
        norm_axes = {"scale": ("layers", "embed"), **({"bias": ("layers", "embed")} if cfg.layer_norm else {})}
        bias = lambda axis: {"bias": ("layers", axis)} if cfg.attn_bias else {}  # noqa: E731
        dhd = {"mixer_norm": norm_axes, **mlp_axes, "mlp_norm": norm_axes}
        diff_axes = {
            "q": {"kernel": ("layers", "embed", "heads"), **bias("heads")},
            "o": {"kernel": ("layers", "heads", "embed"), **bias("embed")},
            "lambdas": ("layers", None, None),
            "lambda_init": ("layers",),
            "sub_norm": {"scale": ("layers", None)},
            **dhd,
        }
        kv_axes = {"k": {"kernel": ("layers", "embed", "kv_heads"), **bias("kv_heads")},
                   "v": {"kernel": ("layers", "embed", "kv_heads"), **bias("kv_heads")}}
        stacks.update({
            "window_attn": {**kv_axes, **diff_axes},
            "full_attn": {**kv_axes, **diff_axes},
            "cross_attn": diff_axes,
            # the mixer's inner width has no head-aligned split: it stays whole
            "mamba1": {
                "in_proj": {"kernel": ("layers", "embed", None)},
                "conv": {"kernel": ("layers", None, None), "bias": ("layers", None)},
                "x_proj": {"kernel": ("layers", None, None)},
                "dt_proj": {"kernel": ("layers", None, None)},
                "dt_bias": ("layers", None),
                "A_log": ("layers", None, None),
                "D": ("layers", None),
                "out_proj": {"kernel": ("layers", None, "embed")},
                **dhd,
            },
            "gmu": {"in_proj": {"kernel": ("layers", "embed", None)},
                    "out_proj": {"kernel": ("layers", None, "embed")}, **dhd},
        })
        out = {
            "embed": {"embedding": ("vocab", "embed")},
            "layers": {kind: stacks[kind]
                       for kind in dict.fromkeys(k for k, _, _ in cfg.layer_runs())},
            "final_norm": {"scale": ("embed",), **({"bias": ("embed",)} if cfg.layer_norm else {})},
        }
        if not cfg.tied_head:
            out["lm_head"] = {"kernel": ("embed", "vocab")}
        return out
    if cfg.arch == "gpt2":
        return {
            "embed": {"embedding": ("vocab", "embed")},
            "pos_embed": {"embedding": (None, "embed")},
            "layers": {
                "attn_norm": {"scale": ("layers", "embed"),
                              "bias": ("layers", "embed")},
                "q": {"kernel": ("layers", "embed", "heads"),
                      "bias": ("layers", "heads")},
                "k": {"kernel": ("layers", "embed", "heads"),
                      "bias": ("layers", "heads")},
                "v": {"kernel": ("layers", "embed", "heads"),
                      "bias": ("layers", "heads")},
                "o": {"kernel": ("layers", "heads", "embed"),
                      "bias": ("layers", "embed")},
                "mlp_norm": {"scale": ("layers", "embed"),
                             "bias": ("layers", "embed")},
                "fc": {"kernel": ("layers", "embed", "mlp"),
                       "bias": ("layers", "mlp")},
                "proj": {"kernel": ("layers", "mlp", "embed"),
                         "bias": ("layers", "embed")},
            },
            "final_norm": {"scale": ("embed",), "bias": ("embed",)},
        }
    layers: dict[str, Any] = {
        "attn_norm": {"scale": ("layers", "embed")},
        "q": {"kernel": ("layers", "embed", "heads")},
        "k": {"kernel": ("layers", "embed", "kv_heads")},
        "v": {"kernel": ("layers", "embed", "kv_heads")},
        "o": {"kernel": ("layers", "heads", "embed")},
        **_mlp_axes(cfg),
    }
    if cfg.arch == "qwen":
        layers["q_norm"] = {"scale": ("layers", None)}
        layers["k_norm"] = {"scale": ("layers", None)}
    out = {
        "embed": {"embedding": ("vocab", "embed")},
        "layers": layers,
        "final_norm": {"scale": ("embed",)},
    }
    if not cfg.tied_head:
        out["lm_head"] = {"kernel": ("embed", "vocab")}
    return out


def param_count(cfg: ModelConfig) -> int:
    L, D, V, F = cfg.n_layers, cfg.d_model, cfg.vocab_size, cfg.d_ff
    H, KV, HD = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if cfg.arch == "gpt2":
        attn = 4 * D * D + 4 * D  # q/k/v/o kernels + biases (H·HD == D)
        mlp = 2 * D * F + F + D   # fc/proj kernels + biases
        per_layer = attn + mlp + 4 * D  # two LayerNorms (scale + bias)
        return V * D + cfg.max_seq_len * D + L * per_layer + 2 * D  # tied head
    # A mixture counts the experts this tree HOLDS (a share of them in a
    # hybrid that says so), the router over all of them, the shared expert.
    mlp = 3 * D * F * (cfg.n_experts_held if cfg.is_moe else 1) + 3 * D * cfg.shared_d_ff
    router = D * cfg.n_experts if cfg.is_moe else 0
    per_layer = D * H * HD + 2 * D * KV * HD + H * HD * D + mlp + router + 2 * D
    if cfg.arch == "qwen":
        per_layer += 2 * HD  # per-head q/k RMSNorm scales
    head = 0 if cfg.tied_head else D * V
    if cfg.is_hybrid:
        I, C, SH = cfg.ssm_inner, cfg.ssm_conv_dim, cfg.ssm_heads
        mlp += router  # the block after every kind of mixer, a mixture's router in it
        # in_proj (z | xBC | dt), conv taps + bias, A_log / dt_bias / D,
        # the gate's norm, out_proj; then the layer's two norms and MLP.
        per_ssm = (D * (I + C + SH) + (cfg.ssm_conv + 1) * C + 3 * SH + I
                   + I * D + mlp + 2 * D)
        # q, k, v, the output gate and o, the q/k norms (and lightning's output
        # norm and decay rates); then the layer's two norms and MLP.
        per_sparse = 2 * D * H * HD + 2 * D * KV * HD + H * HD * D + 2 * HD + mlp + 2 * D
        LI, LHD = cfg.lightning_inner, cfg.lightning_head_dim
        per_lightning = 5 * D * LI + 2 * LHD + LI + cfg.lightning_heads + mlp + 2 * D
        # q, k, v, o, the gate's kernel and bias, the q/k norms; the two norms and MLP.
        per_power = 2 * D * H * HD + 2 * D * KV * HD + D * KV + KV + 2 * HD + mlp + 2 * D
        # q, kv_a, the latent's norm, kv_b, o and the layer's two norms; then
        # the stack's block (with a sigmoid router's bias) or the dense SwiGLU.
        C, R = cfg.kv_latent_dim, cfg.qk_rope_dim
        mla = (D * H * HD + D * (C + R) + C + C * H * (cfg.qk_nope_dim + cfg.v_head_dim)
               + H * cfg.v_head_dim * D + 2 * D)
        bias = cfg.n_experts if cfg.router_scoring == "sigmoid" else 0
        # The decoder-hybrid-decoder kinds: a layer's two norms (with biases
        # under ``layer_norm``) and its MLP, and the mixer: Mamba-1's in_proj
        # (x | z), conv taps + bias, x_proj, dt_proj + dt_bias, A_log, D and
        # out_proj; differential attention's projections (+ biases), four
        # lambda vectors, lambda_init and the sub-norm; the GMU's two.
        MI, MN, MR = cfg.mamba1_inner, cfg.mamba1_state, cfg.mamba1_rank
        norms = 2 * D * (2 if cfg.layer_norm else 1)
        qo = 2 * D * H * HD + (H * HD + D if cfg.attn_bias else 0)
        kv = 2 * D * KV * HD + (2 * KV * HD if cfg.attn_bias else 0)
        diff = 4 * HD + 1 + 2 * HD
        mamba1 = (2 * D * MI + (cfg.ssm_conv + 1) * MI + MI * (MR + 2 * MN) + MR * MI + MI
                  + MN * MI + MI + MI * D)
        dhd = (cfg.n_layers_of("mamba1") * (mamba1 + mlp + norms)
               + (cfg.n_layers_of("diff_window_attention") + cfg.n_layers_of("diff_attention"))
               * (qo + kv + diff + mlp + norms)
               + cfg.n_layers_of("diff_cross_attention") * (qo + diff + mlp + norms)
               + cfg.n_layers_of("gmu") * (2 * D * MI + mlp + norms)
               + (D if cfg.layer_norm else 0))  # the final norm's bias
        return (dhd + V * D + cfg.n_attn_layers * per_layer + cfg.n_ssm_layers * per_ssm
                + cfg.n_layers_of("sparse_attention") * per_sparse
                + cfg.n_layers_of("lightning") * per_lightning
                + cfg.n_layers_of("power_retention") * per_power
                + cfg.n_layers_of("mla") * (mla + mlp + bias)
                + cfg.n_layers_of("mla_dense") * (mla + 3 * D * cfg.dense_d_ff) + D + head)
    return V * D + L * per_layer + D + head


def active_param_count(cfg: ModelConfig) -> int:
    """Parameters touched per token (= param_count for dense; top-k experts
    only for MoE — the honest N for FLOPs accounting)."""
    if not cfg.is_moe:
        return param_count(cfg)
    L, D, F = cfg.n_mixture_layers, cfg.d_model, cfg.d_ff
    inactive_experts = cfg.n_experts_held - cfg.top_k
    return param_count(cfg) - L * 3 * D * F * inactive_experts


def train_flops_per_token(cfg: ModelConfig, seq_len: int) -> float:
    """Approximate training FLOPs/token: 6·N_active_matmul + attention term
    (12·L·D·S accounting fwd+bwd of the S×S score/value matmuls). With
    sliding-window attention each query attends at most ``sliding_window``
    keys, so the attention term uses min(S, W) — keeping MFU honest."""
    if cfg.arch == "gpt2":
        # Tied head: the V·D weight is a real matmul at the head; only the
        # positional-embedding lookup is not.
        n = active_param_count(cfg) - cfg.max_seq_len * cfg.d_model
    elif cfg.tied_head:
        # Tied head: the embedding's V·D is counted once and spent on the
        # head matmul; the lookup itself is free.
        n = active_param_count(cfg)
    else:
        n = active_param_count(cfg) - cfg.vocab_size * cfg.d_model  # embedding lookup is not a matmul
    attn_ctx = min(seq_len, cfg.sliding_window) if cfg.sliding_window else seq_len
    return 6.0 * n + 12.0 * cfg.n_layers * cfg.d_model * attn_ctx


# ---------------------------------------------------------------------------
# Forward pass
# ---------------------------------------------------------------------------


def _rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def _layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float) -> jax.Array:
    """Mean-subtracting LayerNorm with bias (GPT-2 family)."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    out = (x32 - mu) * lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def _norm(x: jax.Array, p: dict, cfg: "ModelConfig") -> jax.Array:
    """Arch-dispatching norm: RMSNorm (llama), LayerNorm+bias (gpt2), or
    zero-centred RMSNorm (gemma: the stored scale is an offset from 1, so a
    zero-initialised checkpoint is the identity scale)."""
    if cfg.arch == "gpt2" or cfg.layer_norm:
        return _layer_norm(x, p["scale"], p["bias"], cfg.norm_eps)
    if cfg.arch == "gemma":
        return _rms_norm(x, p["scale"].astype(jnp.float32) + 1.0, cfg.norm_eps)
    return _rms_norm(x, p["scale"], cfg.norm_eps)


def _residual(x: jax.Array, y: jax.Array, cfg: "ModelConfig") -> jax.Array:
    """A mixer's or MLP's output ``y`` joining the residual stream ``x``,
    times ``cfg.residual_scale`` where the model has one — applied in
    float32, because 0.22 is not a bfloat16 number and every layer would
    carry its rounding."""
    if cfg.residual_scale == 1.0:
        return x + y
    return x + (y.astype(jnp.float32) * cfg.residual_scale).astype(x.dtype)


def attention_scale(cfg: "ModelConfig") -> float:
    """What attention scores are multiplied by: the model's own multiplier,
    else 1/sqrt(head_dim)."""
    return cfg.attn_scale or 1.0 / (cfg.head_dim ** 0.5)


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embeddings. x: [B, S, H, HD], positions: [B, S]."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32) * (jnp.log(theta) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _attention(q, k, v, impl: str, mesh=None, window: int = 0):
    """Causal attention dispatch:

    - ``"ring"`` — sequence-parallel ring attention over the mesh's
      ``sequence`` axis (``tpu_engine/parallel/ring_attention.py``);
    - ``"ulysses"`` — sequence-parallel all-to-all attention (head↔sequence
      shard swap, ``tpu_engine/parallel/ulysses_attention.py``);
    - ``"flash"`` — Pallas TPU flash kernel (``tpu_engine/ops``); a shape
      it cannot run raises, it never degrades to XLA;
    - ``"xla"``  — plain XLA attention (reference semantics).

    ``window > 0`` = sliding-window attention (flash/xla paths only; the
    sequence-parallel strategies are full-context by construction).
    """
    if impl in ("ring", "ulysses"):
        if window:
            raise ValueError(
                f"sliding_window is not supported with attention_impl={impl!r}; "
                "use 'flash' or 'xla' (a windowed model has no use for "
                "full-sequence context parallelism)"
            )
        if mesh is None:
            raise ValueError(f"attention_impl={impl!r} requires a mesh")
        if impl == "ring":
            from tpu_engine.parallel.ring_attention import ring_mha

            return ring_mha(q, k, v, mesh=mesh, causal=True)
        from tpu_engine.parallel.ulysses_attention import ulysses_mha

        return ulysses_mha(q, k, v, mesh=mesh, causal=True)
    from tpu_engine.ops import flash_attention  # lazy: avoids import cycles

    if impl != "flash":
        return flash_attention.mha(q, k, v, causal=True, force_xla=True,
                                   window=window)

    # Interpret mode is decided by the devices the kernel will run on — the
    # mesh's, or the default backend's when no mesh was threaded — never
    # by a fallback: an AOT compile for a described TPU topology may run
    # under a CPU-forced process, and the CPU dry-run mesh must exercise
    # the kernel's real custom_vjp wrapping (a *different* backward graph
    # than XLA attention's).
    probe = mesh.devices.flat[0] if mesh is not None else jax.devices()[0]
    interpret = probe.platform != "tpu"
    if mesh is None or mesh.size == 1:
        return flash_attention.mha(q, k, v, causal=True, window=window,
                                   interpret=interpret)

    # Mosaic (Pallas) calls cannot be partitioned by GSPMD — on a
    # multi-device mesh the kernel must run under shard_map with the
    # activation layout pinned: batch over (data, fsdp), heads over
    # "model", sequence local (a >1 "sequence" axis never reaches the
    # flash path — build_train_program routes it to ring/ulysses).
    from jax.sharding import PartitionSpec as P

    model_size = mesh.shape.get("model", 1)
    H, KV = q.shape[2], k.shape[2]
    if H % model_size or KV % model_size:
        # Sharding heads unevenly would change the per-shard GQA ratio
        # (wrong q→kv mapping). build_train_program resolves "auto" away
        # from flash for such shapes; reaching here is an explicit request.
        raise ValueError(
            f"attention_impl='flash' needs q heads ({H}) and kv heads ({KV}) "
            f"divisible by the 'model' mesh axis ({model_size}); use "
            "attention_impl='xla' or a model axis that divides both"
        )
    spec = P(("data", "fsdp"), None, "model", None)
    sh = jax.sharding.NamedSharding(mesh, spec)
    # Pin the boundary on BOTH sides of the manual region. shard_map
    # reshards implicitly, but the explicit constraints also pin the
    # *cotangents* in the backward pass (with_sharding_constraint is its
    # own transpose) — without them, GSPMD sharding propagation around the
    # manual region is ambiguous and the partitioner's dot-strategy
    # estimator probes layouts it can only reach by involuntary full
    # rematerialization (MULTICHIP_r02 tail).
    q, k, v = (jax.lax.with_sharding_constraint(t, sh) for t in (q, k, v))
    # check_vma off: the checker cannot verify a fully sharded output
    # through a Pallas call.
    fn = jax.shard_map(
        partial(flash_attention.mha, causal=True, window=window,
                interpret=interpret),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return jax.lax.with_sharding_constraint(fn(q, k, v), sh)


def _moe_mlp_ragged(h, layer_params, cfg: ModelConfig):
    """Top-k routed MoE via sort + grouped matmuls (``lax.ragged_dot``).

    The dense-dispatch formulation's [B, S, E, C] dispatch/combine
    einsums cost O(B·S²·cf·k/E·D) FLOPs — a 33% routing tax at seq 2048
    that grows with sequence; this ragged path wins +19% at seq 8192
    (measured crossover, benchmarks/RESULTS.md §MoE, pre-ledger). Tokens are SORTED by
    their assigned expert and each expert's contiguous row-group hits one
    grouped matmul: the dispatch/combine become a gather and a
    segment-sum (memory ops, not FLOPs), and there is NO capacity — no
    token is ever dropped. Routing indices are integers (constant under
    autodiff, the standard straight-through treatment); gradients flow
    through the gather/scatter and ``ragged_dot``'s native transpose.

    Single-shard experts only: ``ragged_dot`` is a custom primitive GSPMD
    cannot partition over the expert dim, so expert parallelism keeps the
    dense path (``build_train_program`` validates).
    """
    B, S, D = h.shape
    E, K = cfg.n_experts, cfg.top_k
    BS = B * S
    x = h.reshape(BS, D)

    with jax.named_scope("moe_router"):
        router_logits = jnp.einsum(
            "td,de->te", x, layer_params["router"]["kernel"],
            preferred_element_type=jnp.float32,
        )
        probs = jax.nn.softmax(router_logits, axis=-1)       # [BS, E] fp32
        gate_vals, expert_idx = lax.top_k(probs, K)          # [BS, K]
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

        flat_expert = expert_idx.reshape(-1)                 # [BS*K]
        order = jnp.argsort(flat_expert)                     # stable
        tok = jnp.arange(BS * K, dtype=jnp.int32) // K       # slot → token
        tok_sorted = tok[order]
        xs = jnp.take(x, tok_sorted, axis=0)                 # [BS*K, D] gather
        group_sizes = jnp.bincount(flat_expert, length=E).astype(jnp.int32)

    def kern(name):
        w = layer_params[name]["kernel"]
        if isinstance(w, QuantWeight):
            return dequantize_weight(w, h.dtype)
        return w

    with jax.named_scope("moe_experts"):
        g = lax.ragged_dot(xs, kern("gate"), group_sizes,
                           preferred_element_type=h.dtype)
        u = lax.ragged_dot(xs, kern("up"), group_sizes,
                           preferred_element_type=h.dtype)
        y = lax.ragged_dot(jax.nn.silu(g) * u, kern("down"), group_sizes,
                           preferred_element_type=h.dtype)   # [BS*K, D]
        w_sorted = gate_vals.reshape(-1)[order].astype(h.dtype)
        out = jax.ops.segment_sum(
            y * w_sorted[:, None], tok_sorted, num_segments=BS
        )
        out = out.reshape(B, S, D)

    # Same load-balancing aux loss as the dense path (Switch eq. 4).
    first_choice = jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32)
    f = jnp.mean(first_choice, axis=0)
    p = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f * p)
    return out, aux


def _moe_mlp(h, layer_params, cfg: ModelConfig):
    """Top-k routed mixture-of-experts MLP (Switch/MTF-style dense dispatch).

    h: [B, S, D] → (out [B, S, D], aux_loss scalar). Static shapes
    throughout: tokens beyond an expert's capacity are dropped (contribute
    zero), the standard TPU-friendly formulation — no dynamic gather, all
    dispatch/combine work is einsum on the MXU. Experts are sharded over the
    "model" mesh axis via the "expert" logical axis (expert parallelism);
    XLA inserts the all-to-all from the sharding annotations.
    """
    B, S, D = h.shape
    E, K = cfg.n_experts, cfg.top_k
    C = cfg.expert_capacity(S)

    with jax.named_scope("moe_router"):
        router_logits = jnp.einsum(
            "bsd,de->bse", h, layer_params["router"]["kernel"],
            preferred_element_type=jnp.float32,
        )
        probs = jax.nn.softmax(router_logits, axis=-1)  # [B, S, E] fp32

        # Greedy top-k assignment with per-expert capacity, one k at a time so
        # first choices claim capacity before second choices.
        remaining = probs
        count_so_far = jnp.zeros((B, E), jnp.float32)  # tokens already accepted
        combine = jnp.zeros((B, S, E, C), h.dtype)
        for _ in range(K):
            idx = jnp.argmax(remaining, axis=-1)                      # [B, S]
            mask = jax.nn.one_hot(idx, E, dtype=jnp.float32)          # [B, S, E]
            gate_val = jnp.sum(probs * mask, axis=-1)                 # [B, S]
            # Position each token takes inside its expert's capacity buffer.
            pos = jnp.cumsum(mask, axis=1) - 1 + count_so_far[:, None, :]
            pos_tok = jnp.sum(pos * mask, axis=-1)                    # [B, S]
            keep = (pos_tok < C) & (gate_val > 0)
            count_so_far = count_so_far + jnp.sum(mask, axis=1)
            onehot_pos = jax.nn.one_hot(pos_tok.astype(jnp.int32), C, dtype=jnp.float32)  # [B, S, C]
            contrib = (gate_val * keep)[:, :, None, None] * mask[:, :, :, None] * onehot_pos[:, :, None, :]
            combine = combine + contrib.astype(h.dtype)
            remaining = remaining * (1.0 - mask)  # exclude chosen expert for next k

        # Renormalise the kept top-k gates to sum to 1 per token.
        denom = jnp.sum(combine, axis=(2, 3), keepdims=True)
        combine = combine / jnp.maximum(denom, 1e-9).astype(h.dtype)
        dispatch = (combine > 0).astype(h.dtype)                      # [B, S, E, C]

    def kern(name):
        # Expert kernels may be int8 QuantWeights (quantized eval /
        # prefill of a serving tree): dequantize inline — XLA fuses the
        # convert+scale into the einsum's operand read.
        w = layer_params[name]["kernel"]
        if isinstance(w, QuantWeight):
            return dequantize_weight(w, h.dtype)
        return w

    # Only the per-expert matmuls ride the quantized-training hook; the
    # router (fp32 softmax input) and the [B,S,E,C] dispatch/combine
    # einsums (0/1 masks and gates — not matmul-heavy per element, and
    # quantization-sensitive) stay full precision.
    with jax.named_scope("moe_experts"):
        dot = _train_dot(cfg, "moe") or jnp.einsum
        expert_in = jnp.einsum("bsec,bsd->ebcd", dispatch, h)         # [E, B, C, D]
        gate = dot("ebcd,edf->ebcf", expert_in, kern("gate"))
        up = dot("ebcd,edf->ebcf", expert_in, kern("up"))
        expert_out = dot("ebcf,efd->ebcd", jax.nn.silu(gate) * up, kern("down"))
        out = jnp.einsum("bsec,ebcd->bsd", combine, expert_out)

    # Load-balancing auxiliary loss (Switch Transformer eq. 4): fraction of
    # tokens dispatched to each expert × mean router prob, scaled by E.
    first_choice = jax.nn.one_hot(jnp.argmax(probs, axis=-1), E, dtype=jnp.float32)
    f = jnp.mean(first_choice, axis=(0, 1))  # fraction per expert
    p = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(f * p)
    return out, aux


def _train_dot(cfg: ModelConfig, group: str):
    """The injectable quantized-dot hook for one matmul group ("attn",
    "mlp", "moe"): :func:`tpu_engine.quant_train.int8_einsum` when
    ``cfg.quant_training == "int8"`` and ``group`` is targeted, else None
    (call sites fall back to plain einsum via ``dot or jnp.einsum``)."""
    if cfg.quant_training == "int8" and group in cfg.quant_train_targets:
        return int8_einsum
    return None


def _proj(h, kernel, lora_ab=None, lora_scale=1.0, bias=None, dot=None):
    """Last-dim projection ``h @ W (+ b)``, with an optional rank-sized LoRA
    term ``scale·(h@A)@B`` — the activation-side formulation: only [.., r]
    intermediates and rank-sized cotangents, never a full ΔW.
    h: [B, S, in], kernel: [in, out] → [B, S, out].

    ``kernel`` may be an int8 :class:`tpu_engine.quant.QuantWeight`
    (weight-only quantized serving): the per-output-channel scale is
    constant along the contraction, so it applies to the matmul OUTPUT —
    the int8→compute-dtype convert fuses into the dot's operand read and
    the weight's HBM traffic stays int8-sized.

    ``dot``: optional quantized-einsum hook (:func:`_train_dot`) for the
    main matmul only — serving QuantWeights are already int8 and the
    rank-sized LoRA terms are too small to be worth quantizing."""
    if isinstance(kernel, QuantWeight):
        out = jnp.einsum("bsi,io->bso", h, kernel.q.astype(h.dtype))
        # Scale in fp32 (one rounding, at the end) — rounding the scale
        # itself to bf16 would add a second, avoidable error; the
        # mul+cast fuses into the matmul's output loop.
        out = (out.astype(jnp.float32) * kernel.scale).astype(h.dtype)
    else:
        out = (dot or jnp.einsum)("bsi,io->bso", h, kernel)
    if bias is not None:
        out = out + bias.astype(out.dtype)
    if lora_ab is not None:
        hA = jnp.einsum("bsi,ir->bsr", h, lora_ab["A"].astype(h.dtype))
        out = out + lora_scale * jnp.einsum("bsr,ro->bso", hA, lora_ab["B"].astype(h.dtype))
    return out


def _dense_mlp(h, layer_params, lora=None, lora_scale=1.0, *, cfg: ModelConfig):
    """MLP shared by the training block and the decode block: SwiGLU
    (llama), biased GELU-tanh fc/proj (gpt2), or GeGLU (gemma).
    h: [B, S, D] (already normed) → [B, S, D]. ``cfg`` is REQUIRED — see
    :func:`embed_tokens`."""
    lora = lora or {}
    dot = _train_dot(cfg, "mlp")
    if cfg.arch == "gpt2":
        h = jax.nn.gelu(
            _proj(h, layer_params["fc"]["kernel"], lora.get("fc"), lora_scale,
                  bias=layer_params["fc"]["bias"], dot=dot),
            approximate=True)
        return _proj(h, layer_params["proj"]["kernel"], lora.get("proj"),
                     lora_scale, bias=layer_params["proj"]["bias"], dot=dot)
    gate = _proj(h, layer_params["gate"]["kernel"], lora.get("gate"), lora_scale,
                 dot=dot)
    up = _proj(h, layer_params["up"]["kernel"], lora.get("up"), lora_scale,
               dot=dot)
    if cfg.arch == "gemma":
        act = jax.nn.gelu(gate, approximate=True)  # GeGLU
    else:
        act = jax.nn.silu(gate)  # SwiGLU
    return _proj(act * up, layer_params["down"]["kernel"],
                 lora.get("down"), lora_scale, dot=dot)


def _block(
    x, layer_params, cfg: ModelConfig, positions, mesh=None, tag_names=False,
    lora=None, lora_scale=1.0,
):
    """One transformer block. x: [B, S, D] → (x, moe_aux_loss).

    ``tag_names=True`` tags q/k/v/attn_out with ``checkpoint_name`` for the
    named remat policies (save_attn_out / save_qkv_attn_out). Tagging is
    opt-in because the names act as optimisation barriers: under a non-named
    policy they cost ~1.5 GB of pointlessly-saved rope buffers at 1B scale.

    ``lora``: optional per-layer adapter dict (target → {A, B}) applied
    inside each projection (``tpu_engine/lora.py``).
    """
    B, S, D = x.shape
    H, KV, HD = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    tag = checkpoint_name if tag_names else (lambda a, _name: a)
    lora = lora or {}

    gpt2 = cfg.arch == "gpt2"
    bias = (lambda name: layer_params[name]["bias"]) if gpt2 else (lambda name: None)
    dot = _train_dot(cfg, "attn")
    with jax.named_scope("attn"):
        h = _norm(x, layer_params["attn_norm"], cfg)
        q = _proj(h, layer_params["q"]["kernel"], lora.get("q"), lora_scale,
                  bias("q"), dot=dot).reshape(B, S, H, HD)
        k = _proj(h, layer_params["k"]["kernel"], lora.get("k"), lora_scale,
                  bias("k"), dot=dot).reshape(B, S, KV, HD)
        v = _proj(h, layer_params["v"]["kernel"], lora.get("v"), lora_scale,
                  bias("v"), dot=dot).reshape(B, S, KV, HD)
        if cfg.arch == "qwen":  # per-head qk-norm, before RoPE
            q = _rms_norm(q, layer_params["q_norm"]["scale"], cfg.norm_eps)
            k = _rms_norm(k, layer_params["k_norm"]["scale"], cfg.norm_eps)
        if cfg.rope and not gpt2:  # gpt2 uses learned absolute positions, added at embed time
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
        if cfg.attn_scale:  # the kernels scale by 1/sqrt(head_dim): fold the rest into q
            q = q * jnp.asarray(cfg.attn_scale * HD ** 0.5, q.dtype)
        q, k, v = tag(q, "q"), tag(k, "k"), tag(v, "v")
        attn = _attention(q, k, v, cfg.attention_impl, mesh=mesh,
                          window=cfg.sliding_window)
        attn = tag(attn.reshape(B, S, H * HD), "attn_out")
        x = _residual(x, _proj(attn, layer_params["o"]["kernel"], lora.get("o"),
                               lora_scale, bias("o"), dot=dot), cfg)

    h = _norm(x, layer_params["mlp_norm"], cfg)
    if cfg.is_moe:
        if cfg.moe_impl not in ("dense", "ragged"):  # trace-time, free
            raise ValueError(
                f"moe_impl={cfg.moe_impl!r} unknown; use 'dense' or 'ragged'"
            )
        if (cfg.moe_impl == "ragged" and cfg.quant_training == "int8"
                and "moe" in cfg.quant_train_targets):
            raise ValueError(
                "quant_training='int8' cannot quantize ragged MoE "
                "(lax.ragged_dot takes no per-channel scales); use "
                "moe_impl='dense' or drop 'moe' from quant_train_targets"
            )
        moe = _moe_mlp_ragged if cfg.moe_impl == "ragged" else _moe_mlp
        mlp_out, aux = moe(h, layer_params, cfg)
        x = _residual(x, mlp_out, cfg)
        return x, aux
    with jax.named_scope("mlp"):
        x = _residual(x, _dense_mlp(h, layer_params, lora, lora_scale, cfg=cfg), cfg)
    return x, jnp.zeros((), jnp.float32)


_REMAT_POLICIES = {
    "nothing_saveable": jax.checkpoint_policies.nothing_saveable,
    "dots_saveable": jax.checkpoint_policies.dots_saveable,
    "dots_with_no_batch_dims_saveable": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    "everything_saveable": jax.checkpoint_policies.everything_saveable,
    # Named-offset policies (activations tagged with checkpoint_name in
    # _block): skip recomputing attention — and optionally the qkv
    # projections + rope — in the backward pass, at a small, bounded
    # activation-memory cost per layer. The TPU analogue of selectively
    # tuning DeepSpeed's activation-checkpointing granularity
    # (reference ``deepspeed_launcher.py:215-223``).
    "save_attn_out": jax.checkpoint_policies.save_only_these_names("attn_out"),
    "save_qkv_attn_out": jax.checkpoint_policies.save_only_these_names(
        "q", "k", "v", "attn_out"
    ),
    # Activation OFFLOAD (not recompute): matmul outputs are saved to
    # pinned host memory during the forward pass and fetched back for the
    # backward — trades HBM for PCIe/DMA bandwidth instead of for FLOPs.
    # The remaining (elementwise) values still rematerialise. The TPU
    # analogue of DeepSpeed's cpu_checkpointing (reference
    # ``deepspeed_launcher.py:403``: the 70b preset's cpu ckpt knob).
    # Measured honestly (AOT, llama-7b/fsdp8/seq4096): the saved-dot
    # streaming buffers RAISE peak temp memory vs full remat (12.3 vs
    # 9.5 GiB) — full rematerialisation wins on these shapes; the policy
    # is the lever for FLOPs-bound shapes, not a default. TPU-only: the
    # CPU partitioner cannot compile host-placement annotations.
    "offload_dots": jax.checkpoint_policies.offload_dot_with_no_batch_dims(
        "device", "pinned_host"
    ),
}

# Policies that rely on checkpoint_name tags in _block (tagging is opt-in —
# under other policies the tags would only add optimisation barriers).
NAMED_REMAT_POLICIES = frozenset({"save_attn_out", "save_qkv_attn_out"})


def resolve_remat_policy(name: str):
    """Strict policy lookup: (policy, needs_name_tags). Raises on typos —
    a silent fallback would train with the wrong memory profile."""
    if name not in _REMAT_POLICIES:
        raise ValueError(
            f"unknown remat_policy {name!r}; valid: {sorted(_REMAT_POLICIES)}"
        )
    return _REMAT_POLICIES[name], name in NAMED_REMAT_POLICIES


def remat_scan_body(
    cfg: ModelConfig,
    positions: jax.Array,
    mesh,
    remat: bool,
    remat_policy: str,
    lora_scale: float = 1.0,
    layer_stream=None,
    layer_constraint=None,
):
    """The (optionally remat-wrapped) per-layer scan body shared by the
    plain forward and the pipelined forward.

    The scan ``xs`` may be either the layer-params dict alone or a
    ``(layer_params, lora_layer)`` pair when adapters train alongside.

    ``layer_stream`` is the param-offload streaming seam: a function applied
    to each layer's params *inside* the (remat-wrapped) body — e.g. a
    pinned_host→device transfer + compute-dtype cast. Placing it inside the
    checkpointed body means the backward pass re-streams each layer from
    host instead of keeping a device-resident copy alive, so weight
    residency stays O(one layer) in both passes.

    ``layer_constraint`` pins each layer's sliced weights (and, via the
    constraint's transpose, their cotangents) to their canonical shardings
    *inside* the body. Without the anchor, GSPMD sharding propagation
    through the remat-wrapped backward scan can lose the weight layout once
    manual (shard_map) regions interrupt propagation, and the partitioner
    falls back to "involuntary full rematerialization" — a per-layer
    all-gather of weights that should stay sharded (observed on the
    multi-chip flash-attention path, MULTICHIP_r02)."""
    policy, tag_names = (None, False) if not remat else resolve_remat_policy(remat_policy)

    def scan_body(carry, xs):
        layer_params, lora_layer = xs if isinstance(xs, tuple) else (xs, None)
        if layer_stream is not None:
            layer_params = layer_stream(layer_params)
        elif layer_constraint is not None:
            layer_params = layer_constraint(layer_params)
        return _block(
            carry, layer_params, cfg, positions, mesh=mesh, tag_names=tag_names,
            lora=lora_layer, lora_scale=lora_scale,
        )

    if remat:
        return jax.checkpoint(scan_body, policy=policy, prevent_cse=True)
    return scan_body


def embed_tokens(params: dict[str, Any], tokens: jax.Array, compute_dtype=jnp.bfloat16,
                 positions: Optional[jax.Array] = None, *,
                 cfg: ModelConfig) -> jax.Array:
    """Embedding lookup: tokens [..., S] int32 → activations [..., S, D].
    GPT-2-family params (a ``pos_embed`` table is present) add learned
    absolute position embeddings — pass ``positions`` for decode offsets
    (defaults to 0..S-1). Gemma-family models (``cfg.arch == "gemma"``)
    scale the looked-up embeddings by sqrt(d_model). ``cfg`` is REQUIRED:
    arch-dependent math behind an optional parameter turns a forgotten
    argument into a silently different model."""
    with jax.named_scope("embed"):
        embed = params["embed"]["embedding"].astype(compute_dtype)
        x = jnp.take(embed, tokens, axis=0)
        if cfg.arch == "gemma":
            x = x * jnp.asarray(cfg.d_model ** 0.5, compute_dtype)
        if cfg.embed_scale != 1.0:
            x = x * jnp.asarray(cfg.embed_scale, compute_dtype)
        if "pos_embed" in params:
            if positions is None:
                positions = jnp.arange(tokens.shape[-1], dtype=jnp.int32)
            wpe = params["pos_embed"]["embedding"].astype(compute_dtype)
            x = x + jnp.take(wpe, positions, axis=0)
    return x


def unembed(params: dict[str, Any], x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Final norm + LM head: activations [..., S, D] → logits [..., S, V]
    fp32. A tied head (``cfg.tied_head``) is the token embedding; a model
    with a ``logits_divisor`` divides its logits by it."""
    with jax.named_scope("head"):
        x = _norm(x, jax.tree.map(lambda a: a.astype(x.dtype), params["final_norm"]), cfg)
        head = (params["embed"]["embedding"].T if cfg.tied_head
                else params["lm_head"]["kernel"])
        if isinstance(head, QuantWeight):
            logits = jnp.einsum(
                "...sd,dv->...sv", x, head.q.astype(x.dtype),
                preferred_element_type=jnp.float32,
            ) * head.scale.astype(jnp.float32)
        else:
            logits = jnp.einsum(
                "...sd,dv->...sv", x, head.astype(x.dtype),
                preferred_element_type=jnp.float32,
            )
        if cfg.logits_divisor != 1.0:
            logits = logits / cfg.logits_divisor
        return logits


def _is_quant(a) -> bool:
    return isinstance(a, QuantWeight)


def _served_dtype(path, leaf, compute_dtype):
    """The dtype ``leaf`` has in the served format, or None where the format
    leaves it as it is: a :class:`QuantWeight` (int8 codes cast at the matmul,
    and its fp32 scales must NOT round to bf16 — that would double the
    quantization error for free), a non-floating leaf, and the recurrence
    leaves (:data:`FLOAT32_LEAVES`)."""
    if _is_quant(leaf) or not jnp.issubdtype(leaf.dtype, jnp.floating):
        return None
    if getattr(path[-1], "key", None) in FLOAT32_LEAVES:
        return None
    return jnp.dtype(compute_dtype)


def served_format(params: dict[str, Any], compute_dtype=jnp.bfloat16) -> dict[str, Any]:
    """The weights as the serving programs read them: every floating leaf in
    the compute dtype — layer stacks, ``embed``, ``pos_embed``, ``lm_head``,
    ``final_norm`` — except what :func:`_served_dtype` exempts.

    A replica converts ONCE, when its engine is built
    (``ContinuousBatcher.__init__``; :func:`generate.generate` at its entry),
    and the cached walks (``decode_step``, ``decode_verify``,
    ``forward_with_cache``) take the tree as it is: no float32 master lies
    beside the pool and no program re-casts the stack. Idempotent (a leaf
    already in the format is returned itself, so a converted tree costs
    nothing); an elementwise convert keeps a sharded leaf's sharding; works on
    tracers."""
    def convert(path, a):
        dtype = _served_dtype(path, a, compute_dtype)
        return a if dtype is None or a.dtype == dtype else a.astype(dtype)

    return jax.tree_util.tree_map_with_path(convert, params, is_leaf=_is_quant)


def require_served_format(stacks: dict[str, Any], compute_dtype) -> None:
    """Raise where a layer stack is not in :func:`served_format` (a trace-time
    look at dtypes). The cached walks no longer cast, and a float32 stack
    under bf16 activations would silently compute in mixed precision."""
    off = []
    for path, a in jax.tree_util.tree_leaves_with_path(stacks, is_leaf=_is_quant):
        want = _served_dtype(path, a, compute_dtype)
        if want is not None and a.dtype != want:
            off.append(f"{jax.tree_util.keystr(path)}: {a.dtype}")
    if off:
        raise TypeError(
            f"weights are not in the served format for {jnp.dtype(compute_dtype)} "
            f"({', '.join(off[:3])}{', ...' if len(off) > 3 else ''}): convert "
            "them once with transformer.served_format(params, compute_dtype)"
        )


def weight_bytes_by_dtype(params: dict[str, Any]) -> dict[str, int]:
    """Bytes a parameter tree holds, by dtype name (a sharded leaf counts
    whole, over all its devices)."""
    out: dict[str, int] = {}
    for a in jax.tree.leaves(params):
        name = str(a.dtype)
        out[name] = out.get(name, 0) + a.size * a.dtype.itemsize
    return out


def cast_layer_stack(params: dict[str, Any], compute_dtype=jnp.bfloat16) -> dict[str, Any]:
    """TRAINING's cast: the stacked per-layer params ([L, ...] leaves) of a
    master-dtype tree as a compute-dtype copy, made inside the step program
    (scope ``cast_weights``) so that the backward keeps bf16 slices and the
    optimizer its float32 master. Serving has no such cast: an engine holds
    :func:`served_format` and nothing else. Same leaves, same exemptions."""
    with jax.named_scope("cast_weights"):
        return served_format(params["layers"], compute_dtype)


def forward_hidden_and_aux(
    params: dict[str, Any],
    tokens: jax.Array,
    cfg: ModelConfig,
    compute_dtype=jnp.bfloat16,
    remat: bool = False,
    remat_policy: str = "nothing_saveable",
    positions: Optional[jax.Array] = None,
    mesh=None,
    lora: Optional[dict[str, Any]] = None,
    lora_scale: float = 1.0,
    layer_stream=None,
    layer_constraint=None,
) -> tuple[jax.Array, jax.Array]:
    """Decoder stack only: tokens [B, S] int32 → (hidden [B, S, D] in the
    compute dtype — final norm / LM head NOT applied, see :func:`unembed` —
    and the mean MoE aux loss).

    ``lora``: optional stacked adapter tree (``tpu_engine/lora.py``) scanned
    alongside the layer stack; applied inside each target projection.

    The whole layer stack is cast to the compute dtype up front (casting
    per-layer inside the scan body reads cheaper but is a pessimisation:
    XLA saves the *master-dtype* param slices as loop residuals for the
    backward pass, costing a full fp32 copy instead of a bf16 one).

    ``layer_stream`` (param offload): when set, the up-front cast is
    SKIPPED — the scan consumes the raw (pinned_host-resident) master-dtype
    stack and the hook transfers + casts one layer at a time inside the
    remat-wrapped body (see :func:`remat_scan_body`). An up-front cast here
    would materialise the full device-resident stack the offload exists to
    avoid."""
    B, S = tokens.shape
    # The cache-less forward (training, evaluation) scans one kind of layer;
    # a hybrid's prefill is generate.forward_with_cache.
    refuse_beyond_kv(cfg, "the cache-less forward pass (training and evaluation)")
    refuse_hybrid_mixture(cfg, "the cache-less forward pass (training and evaluation)")
    if cfg.arch == "gpt2" and S > cfg.max_seq_len:
        # Learned position table: jnp.take would silently clamp out-of-range
        # rows (RoPE models have no such bound).
        raise ValueError(
            f"seq_len {S} exceeds the learned position table "
            f"(max_seq_len={cfg.max_seq_len}) of gpt2-family model {cfg.name!r}"
        )
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))

    x = embed_tokens(params, tokens, compute_dtype, positions=positions,
                     cfg=cfg)  # [B, S, D]
    if layer_stream is None:
        layer_stack = cast_layer_stack(params, compute_dtype)
    else:
        layer_stack = params["layers"]
    body = remat_scan_body(cfg, positions, mesh, remat, remat_policy, lora_scale,
                           layer_stream=layer_stream,
                           layer_constraint=layer_constraint)
    xs = (layer_stack, lora["layers"]) if lora is not None else layer_stack
    x, aux_per_layer = lax.scan(body, x, xs)
    return x, jnp.mean(aux_per_layer)


def forward_and_aux(
    params: dict[str, Any],
    tokens: jax.Array,
    cfg: ModelConfig,
    compute_dtype=jnp.bfloat16,
    remat: bool = False,
    remat_policy: str = "nothing_saveable",
    positions: Optional[jax.Array] = None,
    mesh=None,
) -> tuple[jax.Array, jax.Array]:
    """Forward pass: tokens [B, S] int32 → (logits [B, S, V] float32,
    aux_loss scalar float32).

    ``aux_loss`` is the mean MoE load-balancing loss over layers (0 for
    dense models) — add ``cfg.router_aux_coef * aux_loss`` to the training
    loss. ``mesh`` is only needed for ``attention_impl="ring"`` or
    ``"ulysses"`` (sequence parallelism), where the attention runs as a
    shard_map over the mesh's ``sequence`` axis.
    """
    x, aux = forward_hidden_and_aux(
        params, tokens, cfg, compute_dtype=compute_dtype, remat=remat,
        remat_policy=remat_policy, positions=positions, mesh=mesh,
    )
    return unembed(params, x, cfg), aux


def forward(
    params: dict[str, Any],
    tokens: jax.Array,
    cfg: ModelConfig,
    compute_dtype=jnp.bfloat16,
    remat: bool = False,
    remat_policy: str = "nothing_saveable",
    positions: Optional[jax.Array] = None,
    mesh=None,
) -> jax.Array:
    """Forward pass: tokens [B, S] int32 → logits [B, S, V] float32."""
    logits, _ = forward_and_aux(
        params, tokens, cfg, compute_dtype=compute_dtype, remat=remat,
        remat_policy=remat_policy, positions=positions, mesh=mesh,
    )
    return logits
