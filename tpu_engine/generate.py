"""Autoregressive generation: KV-cache decode + sampling on the mesh.

The reference is a training control plane with no inference path at all;
a complete framework needs one for held-out evaluation, sampling during
training, and serving smoke tests. TPU-first design:

- **Static shapes end to end.** The cache is a fixed-``max_len`` set of
  ``[L, B, M, KV x HD]`` buffers written with ``dynamic_update_slice``; the
  decode loop is a ``lax.scan`` over ``max_new_tokens`` — no data-dependent
  Python control flow, one compile per (batch, max_len) shape.
- **Same layer scan as training.** Layers are stacked ``[L, ...]`` pytrees
  (``models/transformer.py``), so decode loops over the stack instead of
  unrolling Python loops per layer — and the loop CARRIES the cache: a layer
  writes its new rows in place and only reads the rest (:func:`scan_layers`).
- **Sharding by propagation.** Under ``jit`` on a mesh, XLA propagates the
  param shardings (heads/experts over "model", batch over data axes) into
  the cache and attention ops; no decode-specific partition specs needed.

MoE decode note: the training forward uses capacity-bounded dispatch
(tokens over an expert's capacity are dropped — the standard static-shape
formulation, ``_moe_mlp``). The cached walks compute exact capacity-free
top-k routing instead (every token reaches its chosen experts): the router
scores ALL ``n_experts``, and the experts this tree holds (all of them, or
the share a hybrid's configuration names) are contracted with each token's
gate folded into its activations, a token that did not choose an expert
entering it with gate 0 — or, for a long chunk, only the routed pairs are
computed, grouped by expert (:func:`_moe_mlp_decode`,
:func:`experts_grouped_engages`). Dense models produce
bit-identical logits between :func:`forward` and prefill+decode; MoE models
can differ wherever training-time dispatch dropped a token.

One block follows every kind of layer's mixer (:func:`_mlp_block`): a dense
MLP, or that mixture with its shared expert beside it (a mixture's leading
"mla_dense" layers keep a dense one).

A latent-attention (MLA) layer (:func:`_mla_block`) caches neither keys nor
values but one latent row a token, and attends it by two paths: a chunk
expands the row's lanes to every head's keys and values, decode contracts
its queries against the latent rows as they lie (the absorbed form).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from tpu_engine import layer_state
from tpu_engine.models.transformer import (
    POWER_NORM_EPS,
    POWER_TILE,
    ModelConfig,
    _dense_mlp,
    _norm,
    _proj,
    _residual,
    _rms_norm,
    _rope,
    attention_scale,
    check_hybrid,
    embed_tokens,
    refuse_beyond_kv,
    refuse_recurrent,
    require_served_format,
    served_format,
    unembed,
)
from tpu_engine.ops import expert_gmm, lane_decode, mla_decode, power_update, sparse_block_attention, ssd_update
from tpu_engine.quant import QuantWeight, dequantize_weight

_NEG_INF = -1e30


@jax.tree_util.register_dataclass
@dataclass
class KVCache:
    """Per-layer state of ``B`` rows in lockstep (a pytree — crosses jit/scan
    boundaries).

    ``layers`` is the tree :mod:`tpu_engine.layer_state` allocates,
    ``{kind: {leaf: [L_kind, B, ...]}}``: keys and values ``[L, B, slots, KV x
    HD]`` for the attention layers (``[L, B, slots, KV, HD]`` int8 codes beside
    their scales when ``init_cache(kv_quant=True)``), the recurrent state of a
    hybrid stack's Mamba-2 layers, which has no position to mask. ``pos`` [slots] holds the
    global position stored in each slot (-1 = empty); ``length`` is the number
    of positions already written (scalar int32). When ``ring`` is set
    (sliding-window models whose cache is smaller than the sequence) the
    buffer wraps: writes go to ``position % slots`` and the attention mask
    reads ``pos``, so memory and per-step attention cost are O(window), not
    O(sequence). Non-ring caches keep the classic contract: the caller never
    writes past ``slots`` positions total.

    ``moe_counts`` (a mixture's caches only; ``None`` else, and a leaf less):
    what :func:`scan_layers` has counted of the router's choices since the
    cache was made, :data:`MOE_COUNTS` int32.

    ``sharded``: the cache and the stacks it is walked with lie over a mesh
    (set by whoever places them there; a trace cannot see it otherwise), so a
    walk hands no kernel a stack that is not one device's to read."""

    layers: dict
    pos: jax.Array
    length: jax.Array
    ring: bool = field(default=False, metadata=dict(static=True))
    moe_counts: Optional[jax.Array] = None
    sharded: bool = field(default=False, metadata=dict(static=True))

    @property
    def max_len(self) -> int:
        return layer_state.n_lanes(self.layers)

    @property
    def quantized(self) -> bool:
        return layer_state.quantized(self.layers)


def ring_lanes(cfg: ModelConfig, max_len: int,
               chunk: Optional[int] = None) -> int:
    """Lane count for a KV buffer: ``max_len`` for full-context models, or
    the ring size ``min(max_len, window + chunk - 1)`` for sliding-window
    models (a chunk of T queries needs the window behind its oldest query
    resident). THE single source of this formula — the serving slot pool
    copies a single-row ring cache into its own lanes and is only correct
    because both sides size lanes identically."""
    if not cfg.sliding_window or cfg.is_hybrid:  # a hybrid's window is its window KIND's (layer_state)
        return max_len
    chunk = max_len if chunk is None else chunk
    return min(max_len, cfg.sliding_window + chunk - 1)


def init_cache(
    cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
    max_chunk: Optional[int] = None, kv_quant: bool = False, sharded: bool = False,
) -> KVCache:
    """Allocate a cache able to hold ``max_len`` positions — or, for a
    sliding-window model, a ring buffer of ``window + max_chunk - 1`` slots
    (a chunk of T queries needs the window behind its oldest query to still
    be resident). ``max_chunk`` defaults to ``max_len`` (no shrink); pass
    the real prefill length (as :func:`generate` does) to get O(window)
    memory for long generations.

    ``kv_quant=True`` stores k/v as int8 with per-(slot, kv-head) scales —
    half the cache HBM of bf16, at ~1% quantisation error (symmetric
    absmax over head_dim). ``sharded``: the caller will walk it with stacks
    that lie over a mesh (``KVCache.sharded``)."""
    check_hybrid(cfg)
    if kv_quant:
        refuse_recurrent(cfg, "an int8 KV cache (kv_quant)")
    slots = ring_lanes(cfg, max_len, max_chunk)
    return KVCache(
        layers=layer_state.init_layers(cfg, batch, slots, dtype, kv_quant),
        pos=jnp.full((slots,), -1, jnp.int32),
        length=jnp.zeros((), jnp.int32),
        ring=slots < max_len,
        moe_counts=init_moe_counts(cfg),
        sharded=sharded,
    )


# What a walk counts of a mixture's routing, summed over its layers (and a
# caller's steps): token-expert assignments made at real positions, those of
# them that fell on experts this tree holds, held experts that some real
# token chose (distinct per layer), and the expert-rows the layer's
# contractions ran for them (the masked form: real positions x held experts;
# the grouped one: the rows of the tiles it visited, a group's padding among
# them). ``rows_computed / assignments_held`` is 1 where nothing but routed
# pairs is computed.
MOE_COUNTS = ("assignments", "assignments_held", "experts_hit", "rows_computed")

# Rows (B x T) from which a walk's held experts run grouped: under the chip's
# ≈ 240 FLOP a byte the masked contraction is bound by the experts' READ, which
# no grouping saves (decode: 16-32 rows); at 256 rows the grouped form is even
# (Mixtral's widths) or loses (granite-small's, by 0.45 ms a layer), at 512 it
# reads +25 %, +5 % and -11 % at the three mixture cells' widths, at 2 048 it
# wins at all three (2.0x, 2.0x, 1.2x): PERF.md §6 PR 45, the probe's table.
_GROUPED_FROM_ROWS = 1024


def init_moe_counts(cfg: ModelConfig) -> Optional[jax.Array]:
    """Zeroed :data:`MOE_COUNTS` for a cache of ``cfg``; None without experts."""
    return jnp.zeros((len(MOE_COUNTS),), jnp.int32) if cfg.is_moe else None


def _route(h, layer_params, cfg: ModelConfig):
    """The router over ALL ``n_experts`` in float32: (experts [B, T, K], gates
    [B, T, K] float32). ``softmax``: the ``top_k`` largest probabilities,
    renormalised to sum to 1. ``sigmoid``: per-expert scores; the choice is
    ``top_k(score + router_bias)``, the gates are the chosen scores WITHOUT
    the bias, renormalised to sum to 1 and times ``routed_scale``."""
    K = cfg.top_k
    logits = jnp.einsum("btd,de->bte", h, layer_params["router"]["kernel"],
                        preferred_element_type=jnp.float32)
    if cfg.router_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, top_idx = lax.top_k(scores + layer_params["router_bias"], K)
        top_vals = jnp.take_along_axis(scores, top_idx, axis=-1)
        top_vals = top_vals / (jnp.sum(top_vals, -1, keepdims=True) + 1e-20) * cfg.routed_scale
        return top_idx, top_vals
    probs = jax.nn.softmax(logits, axis=-1)  # [B, T, E] fp32
    # Top-k gates, renormalised to sum to 1 (matches training's combine).
    top_vals, top_idx = lax.top_k(probs, K)  # [B, T, K]
    return top_idx, top_vals / jnp.maximum(jnp.sum(top_vals, -1, keepdims=True), 1e-9)


def experts_grouped_engages(rows: int, cfg: ModelConfig, gate, sharded: bool = False) -> bool:
    """Whether a walk of ``rows`` (B x T) positions runs its held experts
    GROUPED over the routed pairs (:func:`_experts_grouped`) and not masked
    over every held expert (decided from what the trace sees; no option):

    - enough rows that the masked contraction's FLOPs lead the experts' read,
      which no grouping saves (:data:`_GROUPED_FROM_ROWS`: a decode step and a
      256-token chunk decline);
    - a routing sparse enough that the tiles the pairs are EXPECTED to fill,
      every group's padding with them, are at most half the masked form's rows;
    - ``gate``, the kind's stacked leaf ``[L, E, D, F]``, is what the kernels
      read (``expert_gmm.engages``: plain bfloat16 or float32 in whole tile
      columns, on a TPU; an int8 ``QuantWeight`` declines);
    - the stacks are one device's (``sharded``: a replica placed over a mesh
      declines; the kernels carry no partitioning rule)."""
    held = cfg.n_experts_held
    expected = rows * cfg.top_k * held // cfg.n_experts + held * expert_gmm.ROWS
    return (not sharded and rows >= _GROUPED_FROM_ROWS and 2 * expected <= rows * held
            and expert_gmm.engages(gate))


def _experts_grouped(h, stacks, at, top_idx, top_vals, valid, cfg: ModelConfig):
    """The held experts over the routed pairs this tree holds, and over
    nothing else: ([B, T, D], expert-rows computed). Exact for any routing.

    The pairs (token, choice) whose expert lies in ``[experts_first,
    experts_first + n_experts_held)`` at a ``valid`` position are laid out by
    expert in row tiles (``expert_gmm.pair_layout``), their rows of ``h``
    gathered, gate / up / down run per tile against the tile's expert in the
    kind's ``stacks`` where they lie (layer ``at``; ``expert_gmm.experts``),
    and every token gathers its ``top_k`` slots back, each pair's gate folded
    in float32 (a pair that is not ours adds zero)."""
    B, T, D = h.shape
    K, first, held = cfg.top_k, cfg.experts_first, cfg.n_experts_held
    local = top_idx - first
    ours = (local >= 0) & (local < held) & valid[..., None]                                   # [B, T, K]
    layout = expert_gmm.pair_layout(jnp.where(ours, local, held).reshape(-1).astype(jnp.int32), held,
                                    expert_gmm.n_tiles(B * T * min(K, held), held))
    xs = expert_gmm.take(h.reshape(B * T, D), layout.src // K)
    ys = expert_gmm.experts(xs, *(stacks[name]["kernel"] for name in ("gate", "up", "down")), at, layout)
    # the way back CHOICE-major, [K, B x T, D], its K slabs added one to the next: ONE elementwise pass over the
    # gathered slots (token-major, a token's K = 6 rows are no whole tile of the chip's, and as a reduction the
    # slots are first written out again in float32: 0.26 + 0.29 s of the 1.84 s under the scope, PERF.md §6 PR 45)
    mine = ours.reshape(B * T, K).T                                                           # [K, B x T]
    back = expert_gmm.take(ys, jnp.where(mine, layout.pos.reshape(B * T, K).T, 0))            # [K, B x T, D]
    gates = top_vals.reshape(B * T, K).T
    out = sum(jnp.where(mine[k, :, None], gates[k, :, None] * back[k].astype(jnp.float32), 0.0) for k in range(K))
    return out.reshape(B, T, D).astype(h.dtype), layout.tiles_used[0] * expert_gmm.ROWS


def _moe_mlp_decode(h, layer_params, cfg: ModelConfig, valid):
    """Exact top-k mixture for the cached walks: every token reaches those of
    its chosen experts that this tree holds (no capacity buffer — see module
    docstring). h: [B, T, D] → ([B, T, D], :data:`MOE_COUNTS` of this layer).

    The router (:func:`_route`) scores all ``n_experts`` in float32 and keeps
    ``top_k`` of them. What an absent expert would have added is left out; a
    token none of whose experts is held gets the shared expert alone. The
    shared expert (``shared_d_ff``) is a SwiGLU of its own width for every
    token. ``valid`` [B, T] marks the real positions, which alone are counted.

    The held experts (``experts_first`` on, ``n_experts_held`` of them) run in
    one of two forms that give the same numbers at every real position:

    - MASKED: every held expert is computed for the T new positions, a token's
      gate (0 for an expert it did not choose) folded into its activations, and
      one contraction over expert and width together brings them down: nothing
      of ``[B, T, E, D]`` is written. Bound by the experts' read while the rows
      are few: every decode step and short chunk.
    - GROUPED (:func:`_experts_grouped`), where the walk hands the kind's whole
      stacks and the layer's index (``layer_params["experts_in_stack"]``:
      :func:`scan_layers` does where :func:`experts_grouped_engages`): only the
      routed pairs this tree holds are computed, a long chunk's 1.5 a token of
      longctx32's 16 and not all 16 (PERF.md §6 PR 45).
    """
    K, first, held = cfg.top_k, cfg.experts_first, cfg.n_experts_held
    with jax.named_scope("moe_router"):
        top_idx, top_vals = _route(h, layer_params, cfg)
        chosen = top_idx[..., None] == first + jnp.arange(held)  # [B, T, K, held]
        weights = jnp.sum(jnp.where(chosen, top_vals[..., None], 0.0), axis=2)  # [B, T, held]
        live = jnp.any(chosen, axis=2) & valid[..., None]
        real = jnp.sum(valid)
        counts = jnp.stack([K * real, jnp.sum(live), jnp.sum(jnp.any(live, axis=(0, 1))),
                            held * real]).astype(jnp.int32)  # the last: the MASKED form's rows

    def kern(name):
        # Expert kernels may be int8 QuantWeights (weight-only quantized
        # serving): dequantize inline — the convert+scale is an
        # elementwise producer XLA fuses into the einsum's operand read,
        # so HBM still sees int8 bytes (the scale's output-dim broadcast
        # does not line up with these expert einsums' outputs, hence
        # operand-side application here, unlike ``_proj``).
        w = layer_params[name]["kernel"]
        if isinstance(w, QuantWeight):
            return dequantize_weight(w, h.dtype)
        return w

    with jax.named_scope("moe_experts"):
        if "experts_in_stack" in layer_params:
            out, rows = _experts_grouped(h, *layer_params["experts_in_stack"], top_idx, top_vals, valid, cfg)
            counts = counts.at[-1].set(rows)  # ``rows_computed``: the visited tiles' rows
        else:
            gate = jnp.einsum("btd,edf->btef", h, kern("gate"))
            up = jnp.einsum("btd,edf->btef", h, kern("up"))
            act = jax.nn.silu(gate) * up * weights[..., None].astype(h.dtype)
            out = jnp.einsum("btef,efd->btd", act, kern("down"))
    if cfg.shared_d_ff:
        with jax.named_scope("moe_shared"):
            shared = jax.nn.silu(_proj(h, layer_params["shared_gate"]["kernel"])) \
                * _proj(h, layer_params["shared_up"]["kernel"])
            out = out + _proj(shared, layer_params["shared_down"]["kernel"])
    return out, counts


def _mlp_block(x, layer_params, cfg: ModelConfig, valid=None, tally: Optional[list] = None,
               dense: bool = False):
    """THE block after the mixer, for every kind of layer: ``x`` plus the
    (residual-scaled) dense MLP of its norm — a stack without experts, and a
    mixture's ``dense`` layers (the leading "mla_dense" ones, whose leaves are
    a dense SwiGLU's) — or a mixture's routed experts and
    shared expert (:func:`_moe_mlp_decode`, under scope ``moe``). ``valid``
    [B, T] marks the real positions (None: all). A mixture appends its
    layer's :data:`MOE_COUNTS` to ``tally``, the list the caller that counts
    hands in (:func:`scan_layers`; traced values, read in the same trace)."""
    if dense or not cfg.is_moe:
        with jax.named_scope("mlp"):
            return _residual(x, _dense_mlp(_norm(x, layer_params["mlp_norm"], cfg),
                                           layer_params, cfg=cfg), cfg)
    with jax.named_scope("moe"):
        valid = jnp.ones(x.shape[:2], bool) if valid is None else valid
        out, counts = _moe_mlp_decode(_norm(x, layer_params["mlp_norm"], cfg), layer_params, cfg, valid)
        if tally is not None:
            tally.append(counts)
        return _residual(x, out, cfg)


def _quantize_rows(rows: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric int8 quantisation over the trailing (head_dim) axis:
    rows [B, T, KV, HD] → (int8 codes, fp32 scales [B, T, KV, 1])."""
    scale = jnp.max(jnp.abs(rows.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    codes = jnp.clip(jnp.round(rows.astype(jnp.float32) / scale), -127, 127)
    return codes, scale


def _heads_a_group(cfg: ModelConfig) -> int:
    """kv-heads one 128-value column group of a cache row holds (1 for heads of 128 or wider)."""
    return max(lane_decode.COLUMNS // cfg.head_dim, 1)


def _grouped_queries(q, cfg: ModelConfig):
    """One step's queries q [B, H, HD] as the rows that attend each COLUMN GROUP
    of a cache row ``[KV x HD]`` as it lies (``ops.lane_decode``): [B, P, R, W].
    A head of 128 values (or a multiple) is a group of its own, its rows the
    kv-head's G queries; narrower heads lie ``128 // HD`` to a group, and a row
    is its query in its own head's columns and zero in the others' (``q_a | 0``
    for the first head's G queries, ``0 | q_b`` for the second's: the packing
    of :func:`_diff_queries`), so that the group's keys are contracted whole."""
    B = q.shape[0]
    KV, HD, G = cfg.n_kv_heads, cfg.head_dim, cfg.n_heads // cfg.n_kv_heads
    per = _heads_a_group(cfg)
    qg = q.reshape(B, KV // per, per, G, HD)
    if per > 1:
        qg = jnp.einsum("bpigd,ij->bpigjd", qg, jnp.eye(per, dtype=q.dtype))
    return qg.reshape(B, KV // per, per * G, per * HD)


def _grouped_outputs(a, cfg: ModelConfig):
    """:func:`_grouped_queries`' rows back: a [B, P, R, W] -> [B, H x HD], of
    each row the columns of its own head."""
    B = a.shape[0]
    KV, HD, G = cfg.n_kv_heads, cfg.head_dim, cfg.n_heads // cfg.n_kv_heads
    per = _heads_a_group(cfg)
    if per > 1:
        a = jnp.einsum("bpigjd,ij->bpigd", a.reshape(B, KV // per, per, G, per, HD),
                       jnp.eye(per, dtype=a.dtype))
    return a.reshape(B, KV * G * HD)


def lane_walk_engages(k_cache, T: int, cfg: ModelConfig) -> bool:
    """Whether a step's read of the ``attn`` kind's keys and values goes
    through ``ops.lane_decode`` (decided from what the trace sees): one query a
    row, a bfloat16 stack of whole blocks and column groups on a TPU
    (``lane_decode.engages``), kv-heads that fill whole column groups, and no
    sliding window shorter than the row's lanes (none binds: a shorter one
    keeps XLA's masked contractions)."""
    KV, HD, M = cfg.n_kv_heads, cfg.head_dim, k_cache.shape[2]
    per = _heads_a_group(cfg)
    return (T == 1 and k_cache.dtype == jnp.bfloat16 and lane_decode.engages(k_cache)
            and (HD * per) % lane_decode.COLUMNS == 0 and KV % per == 0
            and not 0 < cfg.sliding_window < M)


def _decode_block(x, layer_params, k_cache, v_cache, write, slot_pos, positions,
                  cfg: ModelConfig, k_scale_c=None, v_scale_c=None, read=None,
                  valid=None, tally=None, at=None, visible=None):
    """One transformer block attending against the cache as stored.

    Attention contracts the query heads, grouped by the KV head they share,
    straight against ``k_cache`` / ``v_cache``: ``H = KV × G`` KV-major —
    query head ``h`` reads KV head ``h // G``, the order training's repeat
    implies — so the G-fold keys and values are never built, and MHA is the
    case ``G = 1`` of the same two contractions.

    x: [B, T, D] new activations. ``k_cache`` / ``v_cache`` are whatever
    ``write`` takes and returns, and ``read(cache_arr)`` gives this layer's
    [B, M, KV x HD] of it: a lane's row holds its kv-heads side by side, as
    the newer positional kinds' do. Every walk of a stack (:func:`scan_layers`)
    hands the WHOLE ``[L, B, M, KV x HD]`` cache: ``write(cache_arr, rows)``
    stores the chunk's rows [B, T, KV x HD] in this layer's lanes only, in
    place, and the layer is never written back whole. It is READ by one of
    two paths that give the same numbers:

    - a decode step (T = 1) of a walk that says how many leading lanes each
      row sees (``visible`` [B] int32, lane m holding position m; 0: the row
      does not decode) and which layer this is (``at``), where
      :func:`lane_walk_engages`: the kernel ``ops.lane_decode`` (profile name
      ``attn_decode``) reads the blocks of 512 lanes a row's length covers from
      the stack where it lies, once; a row that does not decode is neither
      read nor computed, and its attention is zeros;
    - anywhere else (a chunk, a verify pass, a ring or int8 or mesh-sharded
      cache, off the TPU): ``read`` slices the layer out for XLA's two
      contractions over every lane, masked by ``slot_pos`` — the plain
      statement of what the kernel computes. ``read=None`` is the identity: the
      arrays are then one layer's own [B, M, KV x HD] (the tests'
      layer-by-layer references).

    ``slot_pos`` is the global position held by each cache slot after this
    chunk's writes — [M] (all rows in lockstep, the generate() case) or
    [B, M] (per-row positions, the continuous-batching slot pool in
    ``tpu_engine/serving.py``).
    ``k_scale_c``/``v_scale_c`` are present for int8 caches, which keep
    ``[.., M, KV, HD]`` codes beside ``[.., M, KV, 1]`` scales: new rows
    [B, T, KV, HD] are quantised before the write and the cache reads
    dequantise (the convert+mul fuses into the attention dots). ``valid`` /
    ``tally`` are :func:`_mlp_block`'s.
    """
    B, T, D = x.shape
    H, KV, HD = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    gpt2 = cfg.arch == "gpt2"

    def proj(h, name):
        return _proj(h, layer_params[name]["kernel"],
                     bias=layer_params[name]["bias"] if gpt2 else None)

    # named_scope is metadata only: the names a profile groups device ops by
    # (attn > kv_write / decode_attn, then mlp or moe > moe_router / moe_experts
    # / moe_shared).
    with jax.named_scope("attn"):
        h = _norm(x, layer_params["attn_norm"], cfg)
        q = proj(h, "q").reshape(B, T, H, HD)
        k = proj(h, "k").reshape(B, T, KV, HD)
        v = proj(h, "v").reshape(B, T, KV, HD)
        if cfg.arch == "qwen":  # per-head qk-norm, before RoPE (as in training)
            q = _rms_norm(q, layer_params["q_norm"]["scale"], cfg.norm_eps)
            k = _rms_norm(k, layer_params["k_norm"]["scale"], cfg.norm_eps)
        if cfg.rope and not gpt2:  # gpt2 adds learned positions at embed time instead
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)

        with jax.named_scope("kv_write"):
            if k_scale_c is not None:
                k_codes, k_s = _quantize_rows(k)
                v_codes, v_s = _quantize_rows(v)
                k_cache = write(k_cache, k_codes)
                v_cache = write(v_cache, v_codes)
                k_scale_c = write(k_scale_c, k_s)
                v_scale_c = write(v_scale_c, v_s)
            else:
                k_cache = write(k_cache, k.reshape(B, T, KV * HD))
                v_cache = write(v_cache, v.reshape(B, T, KV * HD))

        scale = attention_scale(cfg)
        with jax.named_scope("decode_attn"):
            if visible is not None and k_scale_c is None and lane_walk_engages(k_cache, T, cfg):
                attn = _grouped_outputs(
                    lane_decode.lane_decode(_grouped_queries(q[:, 0], cfg), k_cache, v_cache, at, visible,
                                            scale=scale, name="attn_decode"), cfg)[:, None].astype(x.dtype)
            else:
                read = read or (lambda a: a)
                kc, vc = read(k_cache), read(v_cache)
                if k_scale_c is not None:
                    kc = kc.astype(x.dtype) * read(k_scale_c).astype(x.dtype)
                    vc = vc.astype(x.dtype) * read(v_scale_c).astype(x.dtype)
                # ONE layer's view (or the staging row's) by kv-head, never the stack's
                kc, vc = (a.reshape(*a.shape[:2], KV, HD) for a in (kc, vc))
                qg = q.reshape(B, T, KV, H // KV, HD)  # KV-major groups
                scores = jnp.einsum(
                    "btkgd,bmkd->bkgtm", qg, kc, preferred_element_type=jnp.float32
                ) * scale
                # Slot m is visible to query t iff it holds a real position (≥ 0)
                # that is ≤ the query's global position (causal). Sliding-window
                # models additionally hide keys older than the window, matching
                # the training-time mask; ring-buffer slots overwritten by
                # in-chunk later positions are masked for earlier queries by the
                # same comparison.
                key_pos = slot_pos if slot_pos.ndim == 2 else slot_pos[None, :]  # [B|1, M]
                kp = key_pos[:, None, :]                                         # [B|1, 1, M]
                mask = (kp >= 0) & (kp <= positions[:, :, None])
                if cfg.sliding_window:
                    mask &= kp > positions[:, :, None] - cfg.sliding_window
                scores = jnp.where(mask[:, None, None, :, :], scores, _NEG_INF)
                probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(x.dtype)
                attn = jnp.einsum("bkgtm,bmkd->btkgd", probs, vc).reshape(B, T, H * HD)
        x = _residual(x, proj(attn, "o"), cfg)

    x = _mlp_block(x, layer_params, cfg, valid, tally)
    return x, k_cache, v_cache, k_scale_c, v_scale_c


# ---------------------------------------------------------------------------
# Mamba-2 layers (hybrid stacks)
# ---------------------------------------------------------------------------


def layer_slice(a: jax.Array, at) -> jax.Array:
    """Layer ``at`` of an array stacked over a kind's layers."""
    return lax.dynamic_index_in_dim(a, at, 0, keepdims=False)


def _time_blocks(a, size: int, mode: str = "constant"):
    """[B,T,...] -> [n,B,size,...]: the time axis cut into ``n`` blocks of
    ``size``, the last padded (zeros, or ``mode="edge"``: its last entry)."""
    B, T = a.shape[:2]
    n = -(-T // size)
    a = jnp.pad(a, ((0, 0), (0, n * size - T)) + ((0, 0),) * (a.ndim - 2), mode=mode)
    return jnp.moveaxis(a.reshape(B, n, size, *a.shape[2:]), 1, 0)


def _ssd_chunk(x, dt, A, Bm, Cm, h):
    """One chunk of the selective scan in its chunked (SSD) form.

    x [B,Q,H,P]; dt [B,Q,H] float32, 0 where a position must leave the state
    as it was; A [H] (negative); Bm, Cm [B,Q,N] (one group, shared by the
    heads: Mamba-2) or [B,Q,H,N] (per head: lightning attention, which is
    this scan with ``dt = 1``, ``A = -rate``, B = k, C = q, x = v);
    h [B,H,P,N] float32, the state entering. Returns (y [B,Q,H,P]
    float32 without the skip term, the state leaving).

    Inside the chunk ``Y = (L o C B^T)(dt x) + diag(exp(cumsum dt A)) C h``
    with ``L[t,s] = exp(sum_{s<r<=t} dt_r A)`` for s <= t: two matmuls and
    a masked decay instead of Q sequential updates. The decay between two
    positions comes from the DIFFERENCE of their cumulative sums (never
    ``d^t d^-s``, which leaves float32 within a chunk for a fast head).
    Decays, their cumulative sums and the state stay float32; the matmul
    operands are the compute dtype's, accumulated in float32."""
    cd = x.dtype
    Q = x.shape[1]
    g = "h" if Bm.ndim == 4 else ""  # B and C's head axis, where they have one
    cum = jnp.cumsum(dt * A, axis=1)                              # [B,Q,H] <= 0
    seg = cum[:, :, None, :] - cum[:, None, :, :]                 # [B,t,s,H]
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum(f"bt{g}n,bs{g}n->bts{g}", Cm, Bm, preferred_element_type=jnp.float32)
    xdt = x.astype(jnp.float32) * dt[..., None]                   # [B,Q,H,P]
    y = jnp.einsum("btsh,bshp->bthp", (decay * (cb if g else cb[..., None])).astype(cd),
                   xdt.astype(cd), preferred_element_type=jnp.float32)
    # What the entering state still contributes at t, read in float32.
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        f"bt{g}n,bhpn->bthp", Cm.astype(jnp.float32), h,
        precision=lax.Precision.HIGHEST)
    to_end = jnp.exp(cum[:, -1:, :] - cum)                        # [B,Q,H]
    h = h * jnp.exp(cum[:, -1, :])[:, :, None, None] + jnp.einsum(
        f"bshp,bs{g}n->bhpn", (xdt * to_end[..., None]).astype(cd), Bm,
        preferred_element_type=jnp.float32)
    return y, h


def _ssd_scan(x, dt, A, Bm, Cm, h, chunk: int):
    """:func:`_ssd_chunk` over T positions, ``chunk`` at a time (the last
    chunk padded with ``dt = 0``: dead positions). Returns (y [B,T,H,P], h)."""
    B, T, H, P = x.shape
    Q = min(chunk, T)
    if T == Q:
        return _ssd_chunk(x, dt, A, Bm, Cm, h)

    def step(h, xs):
        y, h = _ssd_chunk(xs[0], xs[1], A, xs[2], xs[3], h)
        return h, y

    h, y = lax.scan(step, h, tuple(_time_blocks(a, Q) for a in (x, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1).reshape(B, -1, H, P)[:, :T], h


def _ssd_step(x, dt, A, Bm, Cm, h):
    """One recurrence step, all float32 elementwise (it is bound by reading
    and writing ``h``): x [B,H,P], dt [B,H], Bm, Cm [B,N] or [B,H,N],
    h [B,H,P,N]. Returns (y [B,H,P], h)."""
    f32 = jnp.float32

    def over_p(a):  # -> broadcastable against [B,H,P,N]
        return a.astype(f32)[:, None, None, :] if a.ndim == 2 else a.astype(f32)[:, :, None, :]

    dbx = (dt[:, :, None] * x.astype(f32))[..., None] * over_p(Bm)
    h = h * jnp.exp(dt * A)[:, :, None, None] + dbx
    return jnp.sum(h * over_p(Cm), axis=-1), h


def _ssd_step_at(x, dt, A, Bm, Cm, state, at):
    """:func:`_ssd_step` of layer ``at`` of a WHOLE kind's stacked state
    [L,B,H,P,N], written back into the stack. Where the one-pass kernel
    engages (``ops.ssd_update.engages``: a float32 stack of whole ``[P,N]``
    register tiles, on a TPU) it updates the stack's blocks of that layer where
    they lie; anywhere else this is the XLA step on the layer's slice, which
    stays the plain statement of what the kernel computes. Returns (y, state)."""
    if ssd_update.engages(state):
        return ssd_update.ssd_update(x, dt, A, Bm, Cm, state, at)
    # the recurrence runs in float32 whatever the cache stores
    y, h = _ssd_step(x, dt, A, Bm, Cm, layer_slice(state, at).astype(jnp.float32))
    return y, lax.dynamic_update_index_in_dim(state, h.astype(state.dtype), at, 0)


def _ssm_mixer(u, lp, ssm, conv_state, at, valid, cfg: ModelConfig):
    """The Mamba-2 mixer over ``u`` [B,T,D] (already normed), from and into
    layer ``at``'s recurrent state: its slice [B,H,P,N] of ``ssm``, the kind's
    whole stack [L,B,H,P,N], rewritten in place under the scope of the step
    that does it (``ssm_update`` for one token, ``ssm_scan`` for a chunk, so
    that a profile charges the state's traffic to the mixer), and
    ``conv_state`` [B,taps-1,C], the layer's own. ``valid`` [B,T] marks the
    real positions, a PREFIX of each row (pad tokens after a prompt's end; a
    decode row that is not active): a position that is not valid leaves both
    states exactly as they were — ``dt = 0`` there, so the decay is 1 and
    nothing is added, and the convolution state is taken at the row's true
    length. Outputs at such positions are garbage the caller never reads.

    T = 1 is the decode update (one recurrence step, all float32
    elementwise: it is bound by reading and writing the state;
    :func:`_ssd_step_at`); longer T runs the chunked form ``cfg.ssm_chunk``
    positions at a time. Returns (out, ssm, conv_state)."""
    B, T, _ = u.shape
    H, P, N, K = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_conv
    I, C = cfg.ssm_inner, cfg.ssm_conv_dim
    f32 = jnp.float32

    with jax.named_scope("ssm_in_proj"):
        zxbcdt = _proj(u, lp["in_proj"]["kernel"])               # [B,T,I+C+H]
        z, xbc, dt = (zxbcdt[..., :I], zxbcdt[..., I:I + C], zxbcdt[..., I + C:])

    with jax.named_scope("ssm_conv"):
        # Causal depthwise convolution over the last taps-1 inputs and the new.
        window = jnp.concatenate([conv_state.astype(xbc.dtype), xbc], axis=1)
        w = lp["conv"]["kernel"].astype(f32)                      # [K, C]
        acc = lp["conv"]["bias"].astype(f32)
        for k in range(K):
            acc = acc + window[:, k:k + T].astype(f32) * w[k]
        xbc = jax.nn.silu(acc).astype(u.dtype)
        if T == 1:
            conv_state = jnp.where(valid[:, :, None], window[:, 1:], window[:, :-1])
        else:
            n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)
            conv_state = jax.vmap(
                lambda win, n: lax.dynamic_slice_in_dim(win, n, K - 1, 0)
            )(window, n_valid)
    x = xbc[..., :I].reshape(B, T, H, P)
    Bm, Cm = xbc[..., I:I + N], xbc[..., I + N:]
    dt = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32))
    dt = jnp.where(valid[:, :, None], dt, 0.0)                    # [B,T,H]
    A = -jnp.exp(lp["A_log"].astype(f32))                         # [H]

    if T == 1:
        with jax.named_scope("ssm_update"):
            y, ssm = _ssd_step_at(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], ssm, at)
            y = y[:, None]
    else:
        with jax.named_scope("ssm_scan"):
            # the recurrence runs in float32 whatever the cache stores
            y, h = _ssd_scan(x, dt, A, Bm, Cm, layer_slice(ssm, at).astype(f32), cfg.ssm_chunk)
            ssm = lax.dynamic_update_index_in_dim(ssm, h.astype(ssm.dtype), at, 0)
    y = y + lp["D"].astype(f32)[:, None] * x.astype(f32)

    with jax.named_scope("ssm_gate_norm"):
        g = y.reshape(B, T, I) * jax.nn.silu(z.astype(f32))
        g = g * lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True) + cfg.norm_eps)
        o = (g * lp["gate_norm"]["scale"].astype(f32)).astype(u.dtype)
    with jax.named_scope("ssm_out_proj"):
        return _proj(o, lp["out_proj"]["kernel"]), ssm, conv_state


def _ssm_block(x, layer_params, ssm, conv, at, valid, cfg: ModelConfig, tally=None):
    """One Mamba-2 layer: the mixer where an attention layer attends, then
    the block every kind shares (:func:`_mlp_block`). ``ssm`` / ``conv`` are
    the whole per-kind state ([L_ssm, B, ...]); this layer reads and rewrites
    its own slice, ``at``, in place (the mixer that of ``ssm``).
    Returns (x, ssm, conv)."""
    with jax.named_scope("ssm"):
        u = _norm(x, layer_params["ssm_norm"], cfg)
        out, ssm, conv_state = _ssm_mixer(u, layer_params, ssm, layer_slice(conv, at),
                                          at, valid, cfg)
        with jax.named_scope("ssm_conv"):
            conv = lax.dynamic_update_index_in_dim(conv, conv_state.astype(conv.dtype), at, 0)
        x = _residual(x, out, cfg)
    return _mlp_block(x, layer_params, cfg, valid, tally), ssm, conv


# ---------------------------------------------------------------------------
# Lightning (linear) attention layers
# ---------------------------------------------------------------------------


def _lightning_block(x, lp, state, at, positions, valid, cfg: ModelConfig, tally=None):
    """One lightning-attention layer, then the block every kind has
    (:func:`_mlp_block`).

    ``q_t = rope(norm(u_t Wq))``, ``k_t`` alike, ``v_t = u_t Wv`` per head;
    ``S_t = d S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t / sqrt(E)``; the output
    is ``(RMSNorm(o_t) * sigmoid(u_t Wgate)) Wo``, the norm over the whole
    inner width. ``state`` is the kind's whole leaf [L, B, H, E, E] float32,
    a head's ``S`` transposed (value x key, as a Mamba-2 state is [P, N]);
    this layer reads and rewrites its own slice, ``at``, under the scope of
    the step that does it: ``lightning_update`` for one token
    (:func:`_ssd_step_at`), ``lightning_scan`` for a chunk (:func:`_ssd_scan`,
    ``cfg.ssm_chunk`` positions at a time). ``valid`` [B,T] marks the real
    positions, a PREFIX of each row: a position that is not valid leaves
    the state exactly as it was. Returns (x, state)."""
    B, T, _ = x.shape
    H, E = cfg.lightning_heads, cfg.lightning_head_dim
    f32 = jnp.float32
    with jax.named_scope("lightning"):
        u = _norm(x, lp["attn_norm"], cfg)
        with jax.named_scope("lightning_qkv"):
            def heads(name):
                return _proj(u, lp[name]["kernel"]).reshape(B, T, H, E)

            q = _rope(_rms_norm(heads("q"), lp["q_norm"]["scale"], cfg.norm_eps),
                      positions, cfg.rope_theta)
            k = _rope(_rms_norm(heads("k"), lp["k_norm"]["scale"], cfg.norm_eps),
                      positions, cfg.rope_theta)
            v = heads("v")
            gate = jax.nn.sigmoid(_proj(u, lp["o_gate"]["kernel"]).astype(f32))
        # The selective scan with dt = 1 at a real position (0 at one that is
        # not), A = -rate, B = k, C = q per head and x = v.
        A = -lp["decay"].astype(f32)                              # [H]
        dt = jnp.broadcast_to(valid.astype(f32)[..., None], (B, T, H))
        with jax.named_scope("lightning_update" if T == 1 else "lightning_scan"):
            if T == 1:
                o, state = _ssd_step_at(v[:, 0], dt[:, 0], A, k[:, 0], q[:, 0], state, at)
                o = o[:, None]
            else:
                o, h = _ssd_scan(v, dt, A, k, q, layer_slice(state, at), cfg.ssm_chunk)
                state = lax.dynamic_update_index_in_dim(state, h, at, 0)
        with jax.named_scope("lightning_gate_norm"):
            o = o.reshape(B, T, H * E) * (E ** -0.5)
            o = o * lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + cfg.norm_eps)
            o = (o * lp["out_norm"]["scale"].astype(f32) * gate).astype(x.dtype)
        with jax.named_scope("lightning_out_proj"):
            x = _residual(x, _proj(o, lp["o"]["kernel"]), cfg)
    return _mlp_block(x, lp, cfg, valid, tally), state


# ---------------------------------------------------------------------------
# Gated power-retention layers
# ---------------------------------------------------------------------------


def power_expand(y, cfg: ModelConfig, dtype=jnp.float32):
    """``phi(y)``: the tiled symmetric square of ``y`` [..., HD] — ``HD`` cut
    in tiles of ``POWER_TILE``; for each pair of tiles a <= b
    (``cfg.power_tile_pairs``, in that order) the ``tile^2`` products ``y_a (x)
    y_b``, times sqrt(2) where a < b — so that ``phi(y) . phi(z) = (y . z)^2``
    exactly. [..., ``cfg.power_state_width``], formed in float32 and handed
    over in ``dtype`` (a matmul operand is rounded where it is made, not
    written out in float32 first)."""
    t = POWER_TILE
    tiles = y.astype(jnp.float32).reshape(*y.shape[:-1], cfg.head_dim // t, t)
    a, b = (jnp.asarray(ix, jnp.int32) for ix in zip(*cfg.power_tile_pairs))
    weight = jnp.where(a == b, 1.0, 2.0 ** 0.5)[:, None]          # folded into the rows: one product a coordinate
    blocks = jnp.take(tiles, a, axis=-2)[..., :, None] * (jnp.take(tiles, b, axis=-2) * weight)[..., None, :]
    return blocks.astype(dtype).reshape(*y.shape[:-1], cfg.power_state_width)


def _power_step(q, k, v, log_g, w, state, norm, cfg: ModelConfig):
    """One recurrence step, all float32 (it is bound by reading and writing
    the state): q [B,KV,G,HD], k, v [B,KV,HD], ``log_g`` [B,KV] the gate's log
    (0 for a row that keeps its state), ``w`` [B,KV] what the token adds with
    (1/HD, 0 for such a row), state [B,KV,HD,W], norm [B,KV,W]. Returns the
    numerator [B,KV,G,HD], the normaliser [B,KV,G] and the two advanced."""
    fk = power_expand(k, cfg) * w[..., None]                      # [B,KV,W]
    g = jnp.exp(log_g)
    state = state * g[..., None, None] + v.astype(jnp.float32)[..., :, None] * fk[..., None, :]
    norm = norm * g[..., None] + fk
    fq = power_expand(q, cfg)                                     # [B,KV,G,W]
    num = jnp.einsum("bkgw,bkpw->bkgp", fq, state, precision=lax.Precision.HIGHEST)
    den = jnp.einsum("bkgw,bkw->bkg", fq, norm, precision=lax.Precision.HIGHEST)
    return num, den, state, norm


def _power_step_at(q, k, v, log_g, w, state, norm, at, cfg: ModelConfig):
    """:func:`_power_step` of layer ``at`` of the kind's stacked leaves
    (``state`` [L,B,KV,HD,W], ``norm`` [L,B,KV,W]), written back into them.
    Where the one-pass kernel engages (``ops.power_update.engages``) it
    rewrites that layer's blocks where they lie and expands ``phi`` itself;
    anywhere else this is the XLA step on the layer's slices, the plain
    statement of what the kernel computes."""
    if power_update.engages(state, POWER_TILE):
        return power_update.power_update(q, k, v, log_g, w, state, norm, at, tile=POWER_TILE)
    num, den, h, z = _power_step(q, k, v, log_g, w, layer_slice(state, at).astype(jnp.float32),
                                 layer_slice(norm, at).astype(jnp.float32), cfg)
    return (num, den, lax.dynamic_update_index_in_dim(state, h.astype(state.dtype), at, 0),
            lax.dynamic_update_index_in_dim(norm, z.astype(norm.dtype), at, 0))


def _power_chunk(q, k, v, log_g, w, state, norm, cfg: ModelConfig):
    """One chunk of Q positions in the chunked form: scores ``(q . k)^2 / HD``
    with the gate's decay inside the chunk (never the expansion: 128
    multiply-adds a pair, not 9 216), the entering state queried through
    ``phi(q)`` for what came before, the state advanced by the chunk's decayed
    ``v (x) phi(k)``.

    q [B,Q,KV,G,HD], k, v [B,Q,KV,HD] (compute dtype); ``log_g`` [B,Q,KV]
    float32 <= 0 and ``w`` [B,Q,KV] (1/HD; both 0 where a position must leave
    the state as it was); state [B,KV,HD,W], norm [B,KV,W] float32. Returns
    (numerator [B,Q,KV,G,HD], normaliser [B,Q,KV,G], state, norm), float32.

    Decays come from the DIFFERENCE of cumulative sums (as :func:`_ssd_chunk`);
    matmul operands are the compute dtype's, accumulated in float32; the
    float32 state is read as two such terms (its rounding and the rest), so
    that what it carried is not cut to a bfloat16's eight bits."""
    cd, f32 = q.dtype, jnp.float32
    Q, HD = q.shape[1], cfg.head_dim
    cum = jnp.cumsum(log_g, axis=1)                                # [B,Q,KV] <= 0
    seg = cum[:, :, None, :] - cum[:, None, :, :]                  # [B,t,s,KV]
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf)) * w[:, None, :, :]
    s = jnp.einsum("btkgd,bskd->bkgts", q, k, preferred_element_type=f32)
    p = jnp.square(s) * jnp.moveaxis(decay, 3, 1)[:, :, None]      # [B,KV,G,t,s]
    num = jnp.einsum("bkgts,bskd->btkgd", p.astype(cd), v, preferred_element_type=f32)
    den = jnp.moveaxis(jnp.sum(p, axis=-1), 3, 1)                  # [B,t,KV,G]
    # What the entering state still contributes at t: one contraction over
    # the expansion for the values and the normaliser alike.
    held = jnp.concatenate([state, norm[:, :, None, :]], axis=2)   # [B,KV,HD+1,W]
    fq = power_expand(q, cfg, cd)                                  # [B,Q,KV,G,W]
    if cd == f32:
        past = jnp.einsum("btkgw,bkpw->btkgp", fq, held, precision=lax.Precision.HIGHEST)
    else:
        hi = held.astype(cd)
        both = jnp.concatenate([hi, (held - hi.astype(f32)).astype(cd)], axis=2)
        past = jnp.einsum("btkgw,bkpw->btkgp", fq, both, preferred_element_type=f32)
        past = past[..., :HD + 1] + past[..., HD + 1:]
    past = past * jnp.exp(cum)[..., None, None]
    num, den = num + past[..., :HD], den + past[..., HD]
    to_end = jnp.exp(cum[:, -1:, :] - cum) * w                     # [B,Q,KV]
    fk = power_expand(k, cfg) * to_end[..., None]                  # [B,Q,KV,W]
    left = jnp.exp(cum[:, -1, :])                                  # [B,KV]
    state = state * left[..., None, None] + jnp.einsum(
        "bskp,bskw->bkpw", v, fk.astype(cd), preferred_element_type=f32)
    norm = norm * left[..., None] + jnp.sum(fk, axis=1)
    return num, den, state, norm


def _power_scan(q, k, v, log_g, w, state, norm, cfg: ModelConfig):
    """:func:`_power_chunk` over T positions, ``cfg.ssm_chunk`` at a time (the
    last chunk padded with dead positions: ``log_g = w = 0``)."""
    B, T = q.shape[:2]
    Q = min(cfg.ssm_chunk, T)
    if T == Q:
        return _power_chunk(q, k, v, log_g, w, state, norm, cfg)

    def step(carry, xs):
        num, den, h, z = _power_chunk(*xs, *carry, cfg)
        return (h, z), (num, den)

    (state, norm), (num, den) = lax.scan(
        step, (state, norm), tuple(_time_blocks(a, Q) for a in (q, k, v, log_g, w)))
    flat = lambda a: jnp.moveaxis(a, 0, 1).reshape(B, -1, *a.shape[3:])[:, :T]  # noqa: E731
    return flat(num), flat(den), state, norm


def _power_block(x, lp, state, norm, at, positions, valid, cfg: ModelConfig, tally=None):
    """One gated power-retention layer (degree 2), then the block every kind
    has (:func:`_mlp_block`).

    ``q_t`` (``n_heads``), ``k_t``, ``v_t`` (``n_kv_heads``) from ``u_t =
    norm(x_t)``; q and k per-head normed and rotated; ``log gamma_t = log
    sigmoid(u_t Wg + bg)`` per kv-head, float32. Query head i of kv-head h
    reads ``o_t = sum_s w_ts v_s / (sum_s w_ts + eps)`` with ``w_ts =
    (prod_{s<r<=t} gamma_r) (q_t . k_s)^2 / HD`` — held as the state ``S_t =
    gamma_t S_{t-1} + v_t (x) phi(k_t) / HD`` and its normaliser ``z_t`` alike
    (``phi``: :func:`power_expand`), which every one of the kv-head's query
    heads reads through its own ``phi(q_t)``.

    ``state`` [L,B,KV,HD,W] and ``norm`` [L,B,KV,W] are the kind's whole
    leaves, float32; this layer reads and rewrites its own slice, ``at``,
    under the scope of the step that does it: ``power_update`` for one token
    (:func:`_power_step_at`), ``power_scan`` for a chunk (:func:`_power_scan`).
    ``valid`` [B,T] marks the real positions, a PREFIX of each row: a position
    that is not valid leaves the state exactly as it was (``gamma = 1``,
    nothing added). Returns (x, state, norm)."""
    B, T, _ = x.shape
    H, KV, HD = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    f32 = jnp.float32
    with jax.named_scope("power"):
        u = _norm(x, lp["attn_norm"], cfg)
        with jax.named_scope("power_qkvg"):
            def heads(name, n):
                return _proj(u, lp[name]["kernel"]).reshape(B, T, n, HD)

            q = _rope(_rms_norm(heads("q", H), lp["q_norm"]["scale"], cfg.norm_eps),
                      positions, cfg.rope_theta).reshape(B, T, KV, H // KV, HD)
            k = _rope(_rms_norm(heads("k", KV), lp["k_norm"]["scale"], cfg.norm_eps),
                      positions, cfg.rope_theta)
            v = heads("v", KV)
            logit = jnp.einsum("btd,dk->btk", u, lp["g_proj"]["kernel"],
                               preferred_element_type=f32) + lp["g_bias"].astype(f32)
            log_g = jnp.where(valid[..., None], jax.nn.log_sigmoid(logit), 0.0)   # [B,T,KV]
            w = jnp.broadcast_to(valid.astype(f32)[..., None] / HD, (B, T, KV))
        with jax.named_scope("power_update" if T == 1 else "power_scan"):
            if T == 1:
                num, den, state, norm = _power_step_at(
                    q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], w[:, 0], state, norm, at, cfg)
                num, den = num[:, None], den[:, None]
            else:
                # the recurrence runs in float32 whatever the cache stores
                num, den, h, z = _power_scan(q, k, v, log_g, w, layer_slice(state, at).astype(f32),
                                             layer_slice(norm, at).astype(f32), cfg)
                state = lax.dynamic_update_index_in_dim(state, h.astype(state.dtype), at, 0)
                norm = lax.dynamic_update_index_in_dim(norm, z.astype(norm.dtype), at, 0)
        with jax.named_scope("power_norm"):
            o = (num / (den[..., None] + POWER_NORM_EPS)).reshape(B, T, H * HD).astype(x.dtype)
        with jax.named_scope("power_out_proj"):
            x = _residual(x, _proj(o, lp["o"]["kernel"]), cfg)
    return _mlp_block(x, lp, cfg, valid, tally), state, norm


# ---------------------------------------------------------------------------
# Block-sparse attention layers
# ---------------------------------------------------------------------------

# Queries a prefill chunk's indexer scores at a time (float32, for every head
# against every compressed key of the row).
_SPARSE_QUERY_BLOCK = 128
_FORCED = 1e30  # a forced block's score: above any sum of probabilities


def _write_compressed_keys(k_pool, ck_pool, at, positions, valid, cfg: ModelConfig):
    """Write layer ``at``'s compressed key of every window whose LAST lane this
    call wrote: window m is the mean of the ``sparse_kernel_size`` keys from
    lane ``stride x m`` on, so it completes when position ``stride x m + size
    - 1`` arrives — in the middle of a prefill chunk, across two chunks (its
    first lanes are then read back from the pool), or on a decode step. A
    window that ends at a pad or on a row that is not ``valid`` is not written
    (decode writes it when it gets there). k_pool [L,B,S,W] with this call's
    keys already in it; ck_pool [L,B,S/stride,W]; positions [B,T] contiguous."""
    size, stride = cfg.sparse_kernel_size, cfg.sparse_kernel_stride
    B, T = positions.shape
    rows = jnp.arange(B)
    t0 = positions[:, :1]                                         # [B,1]
    first = jnp.maximum((t0 - size + stride) // stride, 0)        # first window ending at or after t0
    m = first + jnp.arange(-(-T // stride))                       # [B,W]
    end = stride * m + size - 1
    done = (end < t0 + T) & jnp.take_along_axis(valid, jnp.clip(end - t0, 0, T - 1), axis=1)
    lanes = stride * m[..., None] + jnp.arange(size)              # [B,W,size]
    keys = k_pool[at, rows[:, None, None], lanes]                 # [B,W,size,KV*HD]
    ck = jnp.mean(keys.astype(jnp.float32), axis=2).astype(ck_pool.dtype)
    m = jnp.where(done, m, ck_pool.shape[2])                      # out of bounds: dropped
    return ck_pool.at[at, rows[:, None], m].set(ck, mode="drop")


def _select_blocks(qg, ck, positions, n_blocks: int, cfg: ModelConfig):
    """The indexer: which ``sparse_topk`` blocks each query attends.

    qg [B,T,KV,G,HD] (normed queries, grouped by kv-head), ck [B,M,KV,HD]
    (one layer's compressed keys), positions [B,T]. For every query head a
    float32 softmax of ``q . ck[m] / sqrt(HD)`` over the windows that end at
    or before the query, summed over the G heads of a group; a block scores
    the best of the windows that overlap it; the first ``sparse_init_blocks``
    and the last ``sparse_local_blocks`` up to the query's own are forced,
    blocks past the query's own cannot be chosen. Returns block ids
    [B,KV,T,topk] int32, forced ones among them."""
    size, stride, block = cfg.sparse_kernel_size, cfg.sparse_kernel_stride, cfg.sparse_block_size
    per, extra = block // stride, size // stride - 1
    M = ck.shape[1]
    s = jnp.einsum("btkgd,bmkd->bkgtm", qg, ck,
                   preferred_element_type=jnp.float32) * attention_scale(cfg)
    seen = (stride * jnp.arange(M) + size - 1 <= positions[..., None])[:, None, None]  # [B,1,1,T,M]
    p = jnp.where(seen, jax.nn.softmax(jnp.where(seen, s, _NEG_INF), axis=-1), 0.0)
    w = jnp.sum(p, axis=2)                                        # [B,KV,T,M]
    # Window m overlaps block b iff per*b - extra <= m < per*(b + 1).
    w = jnp.pad(w, ((0, 0),) * 3 + ((extra, per * n_blocks - M),))
    score = w[..., 0:per * n_blocks:per]
    for j in range(1, per + extra):
        score = jnp.maximum(score, w[..., j:j + per * n_blocks:per])
    b = jnp.arange(n_blocks)
    own = (positions // block)[:, None, :, None]                  # [B,1,T,1]
    forced = (b < cfg.sparse_init_blocks) | (b > own - cfg.sparse_local_blocks)
    score = jnp.where(b <= own, jnp.where(forced, _FORCED, score), -_FORCED)
    return lax.top_k(score, min(cfg.sparse_topk, n_blocks))[1]  # a short staging row holds fewer


def _sparse_decode(qg, k_pool, v_pool, ck_pool, at, positions, valid, cfg: ModelConfig):
    """One query per row (T = 1) against layer ``at`` of the pool [L,B,S,W],
    W = KV x HD. A row past ``sparse_dense_len`` scores its compressed keys
    and attends the chosen blocks' keys and values (``sparse_topk`` blocks of
    ``sparse_block_size`` lanes per kv-head), which the kernel
    (``ops.sparse_block_attention``) copies out of the pool block by block:
    the rest of the row is never read. Every block of the first
    ``sparse_dense_len`` lanes is attended only while some row is still short
    of that (a row that is not ``valid`` — an empty slot, one still ingesting
    its prompt — stands at position 0 and does not count: what it computes is
    thrown away). Returns [B,KV,G,HD]."""
    B, _, KV, G, HD = qg.shape
    S, block, dense_len = k_pool.shape[2], cfg.sparse_block_size, cfg.sparse_dense_len
    pos = positions[:, 0]
    scale = attention_scale(cfg)
    with jax.named_scope("sparse_index"):
        ck = layer_slice(ck_pool, at).reshape(B, -1, KV, HD)
        ids = _select_blocks(qg, ck, positions, S // block, cfg)[:, :, 0]       # [B,KV,topk]

    q = qg[:, 0]
    interpret = sparse_block_attention.interpret_here()  # refused off the TPU unless asked for

    def attend(ids):  # block ids [B,KV,n] -> [B,KV,G,HD]
        with jax.named_scope("sparse_attend"):
            return sparse_block_attention.sparse_block_attend(
                q, k_pool, v_pool, ids, at, pos, block=block, scale=scale, interpret=interpret)

    out = attend(ids)
    # A row still short of dense_len attends every block up to there: the same
    # kernel over all of them, and only while such a row is in the pool.
    short = pos < dense_len
    n_dense = -(-min(dense_len, S) // block)
    every = jnp.broadcast_to(jnp.arange(n_dense), (B, KV, n_dense))
    full = lax.cond(jnp.any(short & valid[:, 0]), lambda: attend(every), lambda: jnp.zeros_like(out))
    return jnp.where(short[:, None, None, None], full, out)


def _sparse_prefill(qg, k_pool, v_pool, ck_pool, at, positions, cfg: ModelConfig):
    """A chunk of queries (T > 1) against layer ``at``'s row(s): the indexer,
    ``_SPARSE_QUERY_BLOCK`` queries at a time, says which blocks each query
    attends — its chosen ones past ``sparse_dense_len``, every block up to its
    own below — and one call of the chunk kernel
    (``ops.sparse_block_attention.sparse_chunk_attend``) attends them where
    they lie in the pool: what a position attends is what
    :func:`_sparse_decode` would attend there. Returns [B,T,KV,G,HD]."""
    B, T, KV, G, HD = qg.shape
    block = cfg.sparse_block_size
    n_blocks, Tq = k_pool.shape[2] // block, min(_SPARSE_QUERY_BLOCK, T)
    ck = layer_slice(ck_pool, at).reshape(B, -1, KV, HD)
    blocks = jnp.arange(n_blocks)

    def choose(xs):
        q, pos = xs                                               # [B,Tq,KV,G,HD], [B,Tq]
        ids = _select_blocks(q, ck, pos, n_blocks, cfg)           # [B,KV,Tq,topk]
        chosen = jnp.any(ids[..., None] == blocks, axis=-2)       # [B,KV,Tq,n_blocks]
        chosen |= (pos < cfg.sparse_dense_len)[:, None, :, None]
        # top_k fills a short context's ids with blocks past the query's own: not chosen
        return chosen & (blocks <= (pos // block)[:, None, :, None])

    with jax.named_scope("sparse_index"):
        # the last query block's padding repeats its last query
        chosen = lax.map(choose, (_time_blocks(qg, Tq, "edge"), _time_blocks(positions, Tq, "edge")))
        chosen = jnp.moveaxis(chosen, 0, 2).reshape(B, KV, -1, n_blocks)[:, :, :T]
    with jax.named_scope("sparse_attend"):
        return sparse_block_attention.sparse_chunk_attend(
            qg, k_pool, v_pool, chosen, at, positions, block=block, scale=attention_scale(cfg),
            interpret=sparse_block_attention.interpret_here())


def _sparse_attn_block(x, lp, k_pool, v_pool, ck_pool, at, write, positions, valid,
                       cfg: ModelConfig, tally=None):
    """One block-sparse attention layer, then the block every kind has
    (:func:`_mlp_block`).

    q and k are normed per head and NOT rotated; the attention's output is
    gated by ``sigmoid(u Wgate)`` before ``Wo``. The kind's three leaves are
    the pool's, whole (``[L,B,S,KV x HD]``, compressed keys ``[L,B,S/stride,
    KV x HD]``): ``write`` stores the chunk's keys and values in this layer's
    lanes, the compressed keys of the windows that completed follow
    (:func:`_write_compressed_keys`), and the layer's lanes are only read —
    by one query per row through the chosen blocks (:func:`_sparse_decode`),
    by a chunk through the key tiles its queries chose (:func:`_sparse_prefill`).
    Returns (x, k_pool, v_pool, ck_pool)."""
    B, T, _ = x.shape
    H, KV, HD = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if k_pool.shape[2] % cfg.sparse_block_size:
        raise ValueError(f"a sparse_attention layer's cache must hold whole blocks: {k_pool.shape[2]} "
                         f"lanes are no multiple of sparse_block_size={cfg.sparse_block_size}")
    with jax.named_scope("sparse_attn"):
        u = _norm(x, lp["attn_norm"], cfg)
        q = _rms_norm(_proj(u, lp["q"]["kernel"]).reshape(B, T, H, HD),
                      lp["q_norm"]["scale"], cfg.norm_eps)
        k = _rms_norm(_proj(u, lp["k"]["kernel"]).reshape(B, T, KV, HD),
                      lp["k_norm"]["scale"], cfg.norm_eps)
        v = _proj(u, lp["v"]["kernel"])
        gate = jax.nn.sigmoid(_proj(u, lp["o_gate"]["kernel"]).astype(jnp.float32))
        with jax.named_scope("kv_write"):
            k_pool = write(k_pool, k.reshape(B, T, KV * HD), at)
            v_pool = write(v_pool, v, at)
        with jax.named_scope("sparse_index"):
            ck_pool = _write_compressed_keys(k_pool, ck_pool, at, positions, valid, cfg)
        qg = q.reshape(B, T, KV, H // KV, HD)  # KV-major groups, as _decode_block's
        if T == 1:
            attn = _sparse_decode(qg, k_pool, v_pool, ck_pool, at, positions, valid, cfg)
        else:
            attn = _sparse_prefill(qg, k_pool, v_pool, ck_pool, at, positions, cfg)
        attn = (attn.reshape(B, T, H * HD).astype(jnp.float32) * gate).astype(x.dtype)
        x = _residual(x, _proj(attn, lp["o"]["kernel"]), cfg)
    return _mlp_block(x, lp, cfg, valid, tally), k_pool, v_pool, ck_pool


# ---------------------------------------------------------------------------
# Latent attention (MLA) layers
# ---------------------------------------------------------------------------

# The latent's own RMSNorm: the published modelling code builds it with its
# norm class's default epsilon, not with the configuration's ``rms_norm_eps``.
_LATENT_NORM_EPS = 1e-6

# Queries a chunk's expanded attention scores at a time (float32, for every
# head against every lane of the row).
MLA_QUERY_BLOCK = 512


def _mla_block(x, lp, latent, at, write, slot_pos, positions, valid, cfg: ModelConfig,
               tally=None, dense: bool = False):
    """One latent-attention layer, then its block (:func:`_mlp_block`; the
    dense SwiGLU where ``dense``).

    ``q = h W_q`` per head ``(q_n [nope], q_r [rope])``; ``(c_raw, k_raw) = h
    W_kva``; the cache row of a token is ``RMSNorm(c_raw) | RoPE(k_raw)``
    (``kv_latent_dim`` + ``qk_rope_dim`` values, one rotated key for all
    heads), written by ``write`` into layer ``at`` of ``latent``, the kind's
    whole leaf [L, B, M, latent + rope + zero padding], under scope
    ``mla_latent``. With ``W_kvb = (W_k, W_v)`` per head the scores are ``(q_n .
    (c W_k) + q_r . k_r) / sqrt(nope + rope)`` and the output ``softmax(s) (c
    W_v)``, by one of two paths that give the same numbers:

    - EXPANDED, a chunk (T > 1): the row's lanes go through ``W_kvb`` once
      (``mla_expand``) and the chunk's queries attend the keys and values so
      built (``mla_attend``): on a TPU, for whole tiles, through the flash-style
      kernel ``ops.mla_decode.mla_chunk_attend``; anywhere else in XLA,
      ``MLA_QUERY_BLOCK`` queries at a time against every lane of the row;
    - ABSORBED, decode (T = 1): ``q_a = q_n W_k^T`` (``mla_absorb``), one
      contraction of ``q_a | q_r`` against the latent rows as they lie, the
      probabilities times the same rows (``mla_attend``: the latent is read for
      all heads at once and nothing is expanded), and ``W_v`` on the way out.
      On a TPU the two contractions are the kernel ``ops.mla_decode`` (one pass
      over the lanes a slot has); anywhere else XLA's, over every lane, which
      stay the plain statement of what the kernel computes.

    ``slot_pos`` / ``positions`` mask as in :func:`_decode_block`.
    Returns (x, latent)."""
    B, T, _ = x.shape
    H, N, R, V, C = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_latent_dim
    scale = attention_scale(cfg)
    with jax.named_scope("mla"):
        h = _norm(x, lp["attn_norm"], cfg)
        q = _proj(h, lp["q"]["kernel"]).reshape(B, T, H, N + R)
        q_n, q_r = q[..., :N], _rope(q[..., N:], positions, cfg.rope_theta)
        with jax.named_scope("mla_latent"):
            kva = _proj(h, lp["kv_a"]["kernel"])                      # [B, T, C + R]
            c = _rms_norm(kva[..., :C], lp["kv_norm"]["scale"], _LATENT_NORM_EPS)
            k_r = _rope(kva[:, :, None, C:], positions, cfg.rope_theta)[:, :, 0]
            pad = jnp.zeros((B, T, latent.shape[-1] - C - R), c.dtype)   # the row's zero padding
            latent = write(latent, jnp.concatenate([c, k_r, pad], axis=-1), at)
        w_kvb = lp["kv_b"]["kernel"]
        if isinstance(w_kvb, QuantWeight):
            w_kvb = dequantize_weight(w_kvb, x.dtype)
        w_kvb = w_kvb.reshape(C, H, N + V)
        key_pos = slot_pos if slot_pos.ndim == 2 else slot_pos[None, :]  # [B|1, M]

        def masked_probs(s, pos):  # s [B, H, t, M] float32, pos [B, t]
            kp = key_pos[:, None, :]
            mask = (kp >= 0) & (kp <= pos[:, :, None])
            s = jnp.where(mask[:, None], s * scale, _NEG_INF)
            return jax.nn.softmax(s, axis=-1).astype(x.dtype)

        if T == 1:
            with jax.named_scope("mla_absorb"):
                q_a = jnp.einsum("bthn,chn->bthc", q_n, w_kvb[..., :N])
                q_cat = jnp.concatenate(                             # [B, 1, H, C + R + pad]
                    [q_a, q_r, jnp.zeros((B, T, H, pad.shape[-1]), q_a.dtype)], axis=-1)
            with jax.named_scope("mla_attend"):
                if mla_decode.engages(latent):
                    # one pass over the lanes a slot has (lane m holds position
                    # m: no latent stack has a window, so no pool of them wraps)
                    o_lat = mla_decode.mla_decode(q_cat[:, 0], latent, at, positions[:, 0] + 1,
                                                  scale=scale)[:, None, :, :C]
                else:
                    rows = layer_slice(latent, at)                    # [B, M, C + R + pad]
                    s = jnp.einsum("bthc,bmc->bhtm", q_cat, rows, preferred_element_type=jnp.float32)
                    # over the whole row (the rotated key and the padding ride along:
                    # a slice of ``rows`` would be a copy of the layer's lanes)
                    o_lat = jnp.einsum("bhtm,bmc->bthc", masked_probs(s, positions), rows)[..., :C]
            with jax.named_scope("mla_absorb"):
                o = jnp.einsum("bthc,chv->bthv", o_lat, w_kvb[..., N:])
        elif mla_decode.chunk_engages(T, latent.shape[2]):
            with jax.named_scope("mla_expand"):
                rows = layer_slice(latent, at)                        # [B, M, C + R + pad]
                kv = jnp.einsum("bmc,chd->bhmd", rows[..., :C], w_kvb)  # head-major: a head's lanes lie together
                k_rot = jnp.broadcast_to(rows[:, None, :, C:C + R], (B, H, rows.shape[1], R))
                k = jnp.concatenate([kv[..., :N], k_rot], axis=-1)    # [B, H, M, N + R]
            with jax.named_scope("mla_attend"):
                qh = jnp.concatenate([q_n, q_r], axis=-1).transpose(0, 2, 1, 3)   # [B, H, T, N + R]
                # flash-style over the key blocks a query tile can see (lane m
                # holds position m, as for the decode kernel)
                o = mla_decode.mla_chunk_attend(qh, k, kv[..., N:], positions[:, 0],
                                                scale=scale).transpose(0, 2, 1, 3)
        else:
            with jax.named_scope("mla_expand"):
                rows = layer_slice(latent, at)                        # [B, M, C + R + pad]
                kv = jnp.einsum("bmc,chd->bmhd", rows[..., :C], w_kvb)  # [B, M, H, N + V]
                k_n, v, k_rot = kv[..., :N], kv[..., N:], rows[..., C:C + R]

            def attend(xs):
                qn, qr, pos = xs                                      # [B, t, H, N], [B, t, H, R], [B, t]
                s = jnp.einsum("bthn,bmhn->bhtm", qn, k_n, preferred_element_type=jnp.float32) \
                    + jnp.einsum("bthr,bmr->bhtm", qr, k_rot, preferred_element_type=jnp.float32)
                return jnp.einsum("bhtm,bmhv->bthv", masked_probs(s, pos), v)

            with jax.named_scope("mla_attend"):
                Tq = min(MLA_QUERY_BLOCK, T)
                if T == Tq:
                    o = attend((q_n, q_r, positions))
                else:
                    # the last query block's padding repeats its last query
                    o = lax.map(attend, tuple(_time_blocks(a, Tq, "edge") for a in (q_n, q_r, positions)))
                    o = jnp.moveaxis(o, 0, 1).reshape(B, -1, H, V)[:, :T]
        x = _residual(x, _proj(o.reshape(B, T, H * V), lp["o"]["kernel"]), cfg)
    return _mlp_block(x, lp, cfg, valid, tally, dense=dense), latent


# ---------------------------------------------------------------------------
# A decoder-hybrid-decoder stack: Mamba-1, differential attention (window,
# full, cross) and gated memory units
# ---------------------------------------------------------------------------

# Queries a chunk's differential attention scores at a time (float32, for
# every head against the lanes that block of queries can see).
DIFF_QUERY_BLOCK = 512
# The sub-norm of differential attention: the published code's default.
_SUB_NORM_EPS = 1e-5
# Positions one iteration of the Mamba-1 chunk scan's loop walks.
_MAMBA1_UNROLL = 16


def _mamba1_scan(x, dt, A, Bm, Cm, h):
    """The selective scan over a chunk, position by position: x, dt [B,T,I]
    (dt float32, 0 where a position must leave the state as it was), A [N,I]
    (negative), Bm, Cm [B,T,N], h [B,N,I] float32 the state entering. Returns
    (y [B,T,I] float32 without the skip term, the state leaving). The decay is
    per (state, channel) pair, so there is no chunked (SSD) form: a step is
    :func:`_mamba1_step`, and the loop carries ``h`` alone."""
    def step(h, xs):
        y, h = _mamba1_step(*xs, A, h)
        return h, y

    h, y = lax.scan(step, h, tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, Bm, Cm)),
                    unroll=min(_MAMBA1_UNROLL, x.shape[1]))
    return jnp.moveaxis(y, 0, 1), h


def _mamba1_step(x, dt, Bm, Cm, A, h):
    """One recurrence step, all float32 elementwise: x, dt [B,I], Bm, Cm [B,N],
    A [N,I], h [B,N,I]: ``h' = exp(dt A) h + (dt x) B``, ``y = sum_n C h'``."""
    f32 = jnp.float32
    h = jnp.exp(dt[:, None, :] * A) * h \
        + (dt * x.astype(f32))[:, None, :] * Bm.astype(f32)[:, :, None]
    return jnp.sum(h * Cm.astype(f32)[:, :, None], axis=1), h


def _mamba1_block(x, lp, state, conv, at, valid, cfg: ModelConfig, tally=None):
    """One Mamba-1 layer, then the block every kind has (:func:`_mlp_block`).

    ``(u, z) = h W_in``; ``u = silu(conv(u) + b)`` (causal, depthwise, over
    the last taps-1 inputs kept in ``conv`` and the new); ``(dt_r, B, C) = u
    W_x``; ``dt = softplus(dt_r W_dt + dt_bias)``; ``s_t = exp(dt_t A) s_{t-1}
    + (dt_t u_t) B_t`` per (state, channel), ``A = -exp(A_log)``; ``m_t = sum_n
    C_t s_t + D u_t``; output ``(m * silu(z)) W_out``. ``state`` [L,B,N,I]
    float32 and ``conv`` [L,B,taps-1,I] are the kind's whole leaves; this
    layer reads and rewrites its own slice, ``at``, under ``mamba1_update``
    (one token) or ``mamba1_scan`` (a chunk). ``valid`` [B,T] marks the real
    positions, a PREFIX of each row: a position that is not valid leaves both
    states exactly as they were (``dt = 0``; the convolution state is taken at
    the row's true length). Returns (x, ``m`` [B,T,I] in x's dtype: the scan
    output BEFORE the gate, the memory a later gated memory unit reads,
    state, conv)."""
    B, T, _ = x.shape
    I, N, R, K = cfg.mamba1_inner, cfg.mamba1_state, cfg.mamba1_rank, cfg.ssm_conv
    f32 = jnp.float32
    with jax.named_scope("mamba1"):
        hn = _norm(x, lp["mixer_norm"], cfg)
        with jax.named_scope("mamba1_in_proj"):
            uz = _proj(hn, lp["in_proj"]["kernel"])
            u, z = uz[..., :I], uz[..., I:]
        with jax.named_scope("mamba1_conv"):
            window = jnp.concatenate([layer_slice(conv, at).astype(u.dtype), u], axis=1)
            w = lp["conv"]["kernel"].astype(f32)                  # [K, I]
            acc = lp["conv"]["bias"].astype(f32)
            for k in range(K):
                acc = acc + window[:, k:k + T].astype(f32) * w[k]
            u = jax.nn.silu(acc).astype(x.dtype)
            if T == 1:
                conv_state = jnp.where(valid[:, :, None], window[:, 1:], window[:, :-1])
            else:
                n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)
                conv_state = jax.vmap(
                    lambda win, n: lax.dynamic_slice_in_dim(win, n, K - 1, 0))(window, n_valid)
            conv = lax.dynamic_update_index_in_dim(conv, conv_state.astype(conv.dtype), at, 0)
        with jax.named_scope("mamba1_x_proj"):
            dbc = _proj(u, lp["x_proj"]["kernel"])
            Bm, Cm = dbc[..., R:R + N], dbc[..., R + N:]
            dt = jax.nn.softplus(_proj(dbc[..., :R], lp["dt_proj"]["kernel"]).astype(f32)
                                 + lp["dt_bias"].astype(f32))
            dt = jnp.where(valid[:, :, None], dt, 0.0)            # [B,T,I]
        A = -jnp.exp(lp["A_log"].astype(f32))                     # [N,I]
        h = layer_slice(state, at)
        if T == 1:
            with jax.named_scope("mamba1_update"):
                y, h = _mamba1_step(u[:, 0], dt[:, 0], Bm[:, 0], Cm[:, 0], A, h)
                y = y[:, None]
                state = lax.dynamic_update_index_in_dim(state, h, at, 0)
        else:
            with jax.named_scope("mamba1_scan"):
                y, h = _mamba1_scan(u, dt, A, Bm, Cm, h)
                state = lax.dynamic_update_index_in_dim(state, h, at, 0)
        m = y + lp["D"].astype(f32) * u.astype(f32)
        with jax.named_scope("mamba1_gate"):
            g = (m * jax.nn.silu(z.astype(f32))).astype(x.dtype)
        with jax.named_scope("mamba1_out_proj"):
            x = _residual(x, _proj(g, lp["out_proj"]["kernel"]), cfg)
    return _mlp_block(x, lp, cfg, valid, tally), m.astype(x.dtype), state, conv


def _diff_attend(q, kc, vc, key_pos, positions, lp, cfg: ModelConfig, window: int, scope: str):
    """Differential attention of q [B,T,H x HD] over keys and values kc, vc
    [B,S,KV x HD] that hold the positions ``key_pos`` [B,S] (-1: none).

    The H query heads are H/2 pairs ``(q1, q2)``, the KV kv-heads KV/2 pairs
    ``(k1, k2)``, ``(v1, v2)`` (interleaved: heads 2j and 2j+1), G query pairs
    a kv pair; ``V = v1 | v2``; ``a1 = softmax(q1 k1^T / sqrt(HD)) V``, ``a2``
    alike from ``(q2, k2)``; ``lambda = exp(l_q1 . l_k1) - exp(l_q2 . l_k2) +
    lambda_init``; the output is ``RMSNorm(a1 - lambda a2) (1 - lambda_init)``
    per pair, 2 HD wide. A kv pair lies in the cache as 2 HD = 128 values
    ``k1 | k2``, so the two softmaxes are ONE grouped-query attention over rows
    of 128: ``q1 | 0`` and ``0 | q2`` against the pair's row as it lies, and
    no half of a row is sliced out. A query at position p sees the keys at
    ``p - window < k <= p`` (``window`` 0: every key up to p). Scope ``scope``
    (``window_attn`` | ``full_attn`` | ``cross_attn``) holds the two
    contractions, ``diff_combine`` what follows."""
    B, T, _ = q.shape
    HD, P = cfg.head_dim, cfg.n_kv_heads // 2
    S = kc.shape[1]
    with jax.named_scope(scope):
        qz = _diff_queries(q, cfg)
        kc, vc = kc.reshape(B, S, P, 2 * HD), vc.reshape(B, S, P, 2 * HD)
        scores = jnp.einsum("btkgd,bmkd->bkgtm", qz, kc,
                            preferred_element_type=jnp.float32) * attention_scale(cfg)
        kp = key_pos[:, None, :]
        mask = (kp >= 0) & (kp <= positions[:, :, None])
        if window:
            mask &= kp > positions[:, :, None] - window
        scores = jnp.where(mask[:, None, None, :, :], scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        a = jnp.einsum("bkgtm,bmkd->btkgd", probs, vc, preferred_element_type=jnp.float32)
    return _diff_combine(a, lp, cfg, q.dtype)


def _diff_queries(q, cfg: ModelConfig):
    """q [B,T,H x HD] as the rows that attend each kv pair's 2 HD-wide keys as
    they lie: [B,T,P,2 G,2 HD], a query pair's ``q1 | 0`` then ``0 | q2``."""
    B, T, _ = q.shape
    HD, P, G = cfg.head_dim, cfg.n_kv_heads // 2, cfg.n_heads // cfg.n_kv_heads
    qp = q.reshape(B, T, P, G, 2, HD)
    zero = jnp.zeros_like(qp[..., 0, :])
    qz = jnp.stack([jnp.concatenate([qp[..., 0, :], zero], axis=-1),
                    jnp.concatenate([zero, qp[..., 1, :]], axis=-1)], axis=4)
    return qz.reshape(B, T, P, 2 * G, 2 * HD)


def _diff_combine(a, lp, cfg: ModelConfig, dtype):
    """What follows the two softmaxes (scope ``diff_combine``): a [B,T,P,2 G,
    2 HD] float32, a query pair's ``a1`` then ``a2`` -> [B,T,H x HD]."""
    B, T = a.shape[:2]
    HD, P, G = cfg.head_dim, cfg.n_kv_heads // 2, cfg.n_heads // cfg.n_kv_heads
    with jax.named_scope("diff_combine"):
        a = a.reshape(B, T, P, G, 2, 2 * HD)
        lam = lp["lambdas"].astype(jnp.float32)                    # [4, HD]: q1, k1, q2, k2
        lam_init = lp["lambda_init"].astype(jnp.float32)
        lam_full = jnp.exp(jnp.sum(lam[0] * lam[1])) - jnp.exp(jnp.sum(lam[2] * lam[3])) + lam_init
        d = a[..., 0, :] - lam_full * a[..., 1, :]
        d = d * lax.rsqrt(jnp.mean(jnp.square(d), -1, keepdims=True) + _SUB_NORM_EPS)
        d = d * lp["sub_norm"]["scale"].astype(jnp.float32) * (1.0 - lam_init)
        return d.reshape(B, T, P * G * 2 * HD).astype(dtype)


def diff_walk_engages(k_arr, T: int, window: int) -> bool:
    """Whether a differential kind's read of ``k_arr`` ``[L,B,M,KV x HD]``
    goes through ``ops.lane_decode`` (decided from what the trace sees): one
    query a row against a leaf of which a row sees every lane it has (a ring no
    longer than the window, or no window), in whole blocks and column groups on
    a TPU (``lane_decode.engages``)."""
    M = k_arr.shape[2]
    return T == 1 and M <= (window or M) and lane_decode.engages(k_arr)


def _diff_attention(q, k_arr, v_arr, at, positions, lp, cfg: ModelConfig, window: int, scope: str):
    """:func:`_diff_attend` of q [B,T,H x HD] at ``positions`` [B,T] over layer
    ``at`` of ``k_arr`` / ``v_arr`` ``[L,B,M,KV x HD]``, whose lanes hold what
    ``layer_state.ring_positions`` says for rows as long as their last
    position + 1 (a leaf as long as the row: position m in lane m).

    ``DIFF_QUERY_BLOCK`` queries at a time. Under a window a block of queries
    reads only the ``block + window - 1`` lanes it can see, sliced out of the
    layer where the leaf is longer than that: a staged cache's leaf (lockstep
    rows, never wrapped, which :func:`init_cache` allocates) at a chunk; the
    pool's ring is no longer than a window and is read whole."""
    B, T = positions.shape
    M = k_arr.shape[2]
    length = positions[:, -1] + 1
    Tq = min(T, DIFF_QUERY_BLOCK)
    S = min(M, Tq + window - 1) if window else M
    if diff_walk_engages(k_arr, T, window):
        # One query a row against the layer as it lies, the lanes a row has and
        # no others (of a ring no longer than the window: all it holds is seen).
        with jax.named_scope(scope):
            a = lane_decode.lane_decode(_diff_queries(q, cfg)[:, 0], k_arr, v_arr, at, jnp.minimum(length, M),
                                        scale=attention_scale(cfg), name="diff_decode")
        return _diff_combine(a[:, None], lp, cfg, q.dtype)

    def attend(xs):
        qb, pb = xs
        if S == M:
            kc, vc, start = layer_slice(k_arr, at), layer_slice(v_arr, at), 0
        else:  # the lanes up to the block's last query
            start = jnp.clip(pb[0, -1] + 1 - S, 0, M - S)
            cut = lambda a: lax.dynamic_slice(a, (at, 0, start, 0), (1, B, S, a.shape[3]))[0]  # noqa: E731
            kc, vc = cut(k_arr), cut(v_arr)
        key_pos = layer_state.ring_positions(M, length, start, S)
        return _diff_attend(qb, kc, vc, key_pos, pb, lp, cfg, window, scope)

    if T == Tq:
        return attend((q, positions))
    # the last query block's padding repeats its last query
    o = lax.map(attend, (_time_blocks(q, Tq, "edge"), _time_blocks(positions, Tq, "edge")))
    return jnp.moveaxis(o, 0, 1).reshape(B, -1, q.shape[-1])[:, :T]


def _diff_proj(h, lp, name: str):
    return _proj(h, lp[name]["kernel"], bias=lp[name].get("bias"))


def _diff_attn_block(x, lp, k_arr, v_arr, at, write, positions, valid, cfg: ModelConfig,
                     window: int, scope: str, tally=None, row=None, write_only: bool = False):
    """One differential-attention layer with keys and values of its own
    (``window`` > 0: a window layer, whose leaves the pool keeps as a ring;
    0: a full layer), then the block every kind has. ``write`` stores the
    chunk's keys and values in layer ``at``'s lanes of the kind's whole leaves
    ``[L,B,M,KV x HD]`` (scope ``kv_write``), which are then only read
    (:func:`_diff_attention`, under ``decode_attn``). No rotation, no learned
    positions.

    ``write_only``: the write and nothing else (a prompt's chunk that is not
    its last, at the layer whose cache the cross-decoder reads). ``row``
    (traced index): keys and values of every position are written, and the
    layer goes on for position ``row`` of the chunk ALONE, x [B,1,D] leaving.
    Returns (x, k_arr, v_arr)."""
    with jax.named_scope(scope + "_layer"):
        h = _norm(x, lp["mixer_norm"], cfg)
        with jax.named_scope("kv_write"):
            k_arr = write(k_arr, _diff_proj(h, lp, "k"), at, ring=bool(window))
            v_arr = write(v_arr, _diff_proj(h, lp, "v"), at, ring=bool(window))
        if write_only:
            return None, k_arr, v_arr
        if row is not None:
            x, h, positions, valid = (lax.dynamic_slice_in_dim(a, row, 1, 1) for a in (x, h, positions, valid))
        with jax.named_scope("decode_attn"):
            o = _diff_attention(_diff_proj(h, lp, "q"), k_arr, v_arr, at, positions, lp, cfg, window, scope)
        x = _residual(x, _diff_proj(o, lp, "o"), cfg)
    return _mlp_block(x, lp, cfg, valid, tally), k_arr, v_arr


def _cross_attn_block(x, lp, k_arr, v_arr, at, positions, valid, cfg: ModelConfig, tally=None):
    """One cross-attention layer: queries of its own, keys and values those of
    layer ``at`` of ANOTHER kind's leaves (the one full-attention cache), which
    it reads and never writes; differential attention with the layer's own
    lambdas. Then the block every kind has. Returns x."""
    with jax.named_scope("cross_attn_layer"):
        h = _norm(x, lp["mixer_norm"], cfg)
        with jax.named_scope("decode_attn"):
            o = _diff_attention(_diff_proj(h, lp, "q"), k_arr, v_arr, at, positions, lp, cfg, 0, "cross_attn")
        x = _residual(x, _diff_proj(o, lp, "o"), cfg)
    return _mlp_block(x, lp, cfg, valid, tally)


def _gmu_block(x, lp, mem, valid, cfg: ModelConfig, tally=None):
    """One gated memory unit: ``(m * silu(h W_1)) W_2`` with ``m`` [B,T,I] the
    memory carried along the walk (the scan output of the last Mamba-1 layer
    before it, at the SAME positions): an activation, nothing cached. Then the
    block every kind has. Returns x."""
    with jax.named_scope("gmu"):
        h = _norm(x, lp["mixer_norm"], cfg)
        gate = jax.nn.silu(_proj(h, lp["in_proj"]["kernel"]).astype(jnp.float32))
        o = (mem.astype(jnp.float32) * gate).astype(x.dtype)
        x = _residual(x, _proj(o, lp["out_proj"]["kernel"]), cfg)
    return _mlp_block(x, lp, cfg, valid, tally)


def scan_layers(x, stacks, cfg: ModelConfig, cache, write, slot_pos, positions,
                valid=None, ingest_only: bool = False, tail_row=None, visible=None):
    """Walk the stack against (and into) ``cache`` — THE one cached walk, for
    every architecture, a :class:`KVCache` or the serving pool alike (both
    hold their per-layer arrays as ``cache.layers``, the tree by kind of
    :mod:`tpu_engine.layer_state`).

    The stack is walked by RUNS of like layers (``cfg.layer_runs()``): one
    ``lax.scan`` per run — a stack of one kind is one run and one loop; 5 ssm,
    1 attn, 9 ssm, 1 attn, 4 ssm are five small loops, not twenty unrolled
    blocks. What differs between callers is data: the run list, whether scale
    arrays ride along, which lanes ``write`` picks.

    - CARRIED: ``x`` and ``cache.layers``, whole — keys and values
      ``[L_attn, B, M, KV x HD]`` (an int8 cache: ``[L_attn, B, M, KV, HD]``
      codes with their scales), the recurrent ``ssm`` / ``conv``
      ``[L_ssm, B, ...]``. Nothing of the cache is a scan input or output, so
      no run rebuilds it and the loop updates the (donated) buffers where
      they lie.
    - WRITTEN IN PLACE: ``write(cache_arr, rows, at)`` stores a layer's new
      rows [B, T, KV x HD] in layer ``at``'s lanes of ``cache_arr`` (a scatter
      or ``dynamic_update_slice`` with the layer index folded in); a Mamba-2
      layer rewrites its own slice of ``ssm`` / ``conv`` (:func:`_ssm_block`).
    - ONLY READ: an attention layer's keys and values — by a decode step of a
      caller that hands ``visible`` ([B] int32: the leading lanes each row
      sees, lane m holding position m, 0 for a row that does not decode; the
      serving pool's non-ring step) through the kernel ``ops.lane_decode``,
      the blocks a row's length covers from the stack where it lies, where
      that engages (``_decode_block``); else the layer's [B, M, KV x HD]
      sliced out for XLA's two contractions (``_decode_block(read=)``) — and
      the parameters — ``stacks``
      is ``params["layers"]`` of a tree in ``transformer.served_format`` (the
      walk casts nothing and refuses a stack in another dtype than ``x``'s),
      never written, so it stays outside the carry and a run indexes its
      kind's stack at ``first + i`` (for a run that is a whole stack that is
      what scanning it as ``xs`` lowers to).

    A kind's layer function takes ``(x, lp, at, leaves, tally)``, the kind's
    leaves whole, and returns ``(x, leaves)``. ``valid`` [B, T] marks the real
    positions for the recurrent layers (which have no mask) and for a
    mixture's counts; attention-only callers may leave it out. A cache that
    carries ``moe_counts`` has each mixture layer's :data:`MOE_COUNTS` added
    to it (carried beside ``x``; a cache without counts none and nothing
    more). Returns ``(x, cache)`` with ``cache.layers`` (and the counts)
    replaced.

    A DECODER-HYBRID-DECODER stack (``cfg.layer_periods()``: a stretch that
    alternates is one loop over its period, 8 x (mamba1, window_attn), not
    sixteen runs of one) adds two things to the walk. Its layer functions take
    ``(x, lp, at, state, mem, tally, kind, view)``, the WHOLE tree, the carried
    memory ``mem`` [B, T, I] (a Mamba-1 layer's scan output before its gate,
    which the gated memory units after it read) and ``view`` = (positions,
    valid) of the positions the layer runs at, and return ``(x, leaves, mem)``: a
    cross-attention layer owns no leaves and reads the full-attention kind's.
    And a prompt's chunk need not run it whole: logits are wanted at a
    prompt's last position only, and that position's cross-decoder (the
    layers after ``cfg.cross_decoder_start``) needs the cross-decoder at no
    other position. ``ingest_only``: the walk ends with the keys and values
    that layer writes (a chunk that is not a prompt's last; ``x`` returned is
    None). ``tail_row`` (traced index into the chunk): from that layer's
    attention on the stack runs at position ``tail_row`` alone, and ``x``
    returned is [B, 1, D]. Neither: every layer at every position (decode)."""
    require_served_format(stacks, x.dtype)
    stacks = stacks if cfg.is_hybrid else {"attn": stacks}
    shared_at = cfg.cross_decoder_start
    if (ingest_only or tail_row is not None) and shared_at is None:
        raise ValueError(f"model {cfg.name!r} has no cross-decoder to leave out of a prompt's chunk")

    def attn_layer(x, lp, at, s, tally):
        x, k, v, k_scale, v_scale = _decode_block(
            x, lp, s["k"], s["v"], lambda arr, rows: write(arr, rows, at), slot_pos,
            positions, cfg, k_scale_c=s.get("k_scale"), v_scale_c=s.get("v_scale"),
            read=lambda arr: layer_slice(arr, at), valid=valid, tally=tally, at=at, visible=visible)
        new = {"k": k, "v": v, "k_scale": k_scale, "v_scale": v_scale}
        return x, {name: new[name] for name in s}  # scales only where they came in

    def ssm_layer(x, lp, at, s, tally):
        x, ssm, conv = _ssm_block(x, lp, s["ssm"], s["conv"], at, valid, cfg, tally)
        return x, {"ssm": ssm, "conv": conv}

    def sparse_attn_layer(x, lp, at, s, tally):
        x, k, v, ck = _sparse_attn_block(x, lp, s["k"], s["v"], s["ck"], at, write,
                                         positions, valid, cfg, tally)
        return x, {"k": k, "v": v, "ck": ck}

    def lightning_layer(x, lp, at, s, tally):
        x, state = _lightning_block(x, lp, s["state"], at, positions, valid, cfg, tally)
        return x, {"state": state}

    def power_layer(x, lp, at, s, tally):
        x, state, norm = _power_block(x, lp, s["state"], s["norm"], at, positions, valid, cfg, tally)
        return x, {"state": state, "norm": norm}

    def mla_layer(x, lp, at, s, tally, dense=False):
        x, latent = _mla_block(x, lp, s["latent"], at, write, slot_pos, positions, valid,
                               cfg, tally, dense)
        return x, {"latent": latent}

    def own(fn):  # a kind that reads and rewrites its own leaves and nothing else
        def layer(x, lp, at, state, mem, tally, kind, view):
            x, leaves = fn(x, lp, at, state[kind], tally)
            return x, leaves, mem
        return layer

    # A decoder-hybrid-decoder kind is handed what it sees of the chunk, ``view``
    # = (positions, valid): the walk narrows it to one position once it has
    # passed the shared layer under ``tail_row``.
    def mamba1_layer(x, lp, at, state, mem, tally, kind, view):
        s = state[kind]
        x, mem, h, conv = _mamba1_block(x, lp, s["state"], s["conv"], at, view[1], cfg, tally)
        return x, {"state": h, "conv": conv}, mem

    def diff_attn_layer(x, lp, at, state, mem, tally, kind, view, **tail):
        s = state[kind]
        x, k, v = _diff_attn_block(
            x, lp, s["k"], s["v"], at, write, view[0], view[1], cfg,
            cfg.sliding_window if kind == "window_attn" else 0, kind, tally, **tail)
        return x, {"k": k, "v": v}, mem

    def cross_attn_layer(x, lp, at, state, mem, tally, kind, view):
        shared = state["full_attn"]  # the one cache: its last layer's keys and values
        x = _cross_attn_block(x, lp, shared["k"], shared["v"], shared["k"].shape[0] - 1,
                              view[0], view[1], cfg, tally)
        return x, state[kind], mem

    def gmu_layer(x, lp, at, state, mem, tally, kind, view):
        return _gmu_block(x, lp, mem, view[1], cfg, tally), state[kind], mem

    layer_fns = {"attn": own(attn_layer), "ssm": own(ssm_layer),
                 "sparse_attn": own(sparse_attn_layer), "lightning": own(lightning_layer),
                 "power": own(power_layer),
                 "mla": own(mla_layer), "mla_dense": own(partial(mla_layer, dense=True)),
                 "mamba1": mamba1_layer, "window_attn": diff_attn_layer, "full_attn": diff_attn_layer,
                 "cross_attn": cross_attn_layer, "gmu": gmu_layer}
    state, counts = cache.layers, cache.moe_counts
    # The memory the gated memory units read rides beside ``x`` (a stack
    # without them carries none, and not a leaf more).
    mem = jnp.zeros(x.shape[:2] + (cfg.mamba1_inner,), x.dtype) if "gmu" in state else None
    walked = 0  # layers behind the walk
    view = (positions, valid)
    for kinds, firsts, count in cfg.layer_periods():

        def body(carry, i, kinds=kinds, firsts=firsts, view=view, **tail):
            x, state, counts, mem = carry
            for kind, first in zip(kinds, firsts):
                at = first + i
                lp = jax.tree.map(lambda a: layer_slice(a, at), stacks[kind])
                if cfg.is_moe and "gate" in stacks[kind] and experts_grouped_engages(
                        x.shape[0] * x.shape[1], cfg, stacks[kind]["gate"]["kernel"], cache.sharded):
                    lp["experts_in_stack"] = (stacks[kind], at)  # run grouped, read where they lie
                tally = None if counts is None else []
                x, leaves, mem = layer_fns[kind](x, lp, at, state, mem, tally, kind, view, **tail)
                if tally:
                    counts = counts + sum(tally)
                state = {**state, kind: leaves}
            return (x, state, counts, mem), None

        if (ingest_only or tail_row is not None) and walked == shared_at:
            if count != 1 or kinds != ("full_attn",):
                raise NotImplementedError("the layer whose cache the cross-decoder reads must stand alone "
                                          f"in the pattern to split a prompt's chunk at it (got {kinds} x {count})")
            tail = {"write_only": True} if ingest_only else {"row": tail_row}
            (x, state, counts, mem), _ = body((x, state, counts, mem), jnp.int32(0), **tail)
            if ingest_only:
                break
            view = tuple(lax.dynamic_slice_in_dim(a, tail_row, 1, 1) for a in view)
            mem = lax.dynamic_slice_in_dim(mem, tail_row, 1, 1)
        else:
            (x, state, counts, mem), _ = lax.scan(body, (x, state, counts, mem),
                                                  jnp.arange(count, dtype=jnp.int32))
        walked += len(kinds) * count
    return x, dataclasses.replace(cache, layers=state, moe_counts=counts)


def forward_with_cache(
    params: dict[str, Any],
    tokens: jax.Array,
    cache: KVCache,
    cfg: ModelConfig,
    compute_dtype=jnp.bfloat16,
    want_logits: bool = True,
    n_valid: Optional[jax.Array] = None,
    ingest_only: bool = False,
    logits_row: Optional[jax.Array] = None,
) -> tuple[Optional[jax.Array], KVCache]:
    """Run ``tokens`` [B, T] through the stack against (and into) ``cache``.

    ``params`` are in ``transformer.served_format`` for ``compute_dtype``: the
    walk casts no weight (a float32 tree with ``compute_dtype=float32`` is in
    that format as it is).

    ``n_valid`` (scalar, default T): how many leading tokens of the chunk are
    real; the rest is padding. Keys and values of padding are written and
    masked later, as ever; a hybrid stack's recurrent state has no mask, so
    its Mamba-2 layers stop at ``n_valid``.

    Serves both phases: prefill (T = prompt length) and decode (T = 1).
    Returns (logits [B, T, V] fp32, updated cache with length += T).
    The layer walk (:func:`scan_layers`) CARRIES the cache's arrays whole and
    each layer writes the chunk's T rows straight into its own lanes of them
    (one ``dynamic_update_slice`` at ``(layer, 0, offset, 0)``); a layer's
    [B, M, KV x HD] is only read, for attention. Jitted with the cache donated,
    the cache is updated where it lies.
    ``want_logits=False`` (static) skips the unembed entirely and returns
    ``(None, cache)`` — cache-ingestion-only callers (the speculative
    draft's prompt prefill) should not pay a T×D×V matmul per chunk.
    ``logits_row`` (traced index into the chunk), for ANY stack: the final
    norm and the head run at that position alone, logits [B, 1, V] — a
    prefill chunk's caller reads one row, and a dynamic row slice of
    [B, T, V] does not move in front of the contraction by itself. Every
    layer still runs at every position (the cache is the full walk's), except
    in a decoder-hybrid-decoder stack, whose prompt takes two programs
    (:func:`scan_layers`): ``ingest_only`` (static) for a chunk that is not
    its last — the self-decoder and the shared layer's keys and values,
    ``(None, cache)`` — and ``logits_row`` for its last, where the
    cross-decoder too runs at that position alone.

    For non-ring caches the caller must keep ``cache.length + T <=
    cache.max_len`` (size the cache to prompt + max_new_tokens, as
    :func:`generate` does). Ring caches (sliding-window models with fewer
    slots than the sequence) wrap; a chunk of T queries needs the window
    behind its oldest query resident, so the cache must hold at least
    ``window + T - 1`` slots (checked statically below — T=1 decode needs
    the full window resident too).
    """
    B, T = tokens.shape
    M = cache.max_len
    if cfg.arch == "gpt2" and not cache.ring and M > cfg.max_seq_len:
        # The cache is sized to the full generation; a learned position
        # table shorter than that would be silently clamped by jnp.take.
        raise ValueError(
            f"generation length {M} exceeds the learned position table "
            f"(max_seq_len={cfg.max_seq_len}) of gpt2-family model {cfg.name!r}"
        )
    if cache.ring and M < cfg.sliding_window + T - 1:
        raise ValueError(
            f"chunk of {T} queries needs >= {cfg.sliding_window + T - 1} cache "
            f"slots (window {cfg.sliding_window}), cache has {M}; prefill in "
            "smaller chunks or allocate with a larger max_chunk"
        )
    positions = cache.length + jnp.broadcast_to(
        jnp.arange(T, dtype=jnp.int32)[None, :], (B, T)
    )
    new_pos = cache.length + jnp.arange(T, dtype=jnp.int32)
    if cache.ring and T > 1:
        # A multi-token chunk on a ring cache can wrap mid-chunk; write it
        # as a one-hot select — TPU's scatter emitter rejects the
        # [B, slots, ...] multi-dim scatter (and even the 1-D traced-index
        # scatter for pos), and a select fuses cleanly. Slots within a
        # chunk are distinct (M >= T via the guard above), so the einsum
        # copies exactly one row per written slot. O(T·M) int ops — paid
        # only on this wrapping path, not on contiguous prefill/decode
        # (round-1 advisor finding). The one write that takes its layer out
        # and puts it back whole: a select has no in-place form.
        slots = new_pos % M
        onehot = jnp.arange(M)[None, :] == slots[:, None]  # [T, M]
        written = onehot.any(axis=0)
        pos_new = jnp.where(
            written,
            (onehot.astype(jnp.int32) * new_pos[:, None]).sum(axis=0),
            cache.pos,
        )

        def write(cache_arr, rows, at):
            rows_m = jnp.einsum("tm,bt...->bm...", onehot.astype(cache_arr.dtype),
                                rows.astype(cache_arr.dtype))
            layer = jnp.where(written.reshape((1, M) + (1,) * (rows.ndim - 2)), rows_m,
                              layer_slice(cache_arr, at))
            return lax.dynamic_update_index_in_dim(cache_arr, layer, at, 0)
    else:
        # Contiguous, non-wrapping write (T=1 ring decode, or any non-ring
        # chunk): a cheap O(T) dynamic_update_slice at the layer and the slot
        # offset, straight into the [L, B, M, KV x HD] cache; likewise the pos
        # vector.
        offset = cache.length % M if cache.ring else cache.length
        pos_new = lax.dynamic_update_slice(cache.pos, new_pos, (offset,))

        def write(cache_arr, rows, at, ring=False):
            # (a ring kind's leaves are as long as the cache here: they never wrap)
            return lax.dynamic_update_slice(
                cache_arr, rows[None].astype(cache_arr.dtype),
                (at, 0, offset) + (0,) * (cache_arr.ndim - 3))

    x = embed_tokens(params, tokens, compute_dtype, positions=positions, cfg=cfg)
    # Padding's keys and values are masked later; a recurrent state has no
    # mask, so the Mamba-2 layers stop at ``n_valid``.
    valid = jnp.broadcast_to(
        jnp.arange(T)[None, :] < (T if n_valid is None else n_valid), (B, T))
    # Only a stack with a cross-decoder walks part of its layers at one row.
    tail = logits_row is not None and cfg.cross_decoder_start is not None
    x, cache = scan_layers(x, params["layers"], cfg,
                           cache, write, pos_new, positions, valid,
                           **({"ingest_only": True} if ingest_only else {}),
                           **({"tail_row": logits_row} if tail else {}))
    if logits_row is not None and not tail:
        x = lax.dynamic_slice_in_dim(x, logits_row, 1, 1)
    logits = unembed(params, x, cfg) if want_logits and not ingest_only else None
    return logits, dataclasses.replace(cache, pos=pos_new,
                                       length=cache.length + T)


def _filtered_sample(
    logits: jax.Array,
    rng: jax.Array,
    temperature,
    top_k: Optional[int],
    top_p,
) -> jax.Array:
    """Temperature → top-k → nucleus (top-p) → categorical draw.

    ``temperature`` and ``top_p`` may be Python floats *or traced scalars*
    (the decode loop passes them as operands so sweeping them never triggers
    a recompile); ``top_k`` must be static (``lax.top_k`` needs a static k).
    ``top_p=None`` skips the nucleus sort entirely. All static shapes — the
    top-p cutoff is a mask over the sorted cumulative distribution, not a
    dynamic truncation.
    """
    logits = logits / temperature
    if top_k is not None:
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, _NEG_INF, logits)
    if top_p is not None:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum_excl = jnp.cumsum(probs, axis=-1) - probs  # mass strictly before
        keep_sorted = cum_excl < top_p  # always keeps the top token
        kept_min = jnp.min(
            jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1, keepdims=True
        )
        logits = jnp.where(logits < kept_min, _NEG_INF, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def sample_token(
    logits: jax.Array,
    rng: jax.Array,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
) -> jax.Array:
    """logits [B, V] fp32 → token ids [B] int32. ``temperature=0`` = greedy."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return _filtered_sample(logits, rng, temperature, top_k, top_p)


def _over_a_mesh(*trees) -> bool:
    """Whether some leaf of these (concrete) trees lies over more than one
    device: what a host entry tells its caches (``KVCache.sharded``), since
    the traces inside cannot see where a stack lies."""
    return any(len(a.sharding.device_set) > 1
               for a in jax.tree.leaves(trees) if isinstance(a, jax.Array) and hasattr(a, "sharding"))


def generate(
    params: dict[str, Any],
    prompt: jax.Array,
    cfg: ModelConfig,
    max_new_tokens: int,
    rng: Optional[jax.Array] = None,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    compute_dtype=jnp.bfloat16,
    kv_quant: bool = False,
) -> jax.Array:
    """Generate ``max_new_tokens`` continuations of ``prompt`` [B, P] int32.

    Returns [B, P + max_new_tokens] int32. One prefill pass over the prompt,
    then a ``lax.scan`` of single-token decode steps — the whole loop is one
    XLA program. Greedy by default; pass ``rng`` + ``temperature`` (and
    optionally ``top_k`` / ``top_p``) for sampling. ``kv_quant`` stores the
    KV cache as int8 (half the decode HBM; see :func:`init_cache`).

    Recompiles only on shape / ``cfg`` / ``top_k`` / greedy-vs-sampled /
    ``kv_quant`` changes: ``temperature`` and ``top_p`` enter the compiled
    program as traced scalars, so sweeping them (e.g. through the HTTP
    sampling endpoint) reuses the cached executable.
    """
    if rng is None:
        rng = jax.random.PRNGKey(0)
    greedy = temperature == 0.0
    return _generate_jit(
        params,
        prompt,
        jnp.asarray(1.0 if greedy else temperature, jnp.float32),
        jnp.asarray(1.0 if top_p is None else top_p, jnp.float32),
        rng,
        cfg=cfg,
        max_new_tokens=max_new_tokens,
        top_k=top_k,
        use_top_p=top_p is not None,
        greedy=greedy,
        compute_dtype=compute_dtype,
        kv_quant=kv_quant,
        sharded=_over_a_mesh(params),
    )


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "max_new_tokens", "top_k", "use_top_p", "greedy", "compute_dtype",
        "kv_quant", "sharded",
    ),
)
def _generate_jit(
    params: dict[str, Any],
    prompt: jax.Array,
    temperature: jax.Array,
    top_p: jax.Array,
    rng: jax.Array,
    *,
    cfg: ModelConfig,
    max_new_tokens: int,
    top_k: Optional[int],
    use_top_p: bool,
    greedy: bool,
    compute_dtype,
    kv_quant: bool = False,
    sharded: bool = False,
) -> jax.Array:
    B, P = prompt.shape
    # A training job's float32 parameters: converted once, outside the token
    # loop, to what the cached walk reads.
    params = served_format(params, compute_dtype)

    def sample(logits, key):
        with jax.named_scope("sample"):
            if greedy:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return _filtered_sample(
                logits, key, temperature, top_k, top_p if use_top_p else None
            )

    keys = jax.random.split(rng, max_new_tokens)  # one fresh key per draw
    cache = init_cache(cfg, B, P + max_new_tokens, dtype=compute_dtype,
                       max_chunk=P, kv_quant=kv_quant, sharded=sharded)
    logits, cache = forward_with_cache(params, prompt, cache, cfg, compute_dtype)
    first = sample(logits[:, -1, :], keys[0])

    def step(carry, step_rng):
        token, cache = carry
        logits, cache = forward_with_cache(
            params, token[:, None], cache, cfg, compute_dtype
        )
        nxt = sample(logits[:, -1, :], step_rng)
        return (nxt, cache), nxt

    if max_new_tokens > 1:
        _, rest = lax.scan(step, (first, cache), keys[1:])
        generated = jnp.concatenate([first[None], rest], axis=0)  # [N, B]
    else:
        generated = first[None]
    return jnp.concatenate([prompt, generated.T.astype(jnp.int32)], axis=1)


def speculative_generate(
    params: dict[str, Any],
    draft_params: dict[str, Any],
    prompt: jax.Array,
    cfg: ModelConfig,
    draft_cfg: ModelConfig,
    max_new_tokens: int,
    gamma: int = 4,
    compute_dtype=jnp.bfloat16,
    return_stats: bool = False,
) -> jax.Array:
    """Speculative greedy decoding: a small draft model proposes ``gamma``
    tokens autoregressively, the target verifies them in ONE forward pass,
    and the longest agreeing prefix (plus the target's correction token) is
    accepted — output is identical to plain greedy decoding of the target,
    in fewer target forward passes.

    Exactness caveat: the guarantee holds whenever the target's chunked
    (T=gamma+1) and incremental (T=1) forwards agree on the argmax. That is
    bit-exact on the CPU backend (pinned in tests); on TPU, XLA's matmul
    pass structure differs with chunk size (~1e-2 logit deltas), so
    near-argmax-ties — pervasive in random-init models, rare in trained
    ones — can resolve differently than single-token greedy.

    Cache rewind is free by construction: rejected positions simply leave
    stale entries whose stored global position exceeds every later query
    (masked by the position-based attention mask) until the sequence
    re-reaches them, at which point the write lands on the same slot before
    attention runs. ``length`` is rolled back to the accepted frontier and
    nothing else needs cleaning.

    Batch 1 only (acceptance lengths diverge across rows). Returns
    [1, P + max_new_tokens] int32 — or, with ``return_stats=True``,
    ``(tokens, rounds)`` where ``rounds`` is the number of target forward
    passes taken (a perfect draft needs ceil(N / (gamma+1))).
    """
    for c in (cfg, draft_cfg):  # the rewind is a length; a recurrent state has none
        refuse_beyond_kv(c, "speculative decoding (speculative_generate)")
    if prompt.shape[0] != 1:
        raise ValueError("speculative_generate supports batch size 1")
    if gamma < 1:
        raise ValueError(f"gamma must be >= 1, got {gamma}")
    out, rounds = _speculative_jit(
        params, draft_params, prompt,
        cfg=cfg, draft_cfg=draft_cfg, max_new_tokens=max_new_tokens,
        gamma=gamma, compute_dtype=compute_dtype, sharded=_over_a_mesh(params, draft_params),
    )
    return (out, int(rounds)) if return_stats else out


@partial(
    jax.jit,
    static_argnames=("cfg", "draft_cfg", "max_new_tokens", "gamma", "compute_dtype", "sharded"),
)
def _speculative_jit(
    params, draft_params, prompt, *,
    cfg: ModelConfig, draft_cfg: ModelConfig,
    max_new_tokens: int, gamma: int, compute_dtype, sharded: bool = False,
) -> jax.Array:
    P = prompt.shape[1]
    total = P + max_new_tokens
    buf_len = total + gamma + 1  # room for one over-full final round
    params = served_format(params, compute_dtype)
    draft_params = served_format(draft_params, compute_dtype)

    cache = init_cache(cfg, 1, buf_len, dtype=compute_dtype,
                       max_chunk=max(P - 1, gamma + 1), sharded=sharded)
    dcache = init_cache(draft_cfg, 1, buf_len, dtype=compute_dtype,
                        max_chunk=max(P - 1, 1), sharded=sharded)

    out = jnp.zeros((1, buf_len), jnp.int32)
    out = lax.dynamic_update_slice(out, prompt.astype(jnp.int32), (0, 0))

    # Ingest the prompt minus its last token (the last token is re-fed each
    # round so its logits participate in verification).
    if P > 1:
        _, cache = forward_with_cache(params, prompt[:, :-1], cache, cfg,
                                      compute_dtype)
        _, dcache = forward_with_cache(draft_params, prompt[:, :-1], dcache,
                                       draft_cfg, compute_dtype)

    def round_body(state):
        out, out_len, rounds, cache, dcache = state
        t_last = lax.dynamic_slice(out, (0, out_len - 1), (1, 1))  # [1, 1]

        # Draft proposes gamma tokens, one at a time. One extra step beyond
        # gamma (its output discarded) so the draft also ingests its own
        # last proposal's K/V: on a fully-accepted round the rewind
        # advances past that position, and without the write it would stay
        # a permanent hole in the draft cache, silently halving acceptance.
        def draft_step(carry, _):
            tok, dc = carry
            logits, dc = forward_with_cache(draft_params, tok, dc, draft_cfg,
                                            compute_dtype)
            nxt = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
            return (nxt, dc), nxt[0, 0]

        (_, dcache), proposals = lax.scan(
            draft_step, (t_last, dcache), None, length=gamma + 1
        )
        proposals = proposals[:gamma]  # [gamma]

        # Target verifies the whole proposal chain in one forward pass.
        chain = jnp.concatenate([t_last[0], proposals])[None, :]  # [1, gamma+1]
        logits, cache = forward_with_cache(params, chain, cache, cfg,
                                           compute_dtype)
        tgt = jnp.argmax(logits[0], axis=-1).astype(jnp.int32)  # [gamma+1]

        # Longest agreeing prefix; tgt[a] is the free correction/bonus token.
        matches = proposals == tgt[:-1]
        a = jnp.sum(jnp.cumprod(matches.astype(jnp.int32)))
        out = lax.dynamic_update_slice(out, tgt[None, :], (0, out_len))
        new_len = out_len + a + 1

        # Rewind both caches to the accepted frontier (stale entries are
        # masked by position and overwritten on re-arrival).
        cache = dataclasses.replace(cache, length=new_len - 1)
        dcache = dataclasses.replace(dcache, length=new_len - 1)
        return out, new_len, rounds + 1, cache, dcache

    def cond(state):
        return state[1] < total

    out, _, rounds, _, _ = lax.while_loop(
        cond, round_body,
        (out, jnp.asarray(P, jnp.int32), jnp.zeros((), jnp.int32), cache, dcache),
    )
    return out[:, :total], rounds
