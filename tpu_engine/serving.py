"""Continuous-batching generation server (in-process, TPU-static shapes).

The reference has no serving story at all; :func:`tpu_engine.generate.generate`
serves the single-request case. This module adds the missing piece for a
shared endpoint: a fixed pool of decode SLOTS that requests join and leave
independently — a finishing request frees its slot for the next queued
prompt while the others keep decoding, so the chip never idles between
requests and short prompts are not held hostage by long ones.

TPU-first design:

- **Static shapes everywhere.** The KV pool is ``[L, slots, S, KV x HD]``
  for the server's lifetime; one jitted dispatch advances ALL slots
  ``chunk_steps`` tokens per call (empty/finished slots are wasted rows of
  the projections, never a recompile; on a TPU a decode step's attention
  reads only the lanes a live slot has, ``ops.lane_decode``).
- **Per-row positions.** Unlike :class:`generate.KVCache` (whose scalar
  ``length`` advances every row in lockstep), each slot carries its own
  length; K/V writes are per-row scatters (``.at[arange(B), lane]``) and
  the attention mask is position-based. Sliding-window models get a
  per-row RING pool (``S = window + prefill_chunk - 1`` lanes, writes at
  ``position % S``) — O(window) serving memory, same as the single-row
  ring cache in :mod:`tpu_engine.generate`.
- **Sampling inside the dispatch.** Greedy AND temperature>0 requests
  advance in the same chunked scan: each slot carries its temperature and
  a folded per-(request, step) key, so a loaded server with mixed
  sampling never drops to one-token-per-dispatch. Streams are
  deterministic for a given ``seed`` and independent of batch
  composition.
- **Chunked prefill.** Prompts are ingested ``prefill_chunk`` tokens per
  dispatch, interleaved with decode — an admission burst stalls running
  slots by at most ONE prefill-chunk dispatch per step, not one full
  prompt per admitted request (head-of-line fix, round-3 verdict).
- **Mesh-sharded serving.** Pass ``mesh=`` to serve models larger than a
  chip: params stay TP/FSDP-sharded exactly as the training job left
  them, the KV pool shards its kv-heads dim over the ``model`` axis, and
  every dispatch is jitted with explicit out-shardings + donation so the
  pool never round-trips. The ``job_id`` start path in
  ``backend/routers/serving.py`` wires a live supervised job's mesh and
  sharded snapshot straight in.

The host-side :class:`ContinuousBatcher` is thread-safe: ``submit`` from
any thread, drive ``step`` from a serving loop (or ``serve_forever`` in a
background thread).
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_engine import layer_state
from tpu_engine.generate import (
    MOE_COUNTS,
    KVCache,
    diff_walk_engages,
    experts_grouped_engages,
    forward_with_cache,
    init_cache,
    init_moe_counts,
    lane_walk_engages,
    ring_lanes,
    scan_layers,
)
from tpu_engine.models.transformer import (
    POWER_TILE,
    ModelConfig,
    check_hybrid,
    embed_tokens,
    refuse_beyond_kv,
    refuse_recurrent,
    served_format,
    unembed,
    weight_bytes_by_dtype,
)
from tpu_engine.ops import lane_decode, power_update, ssd_update
from tpu_engine.profiler import StepProfiler


def _program(name: str, fn, **static):
    """``fn`` with ``static`` bound, under the name its jitted program
    carries in a profile (``jit_<name>``; a bare ``partial`` would be
    ``jit__unknown``)."""
    bound = partial(fn, **static)
    bound.__name__ = name
    return bound


@jax.tree_util.register_dataclass
@dataclass
class SlotCache:
    """Per-slot pool of per-layer state with INDEPENDENT row positions.

    ``layers`` is the tree :mod:`tpu_engine.layer_state` allocates,
    ``{kind: {leaf: [L_kind, B, ...]}}``: what the attention layers keep has a
    lane axis ``S`` that ``lengths`` masks; what a hybrid stack's Mamba-2
    layers keep has no lane to mask, so what keys and values make harmless is
    handled where it happens — pad positions and rows that are not ``active``
    leave it exactly as it was (``generate._ssm_mixer``), and a finished row's
    overshoot steps do advance it, which is why ``_reset_slot`` zeroes it and
    ``_insert_prefill`` overwrites all of it before the slot decodes again.

    ``lengths[b]`` is slot b's global position count (prompt + generated).
    Non-ring pools identify lane m with position m (``pos`` is None);
    ring pools (sliding-window models with fewer lanes than ``max_len``)
    write position p into lane ``p % S`` and track the stored position per
    lane in ``pos`` [B, S] (-1 = empty), mirroring the single-row ring
    cache of :class:`tpu_engine.generate.KVCache`.

    ``moe_counts`` (a mixture's pool only): ``generate.MOE_COUNTS`` of the
    walks since :func:`decode_chunk` last zeroed them, i.e. of one dispatch.

    ``sharded``: the pool lies over a mesh (set by whoever places it there;
    a trace cannot see it otherwise), so its leaves are not one device's to
    walk and a decode step keeps XLA's contractions.
    """

    layers: dict
    lengths: jax.Array  # [B] int32 — resident tokens per slot (0 = empty)
    pos: Optional[jax.Array] = None  # [B, S] int32, ring pools only
    ring: bool = field(default=False, metadata=dict(static=True))
    moe_counts: Optional[jax.Array] = None
    sharded: bool = field(default=False, metadata=dict(static=True))

    @property
    def n_lanes(self) -> int:
        return layer_state.n_lanes(self.layers)

    @property
    def recurrent(self) -> bool:
        return layer_state.keeps_whole_state(self.layers)

    @property
    def recurrent_state_bytes(self) -> int:
        return layer_state.whole_state_bytes(self.layers)

    @property
    def quantized(self) -> bool:
        return layer_state.quantized(self.layers)


def init_slot_cache(
    cfg: ModelConfig, slots: int, max_len: int, dtype=jnp.bfloat16,
    prefill_chunk: Optional[int] = None, kv_quant: bool = False,
) -> SlotCache:
    """Allocate the serving pool. For sliding-window models the pool is a
    per-row ring of ``window + prefill_chunk - 1`` lanes (a prefill chunk
    of T tokens needs the window behind its oldest token resident) — the
    slot-pool analogue of :func:`generate.init_cache`'s ring mode.
    ``kv_quant=True`` stores the pool as int8 codes + per-(lane, kv-head)
    scales — half the serving-pool HBM."""
    check_hybrid(cfg)
    if kv_quant:
        refuse_recurrent(cfg, "an int8 KV pool (kv_quant)")
    lanes = ring_lanes(cfg, max_len, prefill_chunk)
    ring = lanes < max_len
    return SlotCache(
        # A hybrid's window layers keep a ring of one window beside the full
        # kinds' ``lanes`` (a decode step needs no more of them resident; the
        # insert lays a prompt's newest positions out for it).
        layers=layer_state.init_layers(cfg, slots, lanes, dtype, kv_quant,
                                       ring_lanes=cfg.sliding_window if cfg.is_hybrid else None),
        lengths=jnp.zeros((slots,), jnp.int32),
        pos=jnp.full((slots, lanes), -1, jnp.int32) if ring else None,
        ring=ring,
        moe_counts=init_moe_counts(cfg),
    )


def decode_step(
    params: dict[str, Any],
    tokens: jax.Array,      # [B] int32 — last token per slot
    cache: SlotCache,
    active: jax.Array,      # [B] bool — rows that should advance
    cfg: ModelConfig,
    compute_dtype=jnp.bfloat16,
) -> tuple[jax.Array, SlotCache]:
    """One token for every slot. Returns (logits [B, V] fp32, cache).

    Reuses the stock cached walk (``generate.scan_layers`` over
    ``generate._decode_block`` / ``_ssm_block``): the slot pool is just the
    per-row-positions instantiation of its ``write`` callback (row scatter at
    each slot's own lane) and its rank-2 ``slot_pos``. Every architecture
    family the walk supports is therefore served here with zero forked model
    code.

    The walk CARRIES the pool — keys and values ``[L, B, S, KV x HD]`` (an
    int8 pool's codes and scales, the recurrent state of a hybrid) — and each
    layer scatters one row per slot straight into its own lanes of it; a
    layer's keys and values are only read, for attention. No layer is taken
    out and put back and no pool is rebuilt as a scan output, so a jitted
    caller that donates the pool (``decode_chunk`` in the batcher) updates it
    where it lies: one row of ``B × KV × HD`` per layer and step is all that is
    written.

    A pool in which lane m holds position m (not a ring) on one device hands
    the walk each row's visible lanes (``length + 1``, 0 for a row that is not
    active): on a TPU an ``attn`` layer then reads, per active row, the blocks
    of 512 lanes its length covers, from the pool's leaf where it lies, once
    (``generate._decode_block``, the kernel ``ops.lane_decode``), and a row
    that is not active is neither read nor computed — its attention is zeros.
    A ring pool, a pool over a mesh, an int8 pool and every run off the TPU
    keep XLA's two contractions over every lane of every slot.

    Inactive rows still compute their projections (static shapes) but their
    lengths do not advance and their writes land in lanes the mask never
    exposes (for ring pools the overwritten lane held a position already
    outside the window, and its ``pos`` entry is not updated, so the garbage
    stays invisible); a recurrent state has no mask, so a row that is not
    active keeps it exactly.
    """
    B = tokens.shape[0]
    S = cache.n_lanes
    rows = jnp.arange(B)
    positions = cache.lengths[:, None]                      # [B, 1]
    x = embed_tokens(params, tokens[:, None], compute_dtype,
                     positions=positions, cfg=cfg)          # [B, 1, D]

    if cache.ring:
        lane = cache.lengths % S
        # Mark the written lane with its new position — ACTIVE rows only:
        # an inactive row's garbage write must stay invisible.
        pos_new = cache.pos.at[rows, lane].set(
            jnp.where(active, cache.lengths, cache.pos[rows, lane])
        )
        slot_pos = pos_new                                   # [B, S]
        visible = None
    else:
        lane = cache.lengths
        pos_new = None
        # Lane m holds global position m; positions past the row's length
        # are not yet written → the causal mask (m <= length_b) hides them.
        slot_pos = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None, :], (B, S)
        )
        # ... which is the same mask said by a count: a row sees its leading
        # ``length + 1`` lanes, a row that does not advance none (one device's
        # pool: a leaf a mesh shards is not the kernel's to walk).
        visible = None if cache.sharded else jnp.where(active, cache.lengths + 1, 0)

    def write(cache_arr, new_rows, at, ring=False):
        # Per-row scatter at each slot's own lane of layer ``at`` (T = 1).
        # Out-of-bounds lanes (a finished-mid-chunk row running past
        # capacity) drop. Serves the codes and scale arrays of a quantized
        # pool too ([L, B, S, KV, HD] and trailing 1 instead of HD). A
        # ring KIND's leaf (a hybrid's window layers) wraps at its own length.
        return cache_arr.at[at, rows, lane % cache_arr.shape[2] if ring else lane].set(
            new_rows[:, 0].astype(cache_arr.dtype)
        )

    x, cache = scan_layers(x, params["layers"], cfg,
                           cache, write, slot_pos, positions, active[:, None],
                           visible=visible)
    logits = unembed(params, x, cfg)[:, 0]                  # [B, V] fp32
    return logits, dataclasses.replace(
        cache, lengths=cache.lengths + active.astype(jnp.int32), pos=pos_new)


def lane_walk_layers(cfg: ModelConfig, cache: SlotCache) -> int:
    """``attn`` layers of ``cache`` whose keys and values a :func:`decode_step`
    reads through ``ops.lane_decode``, decided as its trace decides (the pool's
    kind, the leaf's shape and dtype, the device); 0 where XLA's contractions
    stay."""
    keys = cache.layers.get("attn", {}).get("k")
    if keys is None or cache.ring or cache.sharded or not lane_walk_engages(keys, 1, cfg):
        return 0
    return keys.shape[0]


def lane_walks(cfg: ModelConfig, cache: SlotCache) -> list[tuple[int, int, int]]:
    """Every read of a :func:`decode_step` that goes through ``ops.lane_decode``,
    decided as its trace decides: (calls a step, lanes of the leaf they walk,
    lanes a slot that does not decode shows them). The ``attn`` kind's layers
    hand such a slot 0 lanes (it owns no block); a differential kind's read
    sees ``length + 1`` lanes of every row, 1 of a freed slot: the full-attention
    layer and every cross-attention layer after it walk the ONE shared cache,
    a window layer its ring."""
    walks = [(lane_walk_layers(cfg, cache), cache.n_lanes, 0)]
    for kind, window, readers in (("full_attn", 0, cfg.n_layers_of("diff_cross_attention")),
                                  ("window_attn", cfg.sliding_window, 0)):
        keys = cache.layers.get(kind, {}).get("k")
        if keys is not None and diff_walk_engages(keys, 1, window):
            walks.append((keys.shape[0] + readers, keys.shape[2], 1))
    return [w for w in walks if w[0]]


def in_place_update_layers(cfg: ModelConfig, cache: SlotCache) -> int:
    """Layers of ``cache``'s whole kinds whose decode step a one-pass kernel
    takes, decided as the trace decides (the leaf and the device): a
    power-retention state through ``ops.power_update``, a Mamba-2 or lightning
    state through ``ops.ssd_update``; 0 where the walk keeps the XLA step."""
    n = 0
    for kind, leaves in cache.layers.items():
        if layer_state.LAYER_KINDS[kind].positional:
            continue
        if kind == "power":
            n += leaves["state"].shape[0] * power_update.engages(leaves["state"], POWER_TILE)
        else:
            n += sum(leaf.shape[0] for leaf in leaves.values() if ssd_update.engages(leaf))
    return n


def _pick_tokens(
    logits: jax.Array,      # [B, V] fp32
    temps: jax.Array,       # [B] f32 — 0 = greedy
    req_ids: jax.Array,     # [B] int32
    counts: jax.Array,      # [B] int32 — tokens already drawn per request
    base_key: jax.Array,
) -> jax.Array:
    """Per-slot sampling INSIDE the dispatch. Greedy rows take argmax;
    temperature>0 rows draw categorically with a key folded from
    (request id, draw count) — the stream for a request is deterministic
    for a given server ``seed`` and independent of which other requests
    share the batch or when they were admitted."""
    def draw(rid, cnt, lg, t):
        key = jax.random.fold_in(jax.random.fold_in(base_key, rid), cnt)
        return jax.random.categorical(key, lg / jnp.maximum(t, 1e-6))

    with jax.named_scope("sample"):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        sampled = jax.vmap(draw)(req_ids, counts, logits, temps).astype(jnp.int32)
        return jnp.where(temps > 0.0, sampled, greedy)


def decode_chunk(
    params: dict[str, Any],
    tokens: jax.Array,      # [B] int32 — last token per slot
    cache: SlotCache,
    active: jax.Array,      # [B] bool
    temps: jax.Array,       # [B] f32
    req_ids: jax.Array,     # [B] int32
    counts: jax.Array,      # [B] int32
    base_key: jax.Array,
    cfg: ModelConfig,
    n_steps: int,
    compute_dtype=jnp.bfloat16,
) -> tuple[jax.Array, SlotCache]:
    """``n_steps`` tokens per active slot in ONE dispatch (greedy and
    sampled alike — see :func:`_pick_tokens`).

    The host drives :func:`decode_step` one token at a time — fine
    on-chip, but each step pays a host→device round trip (expensive
    through remote runtimes). This scans the same step with in-scan token
    feedback, so a chunk of N tokens costs one dispatch + one [B, N]
    transfer. The host trims per-request overshoot (a request hitting eos
    or max_new_tokens mid-chunk): the finished slot is simply reset, so
    its overshoot lanes are masked and later admissions overwrite them.
    A queued request waits at most ``n_steps`` tokens for the next
    admission window — the chunk no longer disengages under load.

    A mixture's pool leaves with ``moe_counts`` those of THIS dispatch (zeroed
    here, summed over its steps and layers): the host reads them in the fetch
    that brings the tokens.

    The pool ``[L, B, S, KV x HD]`` rides in the scan's carry, donated: every
    step writes one row a layer and slot into it and reads, on a TPU, only the
    lanes the active slots have (:func:`decode_step`); no copy, transposition
    or slice of a whole layer is in the compiled program.
    """
    cache = dataclasses.replace(cache, moe_counts=init_moe_counts(cfg))

    def one(carry, _):
        toks, cnts, cache = carry
        logits, cache = decode_step(params, toks, cache, active, cfg,
                                    compute_dtype)
        nxt = _pick_tokens(logits, temps, req_ids, cnts, base_key)
        toks = jnp.where(active, nxt, toks)
        cnts = cnts + active.astype(jnp.int32)
        return (toks, cnts, cache), nxt

    (_, _, cache), out = lax.scan(
        one, (tokens, counts, cache), None, length=n_steps
    )
    return out.T, cache  # [B, n_steps]


def decode_verify(
    params: dict[str, Any],
    tokens: jax.Array,      # [B, T] int32 — chain of inputs per slot
    cache: SlotCache,
    active: jax.Array,      # [B] bool
    cfg: ModelConfig,
    compute_dtype=jnp.bfloat16,
) -> tuple[jax.Array, SlotCache]:
    """T tokens per slot in ONE forward (the speculative verify pass).

    Row b's inputs sit at positions ``lengths[b] + arange(T)``; their K/V
    rows are written before attention (so in-chain causality is the
    ordinary position mask), and logits for ALL T inputs come back —
    logits[b, i] scores the token following input i. Lengths advance by T
    for active rows; the CALLER rewinds them to the accepted frontier
    (free under per-row positions: lanes past a row's length are masked
    and the next round's chain overwrites them before exposure).
    Non-ring pools only (speculative serving rejects window models), and
    attention-only stacks: rewinding to the accepted frontier is free for
    keys and values and impossible for a recurrent state. The pool is
    carried through the layer walk as in :func:`decode_step`; a layer's
    ``[B, T]`` rows are one scatter into its lanes of it."""
    refuse_beyond_kv(cfg, "the speculative verify pass (decode_verify)")
    B, T = tokens.shape
    S = cache.n_lanes
    rows = jnp.arange(B)
    positions = cache.lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    x = embed_tokens(params, tokens, compute_dtype,
                     positions=positions, cfg=cfg)  # [B, T, D]
    slot_pos = jnp.broadcast_to(
        jnp.arange(S, dtype=jnp.int32)[None, :], (B, S)
    )

    def write(cache_arr, new_rows, at):  # new_rows [B, T, KV x HD] (int8: [B, T, KV, HD | 1])
        return cache_arr.at[at, rows[:, None], positions].set(
            new_rows.astype(cache_arr.dtype)
        )

    x, cache = scan_layers(x, params["layers"], cfg,
                           cache, write, slot_pos, positions)
    logits = unembed(params, x, cfg)  # [B, T, V] fp32
    return logits, dataclasses.replace(
        cache, lengths=cache.lengths + T * active.astype(jnp.int32))


def speculative_round(
    params: dict[str, Any],
    draft_params: dict[str, Any],
    tokens: jax.Array,      # [B] int32 — last emitted token per slot
    cache: SlotCache,
    draft_cache: SlotCache,
    active: jax.Array,      # [B] bool
    cfg: ModelConfig,
    draft_cfg: ModelConfig,
    gamma: int,
    compute_dtype=jnp.bfloat16,
) -> tuple[jax.Array, jax.Array, SlotCache, SlotCache]:
    """One batched draft-propose / target-verify round for EVERY slot.

    The slot-pool generalisation of :func:`generate.speculative_generate`
    (single request, its own cache invariant: resident K/V = every token
    EXCEPT the last emitted — which is exactly the serving pool's steady
    state, since each decode writes its INPUT token's K/V). The draft
    proposes ``gamma`` greedy tokens per slot autoregressively (one extra
    step ingests its own last proposal's K/V — a fully-accepted round
    would otherwise leave a permanent draft-cache hole); the target
    verifies all slots' chains in ONE ``T = gamma+1`` forward; per-row
    acceptance is the longest agreeing prefix plus the target's
    correction/bonus token. Both caches rewind per-row to the accepted
    frontier — a [B]-vector subtraction; rejected lanes stay masked until
    the next round's chain overwrites them.

    Returns (tgt [B, gamma+1] candidate tokens, n_acc [B] accepted counts
    (1..gamma+1), target cache, draft cache). Output streams are
    token-identical to plain greedy serving wherever the target's chunked
    and incremental argmax agree (bit-exact on CPU; ~1e-2 logit deltas on
    TPU can flip near-ties — same caveat as ``speculative_generate``)."""

    def dstep(carry, _):
        toks, dc = carry
        logits, dc = decode_step(draft_params, toks, dc, active, draft_cfg,
                                 compute_dtype)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        toks = jnp.where(active, nxt, toks)
        return (toks, dc), nxt

    (_, draft_cache), props = lax.scan(
        dstep, (tokens, draft_cache), None, length=gamma + 1
    )
    proposals = props[:gamma].T                      # [B, gamma]
    chain = jnp.concatenate([tokens[:, None], proposals], axis=1)  # [B, g+1]

    logits, cache = decode_verify(params, chain, cache, active, cfg,
                                  compute_dtype)
    tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, g+1]
    matches = (proposals == tgt[:, :gamma]).astype(jnp.int32)
    n_acc = jnp.sum(jnp.cumprod(matches, axis=1), axis=1) + 1  # [B] 1..g+1

    # Rewind both caches to the accepted frontier: resident = everything
    # except the new last token (tgt[:, n_acc-1]).
    overshoot = jnp.where(active, (gamma + 1) - n_acc, 0).astype(jnp.int32)
    cache = dataclasses.replace(cache, lengths=cache.lengths - overshoot)
    # The draft ran gamma+1 steps; its frontier rewinds to match exactly.
    draft_cache = dataclasses.replace(
        draft_cache, lengths=draft_cache.lengths - overshoot)
    return tgt, n_acc, cache, draft_cache


def _slice_prefix(c1: KVCache, L: int) -> KVCache:
    """First ``L`` lanes of a single-row ingestion cache — the stored
    form of a prefix-cache entry (non-ring caches only: lane == position)."""
    return KVCache(layers=layer_state.slice_lanes(c1.layers, L), pos=c1.pos[:L],
                   length=jnp.asarray(L, jnp.int32), ring=False)


def _paste_prefix(c1: KVCache, entry: KVCache, use_len: jax.Array,
                  lanes: int) -> KVCache:
    """Write the first ``lanes`` lanes of a cached prefix into a fresh
    ingestion cache and set its length to ``use_len`` (<= lanes) — the
    prompt's remaining tokens then prefill from there.

    ``use_len`` may sit strictly inside the pasted lanes: lanes at
    positions >= use_len hold K/V of tokens the new prompt does NOT share,
    but the position mask (position < length) hides them and the resumed
    prefill overwrites each one before the frontier reaches it. That
    masking is what makes TOKEN-granular reuse free — the cache stores
    chunk-aligned entries, yet a prompt sharing any prefix of one reuses
    every full ``grain`` of the shared tokens."""
    return dataclasses.replace(
        c1, layers=layer_state.paste_lanes(c1.layers, entry.layers, lanes),
        pos=lax.dynamic_update_slice(c1.pos, entry.pos[:lanes], (0,)),
        length=use_len.astype(jnp.int32),
    )


class _PrefixCache:
    """LRU cache of prompt-prefix KV (host-side bookkeeping; entries are
    device-resident :class:`KVCache` slices).

    Entries are STORED at ``prefill_chunk`` boundaries (one per prefill
    walk — its last cacheable boundary — so a cold N-token prefix costs
    one slice of N lanes, never an O(N²) chain of nested copies). Reuse
    is TOKEN-granular: ``lookup`` finds the entry with the longest
    token-level common prefix and returns that length floored to
    ``grain`` lanes, so a prompt sharing 1023 of a stored 1024-token
    prefix reuses 15 of its 16 chunks instead of zero (round-4 verdict
    weakness 6), and an identical chunk-aligned resubmission reuses
    everything but the final grain (round-4 advisor finding: the old
    boundary-keyed lookup could never hit those). Budgeted in TOKENS
    (eviction drops least-recently-used entries until a new entry fits).

    Host cost per lookup is one vectorised compare per entry —
    O(entries × prefix_len) int64 compares, bounded by
    budget²/chunk bytes scanned but with no per-boundary tuple hashing
    (the round-4 advisor's O(budget²) hashing concern)."""

    def __init__(self, budget_tokens: int, chunk: int, grain: int = 0):
        self.budget = int(budget_tokens)
        self.chunk = int(chunk)
        # Reuse quantum: hit lengths are floored to this so resumed
        # prefill offsets (and therefore compiled chunk widths) stay
        # multiples of the pad bucket. Defaults to the chunk itself.
        self.grain = int(grain) or int(chunk)
        self._entries: "collections.OrderedDict[tuple, KVCache]" = \
            collections.OrderedDict()
        self._keys: dict[tuple, np.ndarray] = {}
        self._hit_counts: dict[tuple, int] = {}
        self.tokens = 0
        self.hits = 0
        self.misses = 0
        # Reuse signal: total KV tokens served from cache instead of
        # re-prefilled. hits counts lookups; this counts what they saved —
        # the number reuse-driven eviction (and the fleet prefix plane's
        # historian series) actually score on.
        self.hit_tokens = 0

    def lookup(self, prompt: list[int]) -> tuple[int, Optional[KVCache]]:
        """Longest token-level common prefix with any stored entry,
        floored to ``grain`` and capped STRICTLY before the prompt's
        last token (the final token must still prefill — its logits seed
        the first generated token). Returns (use_len, entry|None).
        Compare depth is capped at the budget (no longer entry can
        exist), so host work is budget-bounded, not prompt-bounded."""
        limit = min(len(prompt) - 1, self.budget)
        if limit <= 0 or not self._entries:
            self.misses += 1
            return 0, None
        window = np.asarray(prompt[:limit], dtype=np.int64)
        best_use, best_key = 0, None
        for key, arr in self._keys.items():
            n = min(arr.size, limit)
            diff = np.flatnonzero(arr[:n] != window[:n])
            common = int(n if diff.size == 0 else diff[0])
            use = (common // self.grain) * self.grain
            if use > best_use:
                best_use, best_key = use, key
        if best_key is None:
            self.misses += 1
            return 0, None
        self._entries.move_to_end(best_key)
        self.hits += 1
        self.hit_tokens += best_use
        self._hit_counts[best_key] = self._hit_counts.get(best_key, 0) + 1
        return best_use, self._entries[best_key]

    def wants(self, prefix: tuple) -> bool:
        """True iff ``insert`` would store this key — checked BEFORE the
        caller pays the device slice, so rejected boundaries cost no
        copies."""
        return len(prefix) <= self.budget and prefix not in self._entries

    def _drop(self, key: tuple) -> None:
        old = self._entries.pop(key)
        self._keys.pop(key)
        self._hit_counts.pop(key, None)
        self.tokens -= old.max_len

    def insert(self, prefix: tuple, entry: KVCache) -> None:
        if not self.wants(prefix):
            return
        # Charge the entry's DEVICE footprint (its lane count), the same
        # unit _drop credits back — charging the key length instead lets an
        # entry whose lanes exceed its key corrupt the token ledger (tokens
        # goes negative on its eviction, and the budget never evicts
        # again). An entry that alone exceeds the whole budget is rejected
        # outright: evicting every resident prefix to fit one oversized
        # slice trades the fleet's shared working set for an entry whose
        # excess lanes can never be hit.
        size = int(entry.max_len)
        if size > self.budget:
            return
        while self.tokens + size > self.budget and self._entries:
            self._drop(next(iter(self._entries)))
        self._entries[prefix] = entry
        self._keys[prefix] = np.asarray(prefix, dtype=np.int64)
        self.tokens += size

    def reuse_counts(self) -> dict[tuple, int]:
        """Per-resident-entry lookup-hit counts (entries never hit read 0)."""
        return {k: self._hit_counts.get(k, 0) for k in self._entries}

    def stats(self) -> dict[str, Any]:
        return {
            "entries": len(self._entries), "tokens": self.tokens,
            "hits": self.hits, "misses": self.misses,
            "hit_tokens_total": self.hit_tokens,
            # LRU order (coldest first) — the eviction order a reuse-aware
            # policy would second-guess.
            "entry_hits": [
                {"prefix_tokens": len(k), "hits": self._hit_counts.get(k, 0)}
                for k in self._entries
            ],
        }


@dataclass
class Request:
    """One generation request's lifecycle (host-side bookkeeping)."""

    id: int
    prompt: list[int]
    max_new_tokens: int
    temperature: float
    status: str = "queued"        # queued | running | done | failed
    error: Optional[str] = None
    tokens: list[int] = field(default_factory=list)
    slot: Optional[int] = None
    # Lifecycle stamps, all on time.time(): submitted → admitted (slot
    # taken) → prefill_started (first chunk begins) → first token → finished.
    # A wire-prefilled request (submit_prefilled) never prefills here.
    submitted_at: float = field(default_factory=time.time)
    admitted_at: Optional[float] = None
    prefill_started_at: Optional[float] = None
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    # Disaggregated serving: a finished ``hold_kv`` request keeps its slot
    # (and the prompt K/V in it) resident until the handoff plane extracts
    # or releases it — see :mod:`tpu_engine.disagg`.
    hold_kv: bool = False


class SpecGeometryError(ValueError):
    """A draft/target pairing whose geometry can never run a
    ``speculative_round`` — rejected at construction, not mid-decode.
    Structured (``.reason`` with a ``"kind"`` key) so fleet-level callers
    (:mod:`tpu_engine.spec_pool`, admission planes) can surface the
    rejection without parsing the message."""

    def __init__(self, kind: str, message: str, **detail: object):
        self.kind = kind
        self.reason = {"kind": kind, **detail}
        super().__init__(message)


@dataclass
class _PrefillState:
    """A prompt mid-ingestion: ``consumed`` of ``padded`` tokens are in
    ``c1`` (single-row cache); advanced one bounded chunk per engine step
    so running slots never stall behind a whole long prompt. Speculative
    servers ingest the prompt into the draft model's cache too (``dc1``)."""

    req: Request
    slot: int
    toks: np.ndarray    # [1, padded] int32 — prompt, zero-padded
    c1: Optional[KVCache] = None  # allocated when the first chunk runs
    consumed: int = 0
    chunks: int = 0     # chunks run so far (a prefix hit skips some)
    dc1: Optional[KVCache] = None
    prefix_checked: bool = False

    @property
    def padded(self) -> int:
        return self.toks.shape[1]


# Phases of ``ContinuousBatcher.step`` on its phase clock (StepProfiler):
# ``profile()["phases"]`` and the ``tpu_engine.batcher.<phase>`` trace
# annotations carry these names.
BATCHER_PHASES = (
    "handoff",      # disaggregated-serving extraction orders
    "admit",        # locked admission pass + ingestion-cache allocation
    "prefill",      # one _advance_prefill chunk
    "first_token",  # sampling from the prefill logits + its emission
    "stage",        # host arrays, transfers and the decode/speculative dispatch
    "device",       # the blocking token read
    "emit",         # the locked emission loop
    "idle",         # idle_wait: the driving loop's wait between two steps
)


class ContinuousBatcher:
    """Slot-pool batcher over :func:`decode_chunk`.

    ``submit`` is thread-safe; ``step`` admits queued prompts into free
    slots (one bounded prefill chunk per step), then advances every active
    slot ``chunk_steps`` tokens in one dispatch — greedy or sampled.
    Streams are reproducible for a given ``seed``.

    ``mesh`` (optional) serves models larger than one chip: pass the
    training job's mesh and its sharded params; the KV pool shards
    kv-heads over the ``model`` axis and all dispatches pin their
    out-shardings. The pool is donated to every dispatch and carried
    through the layer walk (``generate.scan_layers``), which writes a step's
    rows in place: the pool is never copied, whole or by the layer.
    """

    def __init__(
        self,
        params: Any,
        cfg: ModelConfig,
        max_slots: int = 8,
        max_len: int = 1024,
        compute_dtype=jnp.bfloat16,
        eos_id: Optional[int] = None,
        seed: int = 0,
        prefill_pad_to: int = 64,
        chunk_steps: int = 1,
        prefill_chunk: int = 256,
        mesh: Optional[Mesh] = None,
        stats_window_s: float = 30.0,
        draft_params: Any = None,
        draft_cfg: Optional[ModelConfig] = None,
        spec_gamma: int = 4,
        kv_quant: bool = False,
        prefix_cache_tokens: int = 0,
    ):
        # The engine holds its weights ONCE, as its programs read them
        # (``served_format``): whatever tree a caller brings — a training
        # job's float32 master, an int8 snapshot, a mesh-sharded tree — is
        # converted here and no program casts it again.
        self.params = served_format(params, compute_dtype)
        self._weight_bytes = weight_bytes_by_dtype(self.params)
        self.cfg = cfg
        self.max_slots = int(max_slots)
        self.max_len = int(max_len)
        self.eos_id = eos_id
        self.seed = seed
        self.prefill_pad_to = int(prefill_pad_to)
        # Prefill ingestion quantum: one chunk per engine step (bounded
        # decode stall). Round to the pad bucket so chunk shapes stay few.
        self.prefill_chunk = max(
            -(-int(prefill_chunk) // self.prefill_pad_to) * self.prefill_pad_to,
            self.prefill_pad_to,
        )
        self.chunk_steps = max(int(chunk_steps), 1)
        self.mesh = mesh
        self.kv_quant = bool(kv_quant)
        self._compute_dtype = compute_dtype
        # What assumes that a slot's state is keys and values is refused for
        # a stack with recurrent layers, by name, before anything is built
        # (kv_quant: init_slot_cache refuses it).
        if mesh is not None:
            refuse_recurrent(cfg, "mesh-sharded serving")
        if prefix_cache_tokens:
            refuse_recurrent(cfg, "the prompt-prefix cache (prefix_cache_tokens)")
        if draft_params is not None:
            refuse_beyond_kv(cfg, "speculative serving (draft_params)")
            if draft_cfg is not None:
                refuse_beyond_kv(draft_cfg, "speculative serving (as the draft)")
        self._cache = init_slot_cache(
            cfg, self.max_slots, self.max_len, compute_dtype,
            prefill_chunk=self.prefill_chunk, kv_quant=self.kv_quant,
        )
        self._base_key = jax.random.PRNGKey(seed)

        # -- sharding surface (mesh-sharded serving) ------------------------
        self._cache_sh = self._rep = None
        if mesh is not None:
            self._rep = NamedSharding(mesh, P())
            self._cache = dataclasses.replace(self._cache, sharded=True)
            self._cache_sh = layer_state.cache_shardings(mesh, cfg, self._cache)
            self._cache = jax.device_put(self._cache, self._cache_sh)
            self._base_key = jax.device_put(self._base_key, self._rep)

        # -- speculative decoding (draft-propose / batched verify) ----------
        self._draft_params = None
        self._draft_cfg = draft_cfg
        self.spec_gamma = int(spec_gamma)
        self._draft_cache = None
        if draft_params is not None:
            if draft_cfg is None:
                raise SpecGeometryError(
                    "draft_cfg_missing", "draft_params requires draft_cfg"
                )
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise SpecGeometryError(
                    "draft_vocab_mismatch",
                    f"draft vocab {draft_cfg.vocab_size} != target vocab "
                    f"{cfg.vocab_size}: speculative verify compares token ids",
                    draft_vocab=draft_cfg.vocab_size,
                    target_vocab=cfg.vocab_size,
                )
            if self._cache.ring or cfg.sliding_window or draft_cfg.sliding_window:
                raise SpecGeometryError(
                    "draft_ring_window",
                    "speculative serving does not support sliding-window "
                    "models (the verify chain's rewind assumes flat lanes)",
                    target_window=cfg.sliding_window,
                    draft_window=draft_cfg.sliding_window,
                )
            if mesh is not None:
                raise SpecGeometryError(
                    "draft_mesh_sharded",
                    "speculative serving does not run mesh-sharded yet; "
                    "drop draft_params or mesh",
                )
            if self.spec_gamma < 1:
                raise SpecGeometryError(
                    "spec_gamma_invalid",
                    f"spec_gamma must be >= 1, got {spec_gamma}",
                    spec_gamma=self.spec_gamma,
                )
            self._draft_params = served_format(draft_params, compute_dtype)
            self._draft_weight_bytes = weight_bytes_by_dtype(self._draft_params)
            self._draft_cache = init_slot_cache(
                draft_cfg, self.max_slots, self.max_len, compute_dtype,
                prefill_chunk=self.prefill_chunk,
            )
            self._spec = jax.jit(
                _program("speculative_round", speculative_round, cfg=cfg,
                         draft_cfg=draft_cfg, gamma=self.spec_gamma,
                         compute_dtype=compute_dtype),
                donate_argnums=(3, 4),  # both pools alias across rounds
            )
            # The draft's prompt ingestion needs no logits — skip the
            # T×D×V unembed per chunk (it would rival the whole 2-layer
            # draft forward it accompanies).
            self._draft_prefill_fn = jax.jit(
                _program("draft_prefill_chunk", _draft_prefill_ingest,
                         cfg=draft_cfg, compute_dtype=compute_dtype),
                donate_argnums=(2,),
            )
            # The draft's pool is inserted into and reset by the target's own
            # programs (``self._insert`` / ``self._reset``: no mesh here, so no
            # out-shardings tell them apart).

        # -- prompt-prefix KV cache (shared system prompts) -----------------
        self._prefix_cache: Optional[_PrefixCache] = None
        if prefix_cache_tokens:
            if self._cache.ring:
                raise ValueError(
                    "prefix_cache_tokens does not support sliding-window "
                    "models (ring lanes wrap — a stored prefix's lanes are "
                    "not position-stable)"
                )
            if draft_params is not None:
                raise ValueError(
                    "prefix_cache_tokens with speculative serving is not "
                    "supported (the draft cache would miss the prefix and "
                    "desynchronise)"
                )
            self._prefix_cache = _PrefixCache(prefix_cache_tokens,
                                              self.prefill_chunk,
                                              grain=self.prefill_pad_to)
            # Slice/paste shapes are static per (cache size, lanes) pair;
            # stored-entry lane counts are prefill_chunk multiples and the
            # traced use_len carries the token-granular hit length, so
            # compiled variants stay few.
            self._slice_prefix = jax.jit(
                _program("slice_prefix", _slice_prefix), static_argnums=(1,))
            self._paste_prefix = jax.jit(
                _program("paste_prefix", _paste_prefix),
                donate_argnums=(0,), static_argnums=(3,),
                out_shardings=None if mesh is None else layer_state.cache_shardings(
                    mesh, cfg, jax.eval_shape(lambda: init_cache(
                        cfg, 1, self.prefill_chunk, compute_dtype,
                        kv_quant=self.kv_quant))),
            )

        self._decode = jax.jit(
            _program("decode_chunk", decode_chunk, cfg=cfg,
                     n_steps=self.chunk_steps, compute_dtype=compute_dtype),
            donate_argnums=(2,),  # the pool: alias, never copy (2x HBM)
            out_shardings=None if mesh is None else (self._rep, self._cache_sh),
        )
        self._prefill_fn = jax.jit(
            _program("prefill_chunk", _prefill_forward, cfg=cfg,
                     compute_dtype=compute_dtype),
            donate_argnums=(2,),
        )
        # NOTE: c1 (arg 1) is dead after the insert but NOT donated — its
        # [L, 1, M, ...] buffers can never alias the [L, slots, S, ...]
        # pool, so donation would only emit "unusable donation" warnings.
        self._insert = jax.jit(
            _program("insert_prefill", _insert_prefill),
            donate_argnums=(0,), static_argnums=(4,),
            out_shardings=None if mesh is None else self._cache_sh,
        )
        self._reset = jax.jit(
            _program("reset_slot", _reset_slot), donate_argnums=(0,),
            out_shardings=None if mesh is None else self._cache_sh,
        )
        # A stack with a cross-decoder ingests a prompt by two programs: every
        # chunk but the last stops at the cache the cross-decoder reads.
        self._ingest_fn = None
        if cfg.cross_decoder_start is not None:
            self._ingest_fn = jax.jit(
                _program("prefill_ingest", _prefill_ingest, cfg=cfg,
                         compute_dtype=compute_dtype),
                donate_argnums=(2,),
            )

        self._slots: list[Optional[Request]] = [None] * self.max_slots
        self._last_tokens = np.zeros((self.max_slots,), np.int32)
        self._queue: list[Request] = []
        self._requests: dict[int, Request] = {}
        self._ids = itertools.count()
        self._prefilling: "collections.OrderedDict[int, _PrefillState]" = \
            collections.OrderedDict()
        self._pending_first_logits: dict[int, np.ndarray] = {}
        # -- disaggregated-serving handoff plane (see tpu_engine/disagg.py).
        # _held maps a finished hold_kv request to the slot still pinning
        # its K/V; _handoff_requests queues (req_id, quantize|None) orders
        # for the ENGINE thread (None = discard); _handoffs holds extracted
        # wire payloads until the caller collects them; _prefilled_queue
        # holds incoming KVHandoff payloads awaiting a free slot.
        self._held: dict[int, int] = {}
        self._handoff_requests: list[tuple[int, Optional[bool]]] = []
        self._handoffs: dict[int, Any] = {}
        self._prefilled_queue: list[tuple[Request, Any]] = []
        self.handoffs_out = 0
        self.handoffs_in = 0
        if cfg.arch == "gpt2" and max_len > cfg.max_seq_len:
            raise ValueError(
                f"max_len {max_len} exceeds the learned position table "
                f"(max_seq_len={cfg.max_seq_len}) of gpt2-family model"
            )
        self._lock = threading.Lock()
        self._done = threading.Condition(self._lock)
        self._tokens_out = 0
        # The engine loop's phase clock (see StepProfiler) and the decode
        # layer's attempted and useful token counts; engine-thread writes only.
        self._profiler = StepProfiler(loop="batcher", phases=BATCHER_PHASES)
        self._idle_waits = 0
        self._idle_waits_with_work = 0
        self._decode_tokens_computed = 0
        self._decode_tokens_emitted = 0
        # Of the computed token-steps, those whose context was past a
        # sparse-attention stack's ``sparse_dense_len`` (they chose their
        # blocks); stays 0 for a stack with no such layer.
        self._sparse_from = cfg.sparse_dense_len \
            if "sparse_attention" in cfg.layer_types else None
        self._decode_tokens_sparse = 0
        # Prompt positions a prefill chunk computed (the bucket's padding
        # among them), and those of them at or past ``sparse_dense_len``:
        # how often block choice, not only the causal bound, engages.
        self._prefill_tokens_computed = 0
        self._prefill_tokens_sparse = 0
        # Of those positions, the ones a prefill program ran the output head
        # for: one a chunk that forms logits (a stack with a cross-decoder
        # runs that at the same position alone).
        self._prefill_head_rows = 0
        # Recurrent state written into a slot by a finished prefill, and
        # zeroed when a slot is freed (hybrid stacks; else both stay 0).
        self._recurrent_state_bytes = self._cache.recurrent_state_bytes
        # The pool's latent-attention (MLA) leaves: every slot's every lane.
        self._latent_cache_bytes = layer_state.latent_bytes(self._cache.layers)
        # The one full-attention cache that cross-attention layers read, and
        # the window layers' rings beside it (0 for a stack without them).
        self._shared_kv_bytes = layer_state.lane_bytes(self._cache.layers, "full_attn") \
            if cfg.cross_decoder_start is not None else 0
        self._window_kv_bytes = layer_state.ring_bytes(self._cache.layers)
        # Layers of the whole kinds whose decode step a one-pass kernel takes
        # (``ops.ssd_update``, ``ops.power_update``: decided where the program
        # is traced, from the leaf and the device); 0 where the walk keeps the
        # XLA step. And the power-retention layers' decode layer-steps run.
        self._in_place_layers = in_place_update_layers(cfg, self._cache)
        self._recurrent_updates_in_place = 0
        self._power_layers = cfg.n_layers_of("power_retention")
        self._power_layer_steps = 0
        # ``attn`` layers whose decode step reads the pool through the
        # lane-walking kernel (``ops.lane_decode``; a speculative engine's
        # target never steps one token), the lanes of keys it read there (and
        # as many of values) and the lanes the pool holds for them.
        self._pool_lanes = self._cache.n_lanes
        self._lane_walk_layers = 0 if draft_params is not None \
            else lane_walk_layers(cfg, self._cache)
        self._decode_attn_lanes_read = 0
        self._decode_attn_lanes_pool = 0
        # Every read a decode step makes through that kernel, the differential
        # kinds' too: the blocks its one walk over all slots carried and the
        # grid steps it took for them.
        self._lane_walks = [] if draft_params is not None else lane_walks(cfg, self._cache)
        self._decode_attn_blocks_walked = 0
        self._decode_attn_grid_steps = 0
        # A mixture's routing, by program: layer-steps run (counted here) and
        # ``generate.MOE_COUNTS`` (counted on the device, fetched with each
        # dispatch's tokens). Empty for a model without experts.
        self._moe_counts = {
            program: dict.fromkeys(("layer_steps",) + MOE_COUNTS, 0)
            for program in (("decode", "prefill") if cfg.is_moe else ())}
        # Prefill chunks whose program runs its held experts grouped over the
        # routed pairs (``generate.experts_grouped_engages``: a property of
        # the chunk's shape and of the experts' stacked leaf, known here
        # without asking the device).
        stacks = self.params["layers"]
        self._expert_gate = next((s["gate"]["kernel"] for s in (stacks.values() if cfg.is_hybrid else [stacks])
                                  if "gate" in s), None) if cfg.is_moe else None  # (a dense MLP has a gate too)
        self._moe_grouped_chunks = 0
        self._shared_expert_bytes = sum(
            a.size * a.dtype.itemsize
            for path, a in jax.tree_util.tree_leaves_with_path(self.params)
            if any(str(getattr(k, "key", "")).startswith("shared_") for k in path))
        self._state_inserts = 0
        self._state_resets = 0
        self._spec_rounds = 0
        self._spec_accepted = 0
        self._started = time.time()
        self._stats_window_s = float(stats_window_s)
        self._recent: collections.deque[tuple[float, int]] = collections.deque()
        self.last_error: Optional[str] = None

    # -- client side ---------------------------------------------------------

    def submit(self, prompt: list[int], max_new_tokens: int = 64,
               temperature: float = 0.0, hold_kv: bool = False) -> int:
        if self.last_error is not None:
            raise RuntimeError(f"serving loop failed: {self.last_error}")
        if not prompt:
            raise ValueError("empty prompt")
        if temperature > 0.0 and self._draft_params is not None:
            raise ValueError(
                "speculative server is greedy-only: temperature>0 requests "
                "would desynchronise the draft cache (verify is exact only "
                "for argmax streams); start a non-speculative server for "
                "sampling"
            )
        if hold_kv:
            refuse_beyond_kv(self.cfg, "hold_kv (the KV handoff plane)")
        if hold_kv and self._cache.ring:
            raise ValueError(
                "hold_kv does not support sliding-window models (ring lanes "
                "wrap — the held slot's lanes are not position-stable for "
                "extraction)"
            )
        if hold_kv and self._draft_params is not None:
            raise ValueError(
                "hold_kv with speculative serving is not supported (the "
                "draft cache cannot travel on the handoff wire)"
            )
        if len(prompt) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds the server's max_len {self.max_len}"
            )
        req = Request(id=next(self._ids), prompt=list(prompt),
                      max_new_tokens=int(max_new_tokens),
                      temperature=float(temperature), hold_kv=bool(hold_kv))
        with self._lock:
            # Re-check under the lock: the failure handler drains the queue
            # holding it, so a submit racing the shutdown cannot strand a
            # request in "queued" with no engine thread left to serve it.
            if self.last_error is not None:
                raise RuntimeError(f"serving loop failed: {self.last_error}")
            self._requests[req.id] = req
            self._queue.append(req)
        return req.id

    # -- disaggregated-serving handoff surface (see tpu_engine/disagg.py) ----

    def submit_prefilled(self, handoff: Any, max_new_tokens: int = 64,
                         temperature: float = 0.0) -> int:
        """Admit a request whose prompt K/V arrives on the handoff wire
        (a :class:`tpu_engine.disagg.KVHandoff` extracted from a prefill
        pool) instead of being prefilled here. The engine inserts the wire
        K/V into a free slot via the ordinary ``_insert_prefill`` path and
        the request goes straight to decode — no prompt forward runs on
        this engine. Token history (prompt + tokens the prefill engine
        already emitted) counts against ``max_len``; ``max_new_tokens``
        bounds the tokens THIS engine adds."""
        if self.last_error is not None:
            raise RuntimeError(f"serving loop failed: {self.last_error}")
        refuse_beyond_kv(self.cfg, "submit_prefilled (the KV handoff wire)")
        if self._cache.ring:
            raise ValueError(
                "submit_prefilled does not support sliding-window pools"
            )
        if self._draft_params is not None:
            raise ValueError(
                "submit_prefilled with speculative serving is not supported "
                "(the draft cache has no wire form)"
            )
        history = list(handoff.prompt) + list(handoff.emitted)
        if handoff.length != len(history) - 1:
            raise ValueError(
                f"handoff length {handoff.length} != resident invariant "
                f"(history {len(history)} - 1): wire payload is inconsistent"
            )
        self._check_handoff_geometry(handoff)
        if len(history) + max_new_tokens > self.max_len:
            raise ValueError(
                f"handoff history ({len(history)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the server's max_len "
                f"{self.max_len}"
            )
        # ``prompt`` holds the FULL token history so _emit's max_len guard
        # and attention-length bookkeeping see the true context size; the
        # last history token is the decode input (resident K/V = everything
        # except it — exactly the pool's steady-state invariant).
        req = Request(id=next(self._ids), prompt=history,
                      max_new_tokens=int(max_new_tokens),
                      temperature=float(temperature))
        with self._lock:
            if self.last_error is not None:
                raise RuntimeError(f"serving loop failed: {self.last_error}")
            self._requests[req.id] = req
            self._prefilled_queue.append((req, handoff))
        return req.id

    def _check_handoff_geometry(self, handoff: Any) -> None:
        if (handoff.n_layers, handoff.n_kv_heads, handoff.head_dim) != \
                (self.cfg.n_layers, self.cfg.n_kv_heads, self.cfg.head_dim):
            raise ValueError(
                "handoff KV geometry does not match this engine's model "
                f"({handoff.n_layers}L/{handoff.n_kv_heads}KV/"
                f"{handoff.head_dim}HD vs {self.cfg.n_layers}L/"
                f"{self.cfg.n_kv_heads}KV/{self.cfg.head_dim}HD)"
            )

    def request_handoff(self, req_id: int, quantize: bool = False) -> None:
        """Order the ENGINE thread to extract the held slot's K/V into a
        wire payload (collect with :meth:`take_handoff`/:meth:`wait_handoff`)
        and free the slot. Only valid for a finished ``hold_kv`` request."""
        with self._lock:
            req = self._requests.get(req_id)
            if req is None:
                raise KeyError(req_id)
            if not req.hold_kv:
                raise ValueError(f"request {req_id} was not submitted hold_kv")
            self._handoff_requests.append((req_id, bool(quantize)))

    def release_held(self, req_id: int) -> None:
        """Discard a held slot's K/V without extracting (the fleet gave up
        on the handoff — e.g. the request was cancelled)."""
        with self._lock:
            self._handoff_requests.append((req_id, None))

    def held_requests(self) -> list[int]:
        """Request ids currently pinning a held slot — the reshard
        plane's drain worklist (``tpu_engine.reshard.migrate_held_requests``)."""
        with self._lock:
            return sorted(self._held)

    def take_handoff(self, req_id: int) -> Any:
        """Non-blocking collect: the extracted :class:`KVHandoff`, or None
        if the engine has not processed the order yet. Raises RuntimeError
        if extraction failed (slot no longer held — e.g. engine drained)."""
        with self._lock:
            if req_id not in self._handoffs:
                return None
            out = self._handoffs.pop(req_id)
        if out is None:
            raise RuntimeError(
                f"handoff extraction failed for request {req_id}: slot no "
                "longer held"
            )
        return out

    def wait_handoff(self, req_id: int, timeout: float = 30.0) -> Any:
        """Block until the engine extracts the payload ordered by
        :meth:`request_handoff`."""
        deadline = time.time() + timeout
        with self._done:
            while req_id not in self._handoffs:
                if self.last_error is not None:
                    raise RuntimeError(
                        f"serving loop failed: {self.last_error}")
                remaining = deadline - time.time()
                if remaining <= 0:
                    raise TimeoutError(
                        f"handoff {req_id} not extracted in {timeout}s")
                self._done.wait(remaining)
            out = self._handoffs.pop(req_id)
        if out is None:
            raise RuntimeError(
                f"handoff extraction failed for request {req_id}: slot no "
                "longer held"
            )
        return out

    # -- fleet prefix plane surface (see tpu_engine/prefix_plane.py) ---------

    def export_prefix(self, prefix: list[int]) -> Optional[Any]:
        """Ship a resident prefix-cache entry as a :class:`KVHandoff` wire
        payload (the host tier's transport). The payload covers the WHOLE
        prefix (``length == len(prefix)``, ``emitted == []``) — it is a
        cache entry, not a decodable request, and ``submit_prefilled``
        correctly rejects it; rehydrate with :meth:`install_prefix`. An
        int8 pool ships codes + scales byte-for-byte; a fp pool ships the
        wire fp dtype (the host tier quantizes on store). Returns None
        when the prefix is not resident. Engine-thread only, like every
        other prefix-cache touch."""
        from tpu_engine import disagg  # local: disagg imports this module

        if self._prefix_cache is None:
            return None
        key = tuple(int(t) for t in prefix)
        entry = self._prefix_cache._entries.get(key)
        if entry is None:
            return None
        # An entry is a one-row pool whose resident lanes are its length.
        return disagg.extract_slot_kv(entry, 0, int(entry.length), cfg=self.cfg,
                                      prompt=list(key), emitted=[])

    def install_prefix(self, prefix: list[int], handoff: Any) -> bool:
        """Rehydrate a host-tier payload into this replica's prefix cache
        so the NEXT prompt sharing ``prefix`` prefills only its tail. The
        payload's resident K/V must cover the prefix (``handoff.length >=
        len(prefix)`` with matching history tokens); all four wire×pool
        dtype conversions ride :func:`tpu_engine.disagg.handoff_to_cache`.
        Returns False when this engine has no prefix cache or the entry
        exceeds its budget. Engine-thread only."""
        from tpu_engine import disagg  # local: disagg imports this module

        if self._prefix_cache is None:
            return False
        key = tuple(int(t) for t in prefix)
        if not key:
            raise ValueError("empty prefix")
        self._check_handoff_geometry(handoff)
        history = list(handoff.prompt) + list(handoff.emitted)
        if handoff.length < len(key) or \
                [int(t) for t in history[: len(key)]] != list(key):
            raise ValueError(
                "handoff does not cover the prefix: resident K/V is "
                f"{handoff.length} tokens of a different history"
            )
        if not self._prefix_cache.wants(key):
            # Already resident (success) or over budget (refusal).
            return key in self._prefix_cache._entries
        c1 = disagg.handoff_to_cache(
            handoff, dtype=self._compute_dtype, kv_quant=self.kv_quant,
            chunk=self.prefill_chunk, max_lanes=self._cache.n_lanes,
        )
        # handoff_to_cache leaves ``pos`` at -1 (the slot insert ignores
        # it); a prefix entry is pasted into fresh ingestion caches, so
        # give it the lane == position form _slice_prefix stores.
        c1 = dataclasses.replace(
            c1, pos=jnp.arange(c1.max_len, dtype=jnp.int32),
            length=jnp.asarray(len(key), jnp.int32),
        )
        self._prefix_cache.insert(key, self._on_mesh(c1))
        return key in self._prefix_cache._entries

    def _result_locked(self, req: Request) -> dict[str, Any]:
        out = {
            "id": req.id, "status": req.status, "tokens": list(req.tokens),
            "prompt_len": len(req.prompt),
        }
        if req.first_token_at is not None:
            out["ttft_ms"] = round(
                (req.first_token_at - req.submitted_at) * 1e3, 2
            )
            # Absolute stamp too: fleet-level TTFT measures from FLEET
            # submission (queue + route + prefill), not engine admission.
            out["first_token_at"] = req.first_token_at
        for stamp in ("submitted_at", "admitted_at", "prefill_started_at",
                      "finished_at"):
            at = getattr(req, stamp)
            if at is not None:
                out[stamp] = at
        if req.error:
            out["error"] = req.error
        return out

    def result(self, req_id: int) -> dict[str, Any]:
        with self._lock:
            req = self._requests.get(req_id)
            if req is None:
                raise KeyError(req_id)
            return self._result_locked(req)

    def wait_tokens(self, req_id: int, have: int = 0,
                    timeout: float = 30.0) -> dict[str, Any]:
        """Block until the request holds MORE than ``have`` tokens or is
        terminal, then return its result snapshot (same shape as
        :meth:`result`). A timeout returns the current snapshot instead of
        raising — callers loop, emitting whatever arrived (this is the
        primitive under the HTTP token-streaming endpoint; heartbeats come
        from the timeout path)."""
        deadline = time.time() + timeout
        with self._done:
            while True:
                req = self._requests.get(req_id)
                if req is None:
                    raise KeyError(req_id)
                if len(req.tokens) > have or req.status in ("done", "failed"):
                    return self._result_locked(req)
                remaining = deadline - time.time()
                if remaining <= 0:
                    return self._result_locked(req)
                self._done.wait(remaining)

    def wait(self, req_id: int, timeout: float = 60.0) -> dict[str, Any]:
        deadline = time.time() + timeout
        with self._done:
            while True:
                req = self._requests.get(req_id)
                if req is None:
                    raise KeyError(req_id)
                if req.status in ("done", "failed"):
                    return self._result_locked(req)
                remaining = deadline - time.time()
                if remaining <= 0:
                    raise TimeoutError(f"request {req_id} not done in {timeout}s")
                self._done.wait(remaining)

    def stats(self) -> dict[str, Any]:
        with self._lock:
            now = time.time()
            while self._recent and now - self._recent[0][0] > self._stats_window_s:
                self._recent.popleft()
            recent_tokens = sum(n for _, n in self._recent)
            window = min(max(now - self._started, 1e-9), self._stats_window_s)
            active = sum(1 for s in self._slots if s is not None)
            dt = max(now - self._started, 1e-9)
            out = {
                "slots": self.max_slots,
                "active_slots": active,
                "prefilling": len(self._prefilling),
                "queued": len(self._queue),
                "requests_total": len(self._requests),
                "tokens_generated": self._tokens_out,
                "tokens_per_sec_recent": round(recent_tokens / window, 2),
                "tokens_per_sec_lifetime": round(self._tokens_out / dt, 2),
                "chunk_steps": self.chunk_steps,
                "sharded": self.mesh is not None,
                "speculative": self._draft_params is not None,
                "kv_quant": self.kv_quant,
                # Disaggregated-serving surface: held = finished prefills
                # pinning K/V for extraction; queued_handoffs = wire
                # payloads awaiting a decode slot (the fleet router counts
                # both against this engine's free capacity).
                "held_slots": len(self._held),
                "queued_handoffs": len(self._prefilled_queue),
                "handoffs_out": self.handoffs_out,
                "handoffs_in": self.handoffs_in,
                # Monotonic; computed/emitted is the decode layer's
                # attempted-to-useful ratio: a dispatch computes chunk_steps
                # tokens for every active slot, and what overshoots a
                # finished request (or a rejected speculative proposal) is
                # thrown away.
                "decode_tokens_computed_total": self._decode_tokens_computed,
                "decode_tokens_emitted_total": self._decode_tokens_emitted,
                "decode_tokens_sparse_total": self._decode_tokens_sparse,
                "prefill_tokens_computed_total": self._prefill_tokens_computed,
                # The rows (positions) the prefill programs ran the output
                # head for: one a chunk that forms logits, of its
                # ``prefill_chunk`` positions.
                "prefill_head_rows_total": self._prefill_head_rows,
                "prefill_tokens_sparse_total": self._prefill_tokens_sparse,
                # Of ``prefill_tokens_computed_total``, the positions that ran
                # a cross-decoder (a decoder-hybrid-decoder stack needs it at
                # a prompt's last position only).
                "prefill_positions_cross_decoder_total":
                    self._prefill_head_rows if self._ingest_fn is not None else 0,
                # Monotonic: waits the driving loop took between two steps
                # (phase ``idle``), and those entered with a prompt still
                # prefilling, queued or awaiting a handoff.
                "idle_waits_total": self._idle_waits,
                "idle_waits_with_work_total": self._idle_waits_with_work,
                # The pool's whole kinds of state (Mamba-2, Mamba-1, lightning,
                # power retention; 0 for attention-only stacks): their bytes,
                # every slot's whether in use or not, and how often a slot's
                # was written whole or zeroed; and the decode layer-steps that
                # updated it in place, in one pass (the kernels
                # ``ops.ssd_update`` and ``ops.power_update``; 0 where the walk
                # keeps the XLA step).
                "recurrent_state_bytes": self._recurrent_state_bytes,
                # What the latent-attention (MLA) layers cache, every slot's
                # every lane (0 for a stack that has none).
                "latent_cache_bytes": self._latent_cache_bytes,
                # The ONE full-attention cache cross-attention layers share,
                # and the window layers' rings, every slot's.
                "shared_kv_bytes": self._shared_kv_bytes,
                "window_kv_bytes": self._window_kv_bytes,
                "recurrent_updates_in_place_total": self._recurrent_updates_in_place,
                # Decode layer-steps of power-retention layers run, by kernel
                # or not (0 for a stack that has none).
                "power_layer_steps_total": self._power_layer_steps,
                # Monotonic, per decode step and ``attn`` layer that reads the
                # pool through the lane-walking kernel (``ops.lane_decode``):
                # the lanes of keys it fetched (whole blocks of 512 up to each
                # active slot's length; as many of values) and the lanes the
                # pool holds (slots x lanes, what XLA's contractions read).
                # Their ratio is the pool's live share as the kernel sees it;
                # both stay 0 where the kernel does not engage.
                "decode_attn_lanes_read_total": self._decode_attn_lanes_read,
                "decode_attn_lanes_pool_total": self._decode_attn_lanes_pool,
                # Monotonic, per call of that kernel (an ``attn`` layer's, and
                # a differential stack's reads of its shared cache and its
                # rings): the blocks of 512 lanes its one walk over all slots
                # carried, and the grid steps the call took (its grid is
                # bounded by the blocks in use: a step a block, and one for a
                # call that has none). Their ratio is the share of grid steps
                # that carry a block: 1.0 but for empty calls.
                "decode_attn_blocks_walked_total": self._decode_attn_blocks_walked,
                "decode_attn_grid_steps_total": self._decode_attn_grid_steps,
                "state_inserts_total": self._state_inserts,
                "state_resets_total": self._state_resets,
                # The weights the engine holds, by dtype, counted once at
                # build (the target's; a speculative engine's draft beside
                # it): what ``estimate_serving_hbm`` prices as ``params_gib``.
                "weight_bytes": dict(self._weight_bytes),
            }
            if self.cfg.is_moe:
                # Monotonic, by program: mixture layer-steps run, token-expert
                # assignments made at real positions, those on experts this
                # replica holds, and held experts some token chose (distinct
                # per layer-step, summed).
                out["held_experts"] = self.cfg.n_experts_held
                out["shared_expert_bytes"] = self._shared_expert_bytes
                for program, counts in self._moe_counts.items():
                    for name, n in counts.items():
                        out[f"moe_{program}_{name}_total"] = n
                out["moe_prefill_grouped_chunks_total"] = self._moe_grouped_chunks
            if self._prefix_cache is not None:
                out["prefix_cache"] = self._prefix_cache.stats()
            if self._draft_params is not None:
                # Fleet-wide speculative telemetry (backend/routers/
                # metrics.py renders these as tpu_engine_serving_spec_*).
                out["draft_weight_bytes"] = dict(self._draft_weight_bytes)
                out["spec_rounds"] = self._spec_rounds
                out["spec_tokens_accepted"] = self._spec_accepted
                out["spec_tokens_proposed"] = (
                    self._spec_rounds * (self.spec_gamma + 1)
                )
            if self._spec_rounds:
                # Mean accepted tokens per draft round, of gamma+1 possible.
                out["spec_accept_rate"] = round(
                    self._spec_accepted / (self._spec_rounds *
                                           (self.spec_gamma + 1)), 3
                )
        return out

    def profile(self) -> dict[str, Any]:
        """The engine loop's phase clock, summarised (``StepProfiler.
        summary``): the operator's view beside :meth:`stats`, which the
        fleet's router reads per request and which therefore stays counters."""
        return self._profiler.summary()

    # -- engine side ---------------------------------------------------------

    def _on_mesh(self, c1: KVCache) -> KVCache:
        """A one-row cache placed as the pool shards (mesh-sharded serving)."""
        if self.mesh is None:
            return c1
        c1 = dataclasses.replace(c1, sharded=True)  # its walks hand no kernel a sharded stack
        return jax.device_put(
            c1, layer_state.cache_shardings(self.mesh, self.cfg, c1))

    def _begin_prefill(self, req: Request, slot: int) -> _PrefillState:
        """Pad the prompt up to a ``prefill_pad_to`` multiple (bounded
        compiled final-chunk shapes); padded positions are never exposed
        (mask is per-row length) and decode overwrites the first pad lane
        before it can be seen."""
        P_len = len(req.prompt)
        pad = min(-(-P_len // self.prefill_pad_to) * self.prefill_pad_to,
                  self.max_len)
        toks = np.zeros((1, pad), np.int32)
        toks[0, :P_len] = req.prompt
        return _PrefillState(req=req, slot=slot, toks=toks)

    def _stage(self, st: _PrefillState) -> None:
        """Allocate ``st``'s single-row ingestion cache(s), when its first
        chunk is about to run: prompts are ingested one at a time, so one
        staging cache is live however many requests an admission pass took
        (a full pool's worth at once held gigabytes of them, all zeros)."""
        pad = st.padded
        if self._cache.ring:
            # Ring pools need lane-aligned ingestion: the c1 ring must have
            # exactly the pool's lane count so positions map to the same
            # lanes (both write at position % S).
            c1 = init_cache(self.cfg, 1, self.max_len, dtype=self._compute_dtype,
                            max_chunk=self.prefill_chunk,
                            kv_quant=self.kv_quant)
        else:
            # Bucket the cache size to prefill_chunk multiples so compiled
            # (chunk_shape, cache_shape) pairs stay few.
            M = min(-(-pad // self.prefill_chunk) * self.prefill_chunk,
                    self.max_len)
            M = max(M, pad)
            c1 = init_cache(self.cfg, 1, M, dtype=self._compute_dtype,
                            kv_quant=self.kv_quant)
        st.c1 = self._on_mesh(c1)
        if self._draft_params is not None:
            st.dc1 = init_cache(self._draft_cfg, 1, c1.max_len,
                                dtype=self._compute_dtype)

    def _advance_prefill(self, st: _PrefillState) -> bool:
        """Ingest ONE bounded chunk; True when the prompt is fully in and
        its K/V rows have been copied into the slot."""
        if st.c1 is None:
            self._stage(st)
        if self._prefix_cache is not None and not st.prefix_checked:
            # Lookup at FIRST advance, not at admission: prefills drain
            # one chunk per engine step in admission order, so a burst of
            # same-prefix admissions still hits entries the first prompt
            # creates (admission-time lookup would see an empty cache).
            st.prefix_checked = True
            hit_len, entry = self._prefix_cache.lookup(st.req.prompt)
            if entry is not None and hit_len > 0:
                # Paste the cached lanes; ingestion resumes at the hit
                # frontier (a grain multiple, possibly mid-chunk) — the
                # shared tokens' forward never reruns. Lanes the entry
                # holds beyond hit_len stay masked until overwritten.
                lanes = min(entry.max_len, st.c1.max_len)
                st.c1 = self._paste_prefix(
                    st.c1, entry, jnp.asarray(hit_len, jnp.int32), lanes
                )
                st.consumed = hit_len
        t0 = st.consumed
        t1 = min(t0 + self.prefill_chunk, st.padded)
        chunk = jnp.asarray(st.toks[:, t0:t1])
        P_len = len(st.req.prompt)
        # Logits row of the last REAL prompt token (it seeds the first
        # sampled/greedy token) — only meaningful in its chunk.
        row = min(max(P_len - 1 - t0, 0), t1 - t0 - 1)
        # A hybrid stack's recurrent layers stop at the chunk's last REAL
        # token (the bucket's padding would otherwise enter the state).
        n_valid = jnp.asarray(min(max(P_len - t0, 0), t1 - t0), jnp.int32) \
            if self._cache.recurrent else None
        is_last = t0 <= P_len - 1 < t1
        if self._ingest_fn is not None and not is_last:
            # A stack with a cross-decoder: only a prompt's last position needs
            # it, so every other chunk stops at the cache it reads.
            last_row, st.c1 = None, self._ingest_fn(self.params, chunk, st.c1, n_valid)
        else:
            last_row, st.c1 = self._prefill_fn(
                self.params, chunk, st.c1, jnp.asarray(row, jnp.int32), n_valid
            )
            self._prefill_head_rows += 1  # the head (and a cross-decoder) runs at ``row`` alone
        if st.dc1 is not None:  # speculative: the draft ingests the prompt too
            st.dc1 = self._draft_prefill_fn(self._draft_params, chunk, st.dc1)
        st.consumed = t1
        st.chunks += 1
        self._prefill_tokens_computed += t1 - t0
        self._moe_grouped_chunks += self._experts_grouped(t1 - t0)
        if self._sparse_from is not None:
            self._prefill_tokens_sparse += max(t1 - max(t0, self._sparse_from), 0)
        if self._prefix_cache is not None:
            # Insert ONLY at the walk's last cacheable boundary (largest
            # full chunk of REAL tokens within the budget): intermediate
            # boundaries would be chain-dropped by the very next insert
            # anyway (lookups happen at first advance and prefills drain
            # head-of-line, so no hit can land mid-walk) — slicing them
            # would add O(N²/chunk) discarded HBM copies to this
            # request's own TTFT. Cross-walk behavior is unchanged: a
            # later request sharing a SHORTER prefix re-creates that
            # boundary on its own walk. The walk COVERS the boundary
            # (t0 < last <= t1) rather than landing exactly on it: a
            # token-granular hit starts the walk at a grain (not chunk)
            # multiple, so chunk steps never equal `last` again — the
            # slice below still works because lane == position.
            c = self.prefill_chunk
            last = min((P_len // c) * c,
                       (self._prefix_cache.budget // c) * c)
            if t0 < last <= t1 and self._prefix_cache.wants(
                tuple(st.req.prompt[:last])
            ):
                self._prefix_cache.insert(
                    tuple(st.req.prompt[:last]),
                    self._slice_prefix(st.c1, last),
                )
        if is_last:
            # The prompt's last chunk: with its logits row come the mixture's
            # counts of all the request's chunks (``c1`` has summed them).
            last_row, counts = jax.device_get((last_row, st.c1.moe_counts))
            self._pending_first_logits[st.slot] = last_row
            self._note_moe("prefill", counts, st.chunks)
        if st.consumed < st.padded:
            return False
        self._cache = self._insert(self._cache, st.c1, jnp.asarray(st.slot),
                                   jnp.asarray(P_len, jnp.int32),
                                   self._cache.ring)
        self._state_inserts += self._cache.recurrent
        if st.dc1 is not None:
            self._draft_cache = self._insert(
                self._draft_cache, st.dc1, jnp.asarray(st.slot),
                jnp.asarray(P_len, jnp.int32), False,
            )
        self._last_tokens[st.slot] = st.req.prompt[-1]
        return True

    def step(self) -> int:
        """Admit queued requests (one prefill chunk per call), advance
        active slots ``chunk_steps`` tokens. Returns tokens produced.

        Locking: the lock guards only host bookkeeping (admission decisions
        and result emission). Prefill, the jitted decode dispatch, and the
        token device→host sync — the long operations — run WITHOUT it, so
        ``submit``/``result``/``stats`` from serving threads never wait on
        device work. The engine thread is the sole mutator of the KV pool
        and slot arrays, so they need no lock at all."""
        prof = self._profiler
        prof.begin_step()  # closes the previous iteration, idle sleep included
        # ---- handoff orders first: extraction frees held slots, so the
        # admission pass below can reuse them in the SAME step ----
        with prof.phase("handoff"):
            with self._lock:
                orders, self._handoff_requests = self._handoff_requests, []
            for rid, quantize in orders:
                self._service_handoff(rid, quantize)

        # ---- admission (bookkeeping under the lock): wire-prefilled
        # requests win free slots (their prompt K/V is already paid for —
        # they only need a lane to decode in), then queued prompts ----
        with prof.phase("admit"):
            admitted_handoffs: list[tuple[int, Request, Any]] = []
            admitted: list[tuple[int, Request]] = []
            with self._lock:
                now = time.time()
                for slot in range(self.max_slots):
                    if self._slots[slot] is not None:
                        continue
                    if self._prefilled_queue:
                        req, handoff = self._prefilled_queue.pop(0)
                        req.status, req.slot = "running", slot
                        req.admitted_at = now
                        self._slots[slot] = req
                        admitted_handoffs.append((slot, req, handoff))
                    elif self._queue:
                        req = self._queue.pop(0)
                        req.status, req.slot = "running", slot
                        req.admitted_at = now
                        self._slots[slot] = req
                        admitted.append((slot, req))
            for slot, req, handoff in admitted_handoffs:  # device insert, no lock
                self._insert_handoff(handoff, slot)
            for slot, req in admitted:  # host-side alloc only — cheap
                self._prefilling[slot] = self._begin_prefill(req, slot)

        # ---- ONE prefill chunk per step (bounded decode stall) ----
        with_prefill = 0  # a chunk rode in this iteration
        if self._prefilling:
            slot, st = next(iter(self._prefilling.items()))
            if st.req.status != "running":
                self._prefilling.pop(slot)  # cancelled/failed meanwhile
            else:
                # ``expert_rows``: a mixture's ``moe_prefill_rows_computed_total``
                # as the phase opens (a prompt's counts come with its last chunk).
                moe = {"expert_rows": self._moe_counts["prefill"]["rows_computed"]} \
                    if self.cfg.is_moe else {}
                with prof.phase("prefill", rid=st.req.id, slot=slot,
                                chunk=st.consumed // self.prefill_chunk,
                                tokens=min(self.prefill_chunk,
                                           st.padded - st.consumed), **moe):
                    with_prefill = 1
                    if st.req.prefill_started_at is None:
                        st.req.prefill_started_at = time.time()
                    ingested = self._advance_prefill(st)
                if ingested:
                    self._prefilling.pop(slot)

        # ---- first token for freshly-prefilled slots comes from the
        # prefill logits; everyone else decodes a chunk. (A slot with
        # pending first logits is never still prefilling: the logits row
        # is captured in the final chunk, which also completes the
        # ingestion in the same _advance_prefill call.) ----
        produced = 0
        with prof.phase("first_token"):
            fresh = self._pending_first_logits
            self._pending_first_logits = {}
            # Sampling a first token can dispatch to the device (categorical
            # draw) — do it OUTSIDE the lock, like every other long operation;
            # only this engine thread mutates _slots, so the reads are safe.
            first_toks = {
                slot: self._first_token(logits, self._slots[slot])
                for slot, logits in fresh.items()
                if self._slots[slot] is not None
            }
            with self._lock:
                for slot, tok in first_toks.items():
                    req = self._slots[slot]
                    if req is None:
                        continue
                    self._emit(req, slot, tok)
                    produced += 1
                self._note_tokens(produced)
                # Status filter matters for held slots: a finished hold_kv
                # request still occupies its slot (pinning the K/V for the
                # handoff plane) but must NOT keep decoding — advancing its
                # length would scribble garbage past the extraction frontier.
                active_reqs = [
                    (i, r) for i, r in enumerate(self._slots)
                    if r is not None and r.status == "running"
                    and i not in self._prefilling
                ]
        if not active_reqs:
            return produced

        # Speculative path: draft proposes gamma tokens per slot, target
        # verifies every slot's chain in one T=gamma+1 forward; each round
        # emits 1..gamma+1 tokens per slot for two model dispatches.
        # (Greedy-only by the submit guard — no sampling state needed.)
        speculative = self._draft_params is not None
        with prof.phase("stage", with_prefill=with_prefill):
            active = np.zeros((self.max_slots,), bool)
            for i, _ in active_reqs:
                active[i] = True
            if speculative:
                tgt, n_acc, self._cache, self._draft_cache = self._spec(
                    self.params, self._draft_params,
                    jnp.asarray(self._last_tokens), self._cache,
                    self._draft_cache, jnp.asarray(active),
                )
            else:
                temps = np.zeros((self.max_slots,), np.float32)
                req_ids = np.zeros((self.max_slots,), np.int32)
                counts = np.zeros((self.max_slots,), np.int32)
                for i, r in active_reqs:
                    temps[i] = r.temperature
                    req_ids[i] = r.id
                    counts[i] = len(r.tokens)
                toks_bn, self._cache = self._decode(
                    self.params, jnp.asarray(self._last_tokens), self._cache,
                    jnp.asarray(active), jnp.asarray(temps), jnp.asarray(req_ids),
                    jnp.asarray(counts), self._base_key,
                )
        # What this dispatch decodes rides on the annotation: a trace that ends
        # before the slots are full can still hold each run of the decode
        # program against the rows it computed for and the lanes they held.
        contexts = [len(r.prompt) + len(r.tokens) for _, r in active_reqs]
        lanes_read = self._attn_lanes_read(contexts)
        with prof.phase("device", rows=len(active_reqs), context=sum(contexts),
                        attn_lanes_read=lanes_read):
            if speculative:
                toks_host = np.asarray(tgt)         # [B, gamma+1]
                n_take = np.asarray(n_acc)          # [B] accepted per slot
            else:
                # [B, n], and a mixture's counts of this dispatch: one fetch
                toks_host, counts = jax.device_get((toks_bn, self._cache.moe_counts))
                self._note_moe("decode", counts, toks_host.shape[1])
                n_take = None
        n_steps = toks_host.shape[1]
        self._decode_tokens_computed += len(active_reqs) * n_steps
        self._recurrent_updates_in_place += n_steps * self._in_place_layers
        self._power_layer_steps += n_steps * self._power_layers
        self._decode_attn_lanes_read += lanes_read
        self._decode_attn_lanes_pool += (n_steps * self._lane_walk_layers
                                         * self.max_slots * self._pool_lanes)
        blocks, grid_steps = self._attn_blocks_walked(contexts)
        self._decode_attn_blocks_walked += blocks
        self._decode_attn_grid_steps += grid_steps
        if self._sparse_from is not None:
            # A row's steps run at positions context - 1 .. context + n - 2.
            self._decode_tokens_sparse += sum(
                min(max(len(r.prompt) + len(r.tokens) - 1 + n_steps - self._sparse_from, 0), n_steps)
                for _, r in active_reqs)
        with prof.phase("emit"), self._lock:
            emitted = 0
            for slot, req in active_reqs:
                if self._slots[slot] is not req:
                    continue  # request state changed while we computed
                row = toks_host[slot]
                if speculative:
                    self._spec_rounds += 1
                    self._spec_accepted += int(n_take[slot])
                    row = row[: n_take[slot]]
                for t in row:
                    self._emit(req, slot, int(t))
                    emitted += 1
                    if req.status != "running":
                        break  # overshoot discarded; slot already reset
            self._note_tokens(emitted)
        self._decode_tokens_emitted += emitted
        return produced + emitted

    def _service_handoff(self, rid: int, quantize: Optional[bool]) -> None:
        """ENGINE thread: extract a held slot's K/V into a wire payload
        (``quantize`` True/False) or discard it (``quantize`` None), then
        free the slot. The engine thread is the pool's sole mutator, so the
        device slice here can never race a donated dispatch."""
        from tpu_engine import disagg  # local: disagg imports this module

        with self._lock:
            slot = self._held.get(rid)
            req = self._requests.get(rid)
        if slot is None or req is None or self._slots[slot] is not req:
            if quantize is not None:
                with self._lock:
                    self._handoffs[rid] = None  # extraction failed marker
                    self._done.notify_all()
            return
        payload = None
        if quantize is not None:
            # Resident K/V = full history minus the last emitted token
            # (decode writes its INPUT token — steady-state invariant).
            length = len(req.prompt) + len(req.tokens) - 1
            payload = disagg.extract_slot_kv(
                self._cache, slot, length, cfg=self.cfg,
                prompt=req.prompt, emitted=req.tokens, quantize=quantize,
            )
        self._cache = self._reset(self._cache, slot)
        with self._lock:
            self._held.pop(rid, None)
            if self._slots[slot] is req:
                self._slots[slot] = None
            if quantize is not None:
                self._handoffs[rid] = payload
                self.handoffs_out += 1
            self._done.notify_all()

    def _insert_handoff(self, handoff: Any, slot: int) -> None:
        """ENGINE thread: materialise a wire payload as a single-row
        ingestion cache (converted to this pool's dtype/quant mode) and
        copy it into ``slot`` via the ordinary ``_insert_prefill`` path."""
        from tpu_engine import disagg  # local: disagg imports this module

        c1 = disagg.handoff_to_cache(
            handoff, dtype=self._compute_dtype, kv_quant=self.kv_quant,
            chunk=self.prefill_chunk, max_lanes=self._cache.n_lanes,
        )
        self._cache = self._insert(
            self._cache, self._on_mesh(c1), jnp.asarray(slot),
            jnp.asarray(handoff.length, jnp.int32), self._cache.ring,
        )
        self._last_tokens[slot] = handoff.last_token
        self.handoffs_in += 1

    def _attn_lanes_read(self, contexts: list[int]) -> int:
        """Lanes of keys a dispatch's ``attn`` layers fetch through the
        lane-walking kernel for rows at ``contexts`` (prompt + generated: what a
        row sees at the dispatch's first step, one more each step, in whole
        blocks, never past the pool's lanes); 0 where it does not engage."""
        if not self._lane_walk_layers:
            return 0
        seen = np.minimum(np.asarray(contexts)[:, None] + np.arange(self.chunk_steps), self._pool_lanes)
        return self._lane_walk_layers * int((-(-seen // lane_decode.LANES) * lane_decode.LANES).sum())

    def _attn_blocks_walked(self, contexts: list[int]) -> tuple[int, int]:
        """(blocks, grid steps) of a dispatch's calls of the lane-walking
        kernel (every entry of ``lane_walks``) for rows at ``contexts``, the
        other slots showing each call what a slot that does not decode shows
        it. A call's grid is bounded by its blocks, so it takes a step a block,
        and one where it has none."""
        idle = self.max_slots - len(contexts)
        blocks = grid_steps = 0
        for calls, lanes, idle_lanes in self._lane_walks:
            seen = np.minimum(np.asarray(contexts, np.int64).reshape(-1, 1) + np.arange(self.chunk_steps), lanes)
            a_call = (-(-seen // lane_decode.LANES)).sum(axis=0) + idle * -(-idle_lanes // lane_decode.LANES)
            blocks += calls * int(a_call.sum())
            grid_steps += calls * int(np.maximum(a_call, 1).sum())
        return blocks, grid_steps

    def _experts_grouped(self, rows: int) -> bool:
        """Whether a walk of ``rows`` positions runs this engine's held experts
        grouped (False without experts)."""
        return self._expert_gate is not None and experts_grouped_engages(
            rows, self.cfg, self._expert_gate, sharded=self.mesh is not None)

    def _note_moe(self, program: str, counts, steps: int) -> None:
        """Add a dispatch's (decode) or a prompt's (prefill) mixture counts:
        ``steps`` walks of the stack and the device's ``MOE_COUNTS``."""
        if counts is None:
            return
        mine = self._moe_counts[program]
        mine["layer_steps"] += steps * self.cfg.n_mixture_layers
        for name, n in zip(MOE_COUNTS, counts.tolist()):
            mine[name] += n

    def _note_tokens(self, n: int) -> None:
        """Caller holds the lock."""
        if n:
            self._tokens_out += n
            now = time.time()
            self._recent.append((now, n))
            while self._recent and now - self._recent[0][0] > self._stats_window_s:
                self._recent.popleft()
            # Wake streamers (wait_tokens) as well as completion waiters —
            # one condition serves both, notified once per emission batch.
            self._done.notify_all()

    def _first_token(self, logits: np.ndarray, req: Request) -> int:
        """First token from the prefill logits — SAME key contract as the
        in-dispatch draws (fold(fold(seed, id), 0)), so a request's stream
        is one deterministic sequence regardless of where draws happen."""
        if req.temperature <= 0.0:
            return int(np.argmax(logits))
        key = jax.random.fold_in(
            jax.random.fold_in(self._base_key, req.id), 0
        )
        return int(jax.random.categorical(
            key, jnp.asarray(logits) / req.temperature
        ))

    def _emit(self, req: Request, slot: int, tok: int) -> None:
        if req.first_token_at is None:
            req.first_token_at = time.time()
        req.tokens.append(tok)
        self._last_tokens[slot] = tok
        finished = (
            len(req.tokens) >= req.max_new_tokens
            or (self.eos_id is not None and tok == self.eos_id)
            or len(req.prompt) + len(req.tokens) >= self.max_len
        )
        if finished:
            req.status = "done"
            req.finished_at = time.time()
            if req.hold_kv:
                # Disaggregated prefill: keep the slot (and the K/V in it)
                # pinned for the handoff plane — _slots[slot] stays set so
                # admission skips it, and step()'s status filter keeps it
                # out of decode. request_handoff/release_held free it.
                self._held[req.id] = slot
                self._done.notify_all()
                return
            self._slots[slot] = None
            # Free slot: zero its length (and ring positions) so admission
            # reuses it cleanly; overshoot lanes from a mid-chunk finish
            # become invisible the same instant.
            self._cache = self._reset(self._cache, slot)
            self._state_resets += self._cache.recurrent
            if self._draft_cache is not None:
                self._draft_cache = self._reset(self._draft_cache, slot)
            self._done.notify_all()

    def idle_wait(self, stop: threading.Event, seconds: float) -> None:
        """ENGINE thread, between two ``step`` calls: wait up to ``seconds``
        on ``stop`` in phase ``idle`` (the annotation says what was pending:
        ``prefilling=``, ``queued=``), so the loop's own waits are no part of
        ``other``. Whoever drives ``step`` decides when to wait
        (:meth:`serve_forever`, ``ServingReplicaJob._run``); a wait entered
        with work pending counts in ``idle_waits_with_work_total``."""
        prefilling, queued = len(self._prefilling), len(self._queue)
        self._idle_waits += 1
        if prefilling or queued or self._prefilled_queue or self._handoff_requests:
            self._idle_waits_with_work += 1
        with self._profiler.phase("idle", prefilling=prefilling, queued=queued):
            stop.wait(seconds)

    def serve_forever(self, stop: threading.Event, idle_sleep: float = 0.01):
        """Drive ``step`` until ``stop``. A step failure (e.g. a prefill
        compile OOM) marks every in-flight and queued request ``failed``
        with the error recorded, and later ``submit`` calls are rejected —
        never a silently dead thread with requests stuck forever. A CLEAN
        stop drains the same way: in-flight requests become terminal
        (``failed``, "server stopped"), so a blocked ``wait``/
        ``wait_tokens`` (e.g. an open SSE stream) terminates instead of
        heartbeating forever against a request no thread will ever
        advance."""
        try:
            while not stop.is_set():
                try:
                    produced = self.step()
                except Exception as e:  # noqa: BLE001 — serving boundary
                    self._drain(f"{type(e).__name__}: {e}")
                    return
                # Sleep only when truly idle: a step that produced no token
                # but advanced a prefill chunk (or left admissions waiting)
                # must loop immediately — sleeping between every chunk of a
                # long prompt would add ~idle_sleep × n_chunks to its TTFT.
                if produced == 0 and not self._prefilling and not self._queue \
                        and not self._handoff_requests \
                        and not self._prefilled_queue:
                    self.idle_wait(stop, idle_sleep)
        finally:
            if self.last_error is None:
                self._drain("server stopped")

    def _drain(self, msg: str) -> None:
        """Fail every queued/running request with ``msg``, reject any later
        ``submit`` (nothing will ever serve it — a post-stop submit would
        sit 'queued' forever), and wake every waiter."""
        self.last_error = msg  # reject new submits first
        with self._lock:
            pending_prefilled = [req for req, _ in self._prefilled_queue]
            for req in list(self._slots) + list(self._queue) + pending_prefilled:
                if req is not None and req.status in ("queued", "running"):
                    req.status, req.error = "failed", msg
                    req.finished_at = time.time()
            self._slots = [None] * self.max_slots
            self._queue.clear()
            self._prefilling.clear()
            self._held.clear()
            self._handoff_requests.clear()
            self._prefilled_queue.clear()
            self._done.notify_all()


def _prefill_forward(params, toks, cache, row_idx, n_valid=None, *, cfg,
                     compute_dtype):
    """One prefill chunk through the stock cached forward; returns only the
    requested logits row (the [V] vector that seeds the first token). The
    final norm and the head run at that row alone (``logits_row``): no
    [T, V] logits are formed, nor all-gathered on a mesh. A stack with a
    cross-decoder runs that too at the one position.
    ``n_valid``: the chunk's real tokens (the rest pads the prompt to its
    bucket), which only a hybrid stack's recurrent layers need."""
    logits, cache = forward_with_cache(params, toks, cache, cfg, compute_dtype=compute_dtype,
                                       n_valid=n_valid, logits_row=row_idx)
    return logits[0, 0], cache


def _prefill_ingest(params, toks, cache, n_valid=None, *, cfg, compute_dtype):
    """A prefill chunk that is not its prompt's last, of a stack with a
    cross-decoder: the self-decoder and the keys and values the cross-decoder
    will read, nothing after them and no logits."""
    _, cache = forward_with_cache(params, toks, cache, cfg, compute_dtype=compute_dtype,
                                  n_valid=n_valid, ingest_only=True)
    return cache


def _draft_prefill_ingest(params, toks, cache, *, cfg, compute_dtype):
    """Cache-only prompt ingestion for the speculative draft: no unembed,
    no logits (the draft's first proposal re-derives from the last token)."""
    _, cache = forward_with_cache(params, toks, cache, cfg,
                                  compute_dtype=compute_dtype,
                                  want_logits=False)
    return cache


def _insert_prefill(cache: SlotCache, c1: KVCache, slot, true_len, ring: bool):
    """Copy a single-row prefill cache into ``slot`` and set its length to
    the TRUE prompt length (padding lanes stay masked — causality for ring
    pools, length for flat pools — and are overwritten as decoding
    proceeds; a state with no lanes is the prefill's at that length). A
    quantized pool requires a quantized ingestion cache (the batcher
    allocates both from one flag)."""
    pos = cache.pos
    if ring:
        # Lane-aligned by construction (c1 ring size == pool lane count).
        pos = lax.dynamic_update_slice(pos, c1.pos[None, :], (slot, 0))
    return dataclasses.replace(
        cache, layers=layer_state.insert_row(cache.layers, c1.layers, slot, true_len),
        lengths=cache.lengths.at[slot].set(true_len.astype(jnp.int32)), pos=pos,
    )


def _reset_slot(cache: SlotCache, slot):
    pos = cache.pos
    if cache.ring:
        pos = pos.at[slot].set(-1)
    return dataclasses.replace(
        cache, layers=layer_state.reset_row(cache.layers, slot),
        lengths=cache.lengths.at[slot].set(0), pos=pos,
    )
