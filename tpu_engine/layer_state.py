"""The format of a slot's per-layer state, in one place.

A request keeps state per LAYER while it is served, and what it keeps depends
on the kind of layer: an attention layer keeps keys and values for every
position (a lane's row ``[KV x HD]``, the kv-heads side by side: the format of
every positional kind that keeps keys and values, so that a block of lanes is
whole contiguous rows and a decode step's kernel reads the pool where it lies;
only an int8 pool keeps ``[KV, HD]`` codes beside their scales), a Mamba-2
layer keeps one recurrent state whatever the length (as do a Mamba-1, a
lightning and a power-retention layer: the WHOLE kinds, whose decode step
rewrites all of it — Mamba-2 and lightning through ``ops.ssd_update``, power
retention through ``ops.power_update``, Mamba-1 in XLA). Both
caches (:class:`generate.KVCache`, one row in lockstep;
:class:`serving.SlotCache`, a pool of rows with their own lengths) hold that
state as ONE tree, ``{kind: {leaf: array [L_kind, rows, ...]}}``, allocated,
inserted, reset, sliced, sharded and priced by the functions below from the
one table :data:`LAYER_KINDS`. ``kind`` is the name ``ModelConfig.layer_runs()``
yields; a kind the stack does not have is absent from the tree.

A kind is **positional** (axis 2 of every leaf is a lane axis, and a row's
length or ``pos`` hides what lies beyond it: an insert copies lanes, a reset
needs nothing, a prefix can be sliced out and pasted, a verify pass rewound)
or **whole** (no lane: an insert overwrites the row's state, a reset zeroes
it, nothing can be sliced or rewound — which is all that
``transformer.refuse_recurrent`` asks). A positional leaf need not hold a
row per lane: one whose axis 2 is shorter holds a row per ``stride`` lanes
(a sparse-attention layer's compressed keys, one per 16), and whoever cuts
lanes cuts that leaf at ``lanes // stride`` (:func:`lane_stride`).

A positional kind may be a **ring**: its leaves hold fewer lanes than the
others', NOT a row per ``stride`` lanes but the newest ``R`` positions, position
``p`` in lane ``p % R`` (a windowed attention layer beside full ones: two lane
counts in one pool). Which position a lane holds follows from the row's length
alone (:func:`ring_positions`), so a ring keeps no bookkeeping of its own; a
leaf with as many lanes as the row is long is the ring that never wrapped, and
an insert lays a row's newest positions out for the pool's ring whatever lane
count staged them. A ring has no stable lanes to slice or paste.

A kind may keep NOTHING (a cross-attention layer reads another kind's leaves,
a gated memory unit an activation carried along the walk): it is in the table
with no leaves, so that every layer of a stack has its kind.

A new kind of state is one entry here and a layer function in
``generate.scan_layers``; the cache manager, the wire's two ends and the
estimator read the table.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Iterable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P


class Leaf(NamedTuple):
    """One array a kind keeps: ``shape`` follows ``[L_kind, rows]``;
    ``model_dim`` is the index (into ``shape``) of the dim that shards over
    the mesh's ``model`` axis when divisible, None = replicated;
    ``model_units`` is how many pieces that dim may be cut into (kv-heads lying
    side by side in it: a shard holds whole ones), None = its length."""

    shape: tuple
    dtype: Any
    model_dim: Optional[int] = None
    model_units: Optional[int] = None


class LayerKind(NamedTuple):
    positional: bool
    leaves: Callable[..., dict]  # (cfg, lanes, dtype, kv_quant) -> {name: Leaf}
    label: str = ""              # what an operator's note calls such a layer
    ring: bool = False           # positional, and its lanes the newest positions (``p % R``)


def _attn_leaves(cfg, lanes: int, dtype, kv_quant: bool) -> dict:
    """Keys and values per lane, the kv-heads side by side in the last dim
    (``[lanes, KV x HD]``, as every newer positional kind's): a block of lanes
    is whole contiguous rows, which is how a decode step reads them where they
    lie (``ops.lane_decode``), and a head of 64 does not leave half a tile's
    columns empty. Over a mesh's ``model`` axis the last dim splits in whole
    kv-heads. ``kv_quant``: int8 codes per lane and kv-head ``[lanes, KV, HD]``
    with one float32 absmax/127 scale per (lane, kv-head) ``[lanes, KV, 1]``,
    half the bytes of bf16 (read by XLA's dequantising contractions)."""
    if kv_quant:
        codes = Leaf((lanes, cfg.n_kv_heads, cfg.head_dim), jnp.int8, model_dim=1)
        scales = Leaf((lanes, cfg.n_kv_heads, 1), jnp.float32, model_dim=1)
        return {"k": codes, "v": codes, "k_scale": scales, "v_scale": scales}
    rows = Leaf((lanes, cfg.n_kv_heads * cfg.head_dim), dtype, model_dim=1,
                model_units=cfg.n_kv_heads)
    return {"k": rows, "v": rows}


def _ssm_leaves(cfg, lanes: int, dtype, kv_quant: bool) -> dict:
    """A Mamba-2 layer's state after the last REAL token fed — float32, it
    integrates hundreds of small updates — and the last taps-1 convolution
    inputs before it, in the compute dtype."""
    return {
        "ssm": Leaf((cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
        "conv": Leaf((cfg.ssm_conv - 1, cfg.ssm_conv_dim), dtype),
    }


def _sparse_attn_leaves(cfg, lanes: int, dtype, kv_quant: bool) -> dict:
    """Keys and values per lane, the kv-heads side by side in the last dim
    (``[lanes, KV x HD]``: with two kv-heads a ``[lanes, 2, HD]`` leaf would
    leave a tile's second-minor dimension at 2 and pad it eightfold), and the
    indexer's compressed keys ``ck``, one row per ``sparse_kernel_stride``
    lanes: row m is the mean of the ``sparse_kernel_size`` keys from lane
    ``stride x m`` on, written once the last of them is."""
    if kv_quant:
        raise NotImplementedError("sparse_attention layers keep no int8 keys and values (kv_quant)")
    rows = Leaf((lanes, cfg.n_kv_heads * cfg.head_dim), dtype)
    return {"k": rows, "v": rows,
            "ck": rows._replace(shape=(lanes // cfg.sparse_kernel_stride,) + rows.shape[1:])}


def _lightning_leaves(cfg, lanes: int, dtype, kv_quant: bool) -> dict:
    """A lightning-attention layer's state after the last REAL token fed:
    per head the decayed sum of ``v^T k`` (value x key, as a Mamba-2 state is
    ``[head_dim, state]``: both run ``generate._ssd_chunk``), float32 (every
    token adds to it)."""
    return {"state": Leaf((cfg.lightning_heads, cfg.lightning_head_dim,
                           cfg.lightning_head_dim), jnp.float32)}


# A latent row is padded with zeros to a whole number of the chip's 128-value
# tile columns.
LATENT_PAD_TO = 128


def latent_row_width(cfg) -> int:
    """Values a latent-attention layer's cache row holds: ``cfg.latent_width``
    (latent | rotated key) rounded up to :data:`LATENT_PAD_TO`."""
    return -(-cfg.latent_width // LATENT_PAD_TO) * LATENT_PAD_TO


def _latent_leaves(cfg, lanes: int, dtype, kv_quant: bool) -> dict:
    """A latent-attention (MLA) layer's one leaf: per lane the normalised
    latent (``kv_latent_dim``) and, beside it in the same row, the one rotated
    key all heads share (``qk_rope_dim``) — neither keys nor values; every
    head's are expanded from it, or never built (the absorbed decode). ONE
    leaf, not two: a decode step writes one row a layer and its scores are one
    contraction over the whole row. The row is padded with zeros to a multiple
    of 128 values (576 -> 640): the chip tiles a bf16 array (8, 128), and left
    at 576 it lays the LANES out as the minor dimension to save the padding,
    which turns every dispatch's one-row writes into a transposition of the
    whole pool there and back (two 4.6 GiB copies and as much scratch, in the
    compiled decode program of the benchmark's cell). Two leaves (512 | 64 ->
    128) would pad to the same 640."""
    if kv_quant:
        raise NotImplementedError("latent-attention (MLA) layers keep no int8 latent (kv_quant)")
    return {"latent": Leaf((lanes, latent_row_width(cfg)), dtype)}


def _mamba1_leaves(cfg, lanes: int, dtype, kv_quant: bool) -> dict:
    """A Mamba-1 layer's state after the last REAL token fed, per (state,
    channel) pair — channels MINOR, so that a tile of it is whole, where
    ``[channels, 16]`` would pad every row of 16 to a tile's 128 columns —
    float32, and the last taps-1 convolution inputs in the compute dtype."""
    return {
        "state": Leaf((cfg.mamba1_state, cfg.mamba1_inner), jnp.float32),
        "conv": Leaf((cfg.ssm_conv - 1, cfg.mamba1_inner), dtype),
    }


def _diff_attn_leaves(cfg, lanes: int, dtype, kv_quant: bool) -> dict:
    """A differential-attention layer's keys and values per lane, the kv-heads
    side by side in the last dim (``[lanes, KV x HD]``, as the sparse kind's: a
    head of 64 would leave half a tile's columns empty): a PAIR of heads is
    128 values, ``k1 | k2`` and ``v1 | v2``, which is how the layer reads them."""
    if kv_quant:
        raise NotImplementedError("differential-attention layers keep no int8 keys and values (kv_quant)")
    rows = Leaf((lanes, cfg.n_kv_heads * cfg.head_dim), dtype)
    return {"k": rows, "v": rows}


def power_tile_pairs(head_dim: int, tile: int) -> tuple:
    """The pairs of tiles ``(a, b)``, a <= b, of a head cut in tiles of
    ``tile`` values, in the order a power-retention state lays their blocks of
    ``tile^2`` products out (the one statement of that order: the expansion,
    the leaf's width and the decode kernel read it)."""
    n = head_dim // tile
    return tuple((a, b) for a in range(n) for b in range(a, n))


def _power_leaves(cfg, lanes: int, dtype, kv_quant: bool) -> dict:
    """A power-retention layer's state after the last REAL token fed, per
    kv-head (its query heads share it): ``state``, the decayed sum of ``v (x)
    phi(k) / HD`` — value x expanded key, the expansion MINOR, as a Mamba-2
    state is ``[head_dim, state]`` — and ``norm``, the same sum of ``phi(k) /
    HD`` alone (the normaliser: the state's 129th row, kept as a leaf of its
    own because a second-minor dimension of 129 would be stored as 136). Both
    float32: every token adds to them. ``phi`` is the tiled symmetric square
    (``cfg.power_state_width``: 9 216 coordinates for a 128-wide key)."""
    return {"state": Leaf((cfg.n_kv_heads, cfg.head_dim, cfg.power_state_width), jnp.float32),
            "norm": Leaf((cfg.n_kv_heads, cfg.power_state_width), jnp.float32)}


def _no_leaves(cfg, lanes: int, dtype, kv_quant: bool) -> dict:
    return {}


LAYER_KINDS: dict[str, LayerKind] = {
    "attn": LayerKind(positional=True, leaves=_attn_leaves, label="attention"),
    "ssm": LayerKind(positional=False, leaves=_ssm_leaves, label="Mamba-2"),
    "sparse_attn": LayerKind(positional=True, leaves=_sparse_attn_leaves, label="sparse-attention"),
    "lightning": LayerKind(positional=False, leaves=_lightning_leaves, label="lightning"),
    # the same leaf under two kinds: a stack's leading layers (dense block)
    # and the rest (mixture) are stacked, scanned and cached apart
    "mla": LayerKind(positional=True, leaves=_latent_leaves, label="latent-attention"),
    "mla_dense": LayerKind(positional=True, leaves=_latent_leaves, label="latent-attention"),
    # a decoder-hybrid-decoder stack: the window layers' ring beside the ONE
    # full layer's lanes, which the cross-attention layers read and do not own
    "mamba1": LayerKind(positional=False, leaves=_mamba1_leaves, label="Mamba-1"),
    "window_attn": LayerKind(positional=True, leaves=_diff_attn_leaves, label="window-attention", ring=True),
    "full_attn": LayerKind(positional=True, leaves=_diff_attn_leaves, label="attention"),
    "cross_attn": LayerKind(positional=True, leaves=_no_leaves, label="cross-attention"),
    "gmu": LayerKind(positional=True, leaves=_no_leaves, label="gated-memory"),
    # a stack of these alone keeps NO lane: ``n_lanes`` of its tree is 0, and a
    # row's length bounds positions (the rotation) and nothing else
    "power": LayerKind(positional=False, leaves=_power_leaves, label="power-retention"),
}


# Questions asked of a stack (its kinds).

def layer_counts(cfg) -> dict[str, int]:
    """Layers of each kind in ``cfg``'s stack, in order of first appearance."""
    counts: dict[str, int] = {}
    for kind, _, count in cfg.layer_runs():
        counts[kind] = counts.get(kind, 0) + count
    return counts


def keeps_whole_state(kinds: Iterable[str]) -> bool:
    """Does some kind among ``kinds`` keep a state with no lanes to slice,
    mask or rewind?"""
    return any(not LAYER_KINDS[kind].positional for kind in kinds)


# The one allocation, and its price.

def leaf_specs(cfg, kinds: Iterable[str], lanes: int, dtype,
               kv_quant: bool = False, ring_lanes: Optional[int] = None) -> dict:
    """``{kind: {leaf: Leaf}}``: what each of ``kinds`` keeps for one row, a
    ring kind at ``ring_lanes`` lanes (None: at ``lanes``, a ring that never
    wraps)."""
    return {kind: LAYER_KINDS[kind].leaves(
                cfg, min(lanes, ring_lanes) if ring_lanes and LAYER_KINDS[kind].ring else lanes,
                dtype, kv_quant)
            for kind in kinds}


def init_layers(cfg, rows: int, lanes: int, dtype, kv_quant: bool = False,
                counts: Optional[dict] = None, ring_lanes: Optional[int] = None) -> dict:
    """Zeros for ``rows`` rows: ``{kind: {leaf: [L_kind, rows, *shape]}}``, for
    ``cfg``'s stack (or for ``counts``, kind -> layers)."""
    counts = layer_counts(cfg) if counts is None else counts
    specs = leaf_specs(cfg, counts, lanes, dtype, kv_quant, ring_lanes)
    return {kind: {name: jnp.zeros((counts[kind], rows) + leaf.shape, leaf.dtype)
                   for name, leaf in leaves.items()}
            for kind, leaves in specs.items()}


def _model_sharded(leaf: Leaf, tp: int) -> bool:
    return leaf.model_dim is not None \
        and (leaf.model_units or leaf.shape[leaf.model_dim]) % tp == 0


def state_bytes(cfg, rows: int, lanes: int, dtype, kv_quant: bool = False,
                tp: int = 1, counts: Optional[dict] = None,
                ring_lanes: Optional[int] = None) -> dict:
    """Bytes per device of what :func:`init_layers` allocates, by kind, with
    the ``model`` axis ``tp`` wide (a kind that keeps nothing: 0)."""
    counts = layer_counts(cfg) if counts is None else counts
    specs = leaf_specs(cfg, counts, lanes, dtype, kv_quant, ring_lanes)
    return {kind: sum(counts[kind] * rows * math.prod(leaf.shape)
                      * jnp.dtype(leaf.dtype).itemsize
                      / (tp if _model_sharded(leaf, tp) else 1)
                      for leaf in leaves.values())
            for kind, leaves in specs.items()}


def split_bytes(by_kind: dict) -> tuple:
    """:func:`state_bytes`' result as (what the positional kinds hold, what
    the whole kinds hold)."""
    positional = sum(b for kind, b in by_kind.items() if LAYER_KINDS[kind].positional)
    return positional, sum(by_kind.values()) - positional


# Questions asked of a tree.

def _positional(layers: dict) -> dict:
    return {kind: leaves for kind, leaves in layers.items()
            if LAYER_KINDS[kind].positional}


def n_lanes(layers: dict) -> int:
    """Lanes of the positional kinds (0 for a stack that has none): the
    length of their longest leaf, which holds a row per lane."""
    return max((a.shape[2] for leaves in _positional(layers).values()
                for a in leaves.values()), default=0)


def lane_stride(layers: dict, a) -> int:
    """Lanes a row of positional leaf ``a`` (of ``layers``) stands for. Not for
    a ring kind's leaf, which is short because it wraps (``LayerKind.ring``:
    whoever cuts lanes refuses a ring first, :func:`slice_lanes`)."""
    return n_lanes(layers) // a.shape[2]


def ring_positions(lanes: int, length, start=0, size: Optional[int] = None):
    """The position each of lanes ``start .. start + size - 1`` (default: all)
    of a ring of ``lanes`` lanes holds in a row ``length`` positions long
    (``length`` [...] int32 -> [..., size]): the newest ``p < length`` with
    ``p % lanes == m``, -1 for a lane no position has reached. A row no longer
    than the ring has position m in lane m."""
    m = start + jnp.arange(lanes if size is None else size, dtype=jnp.int32)
    last = jnp.asarray(length, jnp.int32)[..., None] - 1
    return jnp.where(m <= last, m + lanes * ((last - m) // lanes), -1)


def lane_bytes(layers: dict, kind: str) -> int:
    """Bytes ``kind``'s leaves hold, every row's (0 for a kind not in the tree)."""
    return sum(a.size * a.dtype.itemsize for a in layers.get(kind, {}).values())


def ring_bytes(layers: dict) -> int:
    """Bytes the ring kinds' leaves hold, every row's."""
    return sum(lane_bytes(layers, kind) for kind in layers if LAYER_KINDS[kind].ring)


def quantized(layers: dict) -> bool:
    """Does the tree store int8 codes (``kv_quant``)?"""
    return any(a.dtype == jnp.int8 for a in jax.tree.leaves(layers))


def whole_state_bytes(layers: dict) -> int:
    """Bytes the whole kinds hold, every row's."""
    return sum(a.size * a.dtype.itemsize for kind, leaves in layers.items()
               if not LAYER_KINDS[kind].positional for a in leaves.values())


def latent_bytes(layers: dict) -> int:
    """Bytes the latent-attention kinds' leaves hold, every row's and lane's."""
    return sum(a.size * a.dtype.itemsize for kind, leaves in layers.items()
               if LAYER_KINDS[kind].leaves is _latent_leaves for a in leaves.values())


# What a cache manager does to a row.

def insert_row(layers: dict, row: dict, slot, length=None) -> dict:
    """Copy a one-row tree into row ``slot``, cast to the pool's dtypes: a
    positional kind's lanes from 0, each leaf at its own stride (what lies
    past the row's length stays hidden), a whole kind's whole state (whatever
    the slot held is gone). A ring kind's lanes are laid out anew for the
    pool's ring: lane m takes the position :func:`ring_positions` says it
    holds in a row ``length`` long, from the lane of the staged ring (of any
    size that still holds it) where that position lies."""
    def staged(kind, a, src):
        src = src.astype(a.dtype)
        if LAYER_KINDS[kind].ring:
            at = jnp.clip(ring_positions(a.shape[2], length), 0) % src.shape[2]
            src = jnp.take(src, at, axis=2)
        return lax.dynamic_update_slice(a, src, (0, slot) + (0,) * (a.ndim - 2))

    return {kind: {name: staged(kind, a, row[kind][name]) for name, a in leaves.items()}
            for kind, leaves in layers.items()}


def reset_row(layers: dict, slot) -> dict:
    """Free row ``slot``: a whole kind's state is zeroed (no length hides it,
    and a finished row's overshoot steps advanced it past its last token); a
    positional kind needs nothing, its length is the caller's to zero."""
    return {kind: leaves if LAYER_KINDS[kind].positional
            else {name: a.at[:, slot].set(0) for name, a in leaves.items()}
            for kind, leaves in layers.items()}


def _refuse_ring(layers: dict, what: str) -> None:
    rings = [kind for kind, leaves in layers.items() if LAYER_KINDS[kind].ring and leaves]
    if rings:
        raise NotImplementedError(
            f"{what} needs lanes that keep their positions: the {', '.join(rings)} layers' lanes are a "
            "ring (lane = position % lanes)")


def slice_lanes(layers: dict, lanes: int) -> dict:
    """The first ``lanes`` lanes of every positional kind (of a strided leaf,
    the rows that stand for them). Refused for a tree with a ring kind."""
    _refuse_ring(layers, "slicing a prefix out")
    return {kind: {name: a[:, :, :lanes // lane_stride(layers, a)]
                   for name, a in leaves.items()}
            for kind, leaves in _positional(layers).items()}


def paste_lanes(layers: dict, src: dict, lanes: int) -> dict:
    """Write the first ``lanes`` lanes of ``src``'s positional kinds over the
    same lanes of ``layers``."""
    _refuse_ring(layers, "pasting a prefix in")
    src = _positional(src)
    return {kind: leaves if kind not in src
            else {name: lax.dynamic_update_slice(
                      a, src[kind][name][:, :, :lanes // lane_stride(layers, a)].astype(a.dtype),
                      (0,) * a.ndim)
                  for name, a in leaves.items()}
            for kind, leaves in layers.items()}


# How a cache shards.

def cache_shardings(mesh, cfg, cache):
    """``cache`` (either cache class) with a ``NamedSharding`` for every
    array: each leaf of the tree by the table's ``model_dim`` (kv-heads over
    ``model`` when divisible, else replicated), the bookkeeping replicated."""
    rep = NamedSharding(mesh, P())
    on_mesh = "model" in mesh.axis_names
    # Only each leaf's ``model_dim`` and that dim's size are read: any dtype does.
    specs = leaf_specs(cfg, cache.layers, n_lanes(cache.layers), jnp.bfloat16,
                       quantized(cache.layers))

    def of(leaf: Leaf, a) -> NamedSharding:
        if leaf.model_dim is None:
            return rep
        ax = "model" if on_mesh and _model_sharded(leaf, mesh.shape["model"]) else None
        return NamedSharding(mesh, P(*(ax if i == leaf.model_dim + 2 else None
                                       for i in range(a.ndim))))

    layers = {kind: {name: of(specs[kind][name], a) for name, a in leaves.items()}
              for kind, leaves in cache.layers.items()}
    return dataclasses.replace(jax.tree.map(lambda _: rep, cache), layers=layers)
