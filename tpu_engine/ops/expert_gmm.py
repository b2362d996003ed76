"""A mixture's held experts over the routed pairs only: two Pallas TPU kernels
that run gate / up and down as GROUPED contractions whose rows follow the pairs.

The masked contraction (``generate._moe_mlp_decode``) computes every held
expert for every token and zeroes what was not routed: right while the
experts' READ bounds it (a decode step, a short chunk), ``held / (top_k x held /
n_experts)`` times the routed pairs' FLOPs once the chunk is long. Here the
pairs (token, choice) whose expert this tree holds are laid out BY EXPERT in a
buffer of row tiles (:func:`pair_layout`), every expert's group padded up to
whole tiles of :data:`ROWS` rows, so that a tile belongs to ONE expert and the
kernels are plain tile contractions: tile ``i`` of the rows against expert
``tile_expert[i]``'s ``[D, F]`` of the stack where it lies. The buffer is sized
for any routing (every pair ours); a tile past the last one in use names that
one's blocks again, so it is neither fetched nor computed (the way
``ops/lane_decode.py`` skips an idle slot): the rows computed FOLLOW the pairs,
nothing is dropped and there is no capacity.

The weight operands are the served stacks as stored, ``[L, E, D, F]`` and
``[L, E, F, D]`` whole; the layer index, the tiles' experts and the tiles in use
are prefetched to scalar memory and the index maps pick the blocks (a layer
sliced out of the stack for a custom call would be copied first: 277 MB a layer
of the longctx32 cell). The row-tile axis is the grid's inner one: consecutive
tiles of one expert find its block resident and fetch nothing.

Why not ``lax.ragged_dot`` (which ``transformer._moe_mlp_ragged`` uses in
training): on this chip it lowers to a Mosaic kernel of row tiles of 512 whose
every visited (tile, group) pair costs the whole tile, so ≈ 190 rows an expert
visit 21 tiles for 6 tiles' worth of pairs; its two custom calls carry no
``named_scope`` (``op_name="ragged-dot-none"``), so a profile cannot place
them; and it takes the layer's slice, the copy above (compile-only rehearsal
and probe, PERF.md §6 PR 45).

One device's stacks only (``generate.experts_grouped_engages`` declines a
replica placed over a mesh), bfloat16 or float32 kernels (no ``QuantWeight``).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows of a tile: the chip's matrix unit is 128 wide, and a group's padding is
# half a tile an expert on average, so the smallest whole tile wastes least.
ROWS = 128
# The most one weight block may take of the chip's fast memory (gate and up are
# two, each twice in flight): longctx32's ``[2048, 1408]`` bfloat16 is 5.5 MiB
# and stays whole, so an expert's weights are read once.
_BLOCK_BYTES = 6 << 20
_VMEM_LIMIT = 64 << 20

# Off the TPU the kernels can only be interpreted, and the masked contraction
# is merely slower there, not wrong: the caller keeps it unless a test asks for
# the interpreter here.
INTERPRET_OFF_TPU = False


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def engages(gate) -> bool:
    """Whether :func:`experts` can run for this stacked leaf ``[L, E, D, F]``
    on this process's devices: a plain bfloat16 or float32 array (an int8
    ``QuantWeight`` keeps the masked contraction, which dequantises inline)
    whose widths are whole 128-value tile columns, on a TPU (or interpreted,
    where a test asked). Decided from what the trace sees; no option selects
    it."""
    return (getattr(gate, "ndim", 0) == 4 and getattr(gate, "dtype", None) in (jnp.bfloat16, jnp.float32)
            and gate.shape[2] % 128 == 0 and gate.shape[3] % 128 == 0
            and (on_tpu() or INTERPRET_OFF_TPU))


def n_tiles(pairs: int, n_groups: int) -> int:
    """Row tiles a buffer needs for ``pairs`` pairs in ``n_groups`` groups
    however they fall: each group's last tile may be all but one row padding."""
    return -(-(pairs + n_groups * (ROWS - 1)) // ROWS)


def take(table, i):
    """``table[i]`` along the first axis for indices the layout has made: in
    range by construction, so neither clamped nor filled (a pass each over the
    gathered rows)."""
    return table.at[i].get(mode="promise_in_bounds")


class PairLayout(NamedTuple):
    """Where the pairs lie (:func:`pair_layout`). ``src`` [tiles x ROWS]: the
    pair a buffer row holds (some pair, for a row of padding); ``pos`` [pairs]:
    the buffer row of a pair (meaningless for a pair that is not ours);
    ``tile_expert``, ``tile_named`` [tiles]: the expert whose rows tile i holds
    and the tile whose blocks grid step i names (itself, or the last one in
    use); ``tiles_used`` [1]."""
    src: jax.Array
    pos: jax.Array
    tile_expert: jax.Array
    tile_named: jax.Array
    tiles_used: jax.Array


def pair_layout(expert, n_groups: int, tiles: int) -> PairLayout:
    """Lay pairs out by expert, each group padded up to whole tiles.

    expert [pairs] int32: the (local) expert of each pair, ``n_groups`` for a
    pair that is not ours. Two stable sorts of ``pairs`` integers and nothing
    that scatters: the first gives each group's pairs in order, the second
    brings every pair's row back to the pair's own place."""
    P = expert.shape[0]
    iota = jnp.arange(P, dtype=jnp.int32)
    sorted_e, order = lax.sort((expert, iota), num_keys=1)
    groups = jnp.arange(n_groups, dtype=jnp.int32)
    ends_sorted = jnp.sum(expert[None, :] <= groups[:, None], axis=1, dtype=jnp.int32)     # [G]: where a group
    sizes = jnp.diff(ends_sorted, prepend=0)                                                # ends among the sorted
    firsts_sorted = ends_sorted - sizes
    tiles_of = -(-sizes // ROWS)
    tile_ends = jnp.cumsum(tiles_of)
    starts = (tile_ends - tiles_of) * ROWS                                                  # a group's first row
    tiles_used = tile_ends[-1:]

    tile = jnp.arange(tiles, dtype=jnp.int32)
    tile_named = jnp.minimum(tile, jnp.maximum(tiles_used - 1, 0))
    tile_expert = jnp.minimum(jnp.sum(tile_named[:, None] >= tile_ends[None, :], axis=1, dtype=jnp.int32),
                              n_groups - 1)

    # a tile's rows: per TILE its group's first row, size and first sorted pair (tables of ``tiles`` entries),
    # spread over its rows by broadcasting: the chip gathers integers one at a time, so the only gather of a
    # row's length left is the one that cannot be anything else, ``order`` at the sorted pair a row holds
    within = (tile * ROWS - take(starts, tile_expert))[:, None] + jnp.arange(ROWS, dtype=jnp.int32)[None, :]
    real = (within >= 0) & (within < take(sizes, tile_expert)[:, None])
    src = take(order, jnp.where(real, take(firsts_sorted, tile_expert)[:, None] + within, 0).reshape(-1))

    ours = jnp.minimum(sorted_e, n_groups - 1)
    row_sorted = take(starts, ours) + iota - take(firsts_sorted, ours)
    _, pos = lax.sort((order, row_sorted), num_keys=1)
    return PairLayout(src, pos, tile_expert, tile_named, tiles_used)


def _width_block(contracted: int, width: int, itemsize: int) -> int:
    """Columns of a weight block ``[contracted, columns]``: all of ``width``
    where that fits :data:`_BLOCK_BYTES`, else its largest divisor in whole
    128-column tiles that does (128 where none does)."""
    fits = [c for c in range(128, width + 1, 128)
            if width % c == 0 and contracted * c * itemsize <= _BLOCK_BYTES]
    return max(fits, default=128)


def _gate_up_kernel(at_ref, expert_ref, named_ref, used_ref, x_ref, g_ref, u_ref, o_ref):
    del at_ref, expert_ref, named_ref  # the index maps read them

    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        x = x_ref[...]
        g = jnp.dot(x, g_ref[0, 0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, u_ref[0, 0], preferred_element_type=jnp.float32)
        o_ref[...] = (jax.nn.silu(g) * u).astype(o_ref.dtype)


def _down_kernel(at_ref, expert_ref, named_ref, used_ref, a_ref, d_ref, o_ref):
    del at_ref, expert_ref, named_ref

    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        o_ref[...] = jnp.dot(a_ref[...], d_ref[0, 0], preferred_element_type=jnp.float32).astype(o_ref.dtype)


def experts(xs, gate, up, down, layer, layout: PairLayout):
    """The experts' SwiGLU of every row tile in use.

    xs [tiles x ROWS, D]: the pairs' rows in :func:`pair_layout`'s order;
    gate, up [L, E, D, F] and down [L, E, F, D]: the whole stacks, only read;
    ``layer`` scalar int32. Returns [tiles x ROWS, D] in ``xs``'s dtype: row r
    of a tile in use is ``(silu(x_r W_gate) * (x_r W_up)) W_down`` of its
    tile's expert, float32 accumulation, the activations between the two
    kernels in ``xs``'s dtype; a row of a tile NOT in use is whatever the buffer
    held (the caller reads no such row)."""
    R, D = xs.shape
    F = gate.shape[3]
    tiles = R // ROWS
    itemsize = gate.dtype.itemsize
    fb, db = _width_block(D, F, itemsize), _width_block(F, D, itemsize)
    scalars = (jnp.asarray(layer, jnp.int32).reshape(1), layout.tile_expert, layout.tile_named,
               layout.tiles_used)

    def rows_map(n, i, at, expert, named, used):
        return (named[i], 0)

    def out_map(n, i, at, expert, named, used):
        return (named[i], n)

    def weight_map(n, i, at, expert, named, used):
        return (at[0], expert[i], 0, n)

    def call(kernel, name, operands, in_specs, width, block):
        return pl.pallas_call(
            kernel, name=name,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4, grid=(width // block, tiles), in_specs=in_specs,
                out_specs=pl.BlockSpec((ROWS, block), out_map)),
            out_shape=jax.ShapeDtypeStruct((R, width), xs.dtype),
            compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary"),
                                                 vmem_limit_bytes=_VMEM_LIMIT),
            interpret=not on_tpu(),
        )(*scalars, *operands)

    act = call(_gate_up_kernel, "expert_gate_up", (xs, gate, up),
               [pl.BlockSpec((ROWS, D), rows_map), pl.BlockSpec((1, 1, D, fb), weight_map),
                pl.BlockSpec((1, 1, D, fb), weight_map)], F, fb)
    return call(_down_kernel, "expert_down", (act, down),
                [pl.BlockSpec((ROWS, F), rows_map), pl.BlockSpec((1, 1, F, db), weight_map)], D, db)
