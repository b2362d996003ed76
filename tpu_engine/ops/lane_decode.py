"""A decode step's attention on the chip: one Pallas TPU kernel that reads a
slot's keys and values where the pool holds them, once, and only the lanes the
slot has. A slot that does not decode is neither read nor computed, and a call
pays for the blocks it walks, not for the slots it walks them for.

A decode step (one query a slot) contracts each slot's query rows against the
slot's keys as the pool stores them (``[lanes, KV x HD]``, whole rows; a
COLUMN GROUP is 128 of a row's values: one kv-head of 128, or two of 64 side by
side), softmaxes over the lanes the slot has, and contracts the probabilities
with the values. XLA's form of so few rows first copies the layer out of the
carried pool (it does not stream a slice of the pool into the contraction's
fusion), or transposes it so that the LANES lie minor, and then reads EVERY
lane of EVERY slot. Here ONE sequence of grid steps walks the blocks in use of
all slots back to back, flash-style (slot 0's blocks, then the next live
slot's, ...: :func:`walk_tables`): a step copies a block of keys and one of
values, whole rows as they lie, into the chip's fast memory once, and they
serve all the column groups; the running maximum, sum and accumulator stay
there, reset at a slot's first block, divided and stored at its last. A step's
operands are named by the tables and not by the grid, so while a slot's last
block is folded the next slot's first is already on its way, its queries with
it, and the result of the slot before goes out (a program a slot, which this
replaced, left a block's copy uncovered at every slot: 3.1 us a slot, 0.10 of
a call's 1.59 ms at 32 slots x 13 blocks; PERF.md, PR 49). A slot with no lane
to see owns no step: it is neither copied nor computed, and its rows come back
zeros. The grid's extent is traced: as many steps as there are blocks in use
(one, which does nothing, where there are none), so a call over a pool that is
mostly empty costs what its few blocks cost (a static grid of a step for every
block the leaf holds walked a tail of empty steps, 0.1 us each: 33 us of 1 487
at 13 of 24 blocks a slot, 69 of 85 for an idle call; PERF.md, PR 49).

The operands are the serving pool's leaves as stored, the WHOLE stacks
``[L, slots, lanes, KV x HD]``; the layer index, the slots' lane counts and the
walk's tables are prefetched to scalar memory and the index maps pick the
blocks. A window layer's ring (one window of lanes, every one of them inside
the window of the position just written) is read the same way: its lanes up to
the row's length, all of them once it has wrapped: one block a slot. One
device's pool only (no caller hands it a leaf that a mesh shards).

The callers (``generate``): the ``attn`` kind's ``_decode_block`` (a column
group's rows are its kv-head's G queries, or ``q_a | 0`` and ``0 | q_b`` of two
heads of 64: ``_grouped_queries``; profile name ``attn_decode``) and the three
differential kinds' ``_diff_attention`` (``q1 | 0`` and ``0 | q2`` of a pair:
``_diff_queries``; profile name ``diff_decode``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_engine.ops.mla_decode import _fold, _reset

_NEG_INF = -1e30
# Lanes a step copies and scores: 512 rows of 1 280 bfloat16 values are 1.25
# MiB, keys and values each, twice in flight.
LANES = 512
# Values of a row one column group holds: the chip's tile is 128 columns wide.
COLUMNS = 128
# Query rows a column group's block of the kernel holds (padded with zeros): a
# whole bfloat16 tile of sublanes.
_ROWS = 16

# Off the TPU the kernel can only be interpreted, and XLA's two contractions
# are merely slower there, not wrong: the caller keeps them unless a test asks
# for the interpreter here.
INTERPRET_OFF_TPU = False


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def engages(keys) -> bool:
    """Whether :func:`lane_decode` runs for this stacked leaf ``[L, slots,
    lanes, width]`` on this process's devices: whole blocks of lanes, whole
    groups of 128 columns, on a TPU (or interpreted, where a test asked).
    Anything else keeps the XLA contractions. Decided from what the trace
    sees; no option selects it."""
    return (keys.ndim == 4 and keys.shape[2] % LANES == 0 and keys.shape[3] % COLUMNS == 0
            and (on_tpu() or INTERPRET_OFF_TPU))


def walk_tables(visible, steps: int):
    """The one walk over all slots' blocks, from ``visible`` [slots] int32
    (lanes a slot sees; 0: the slot is idle): (slot_of, block_of) [steps] int32
    and the number of blocks in use, int32. Step t < blocks reads block
    ``block_of[t]`` of slot ``slot_of[t]``: slot 0's blocks 0 .. its last, then
    the next live slot's, back to back; an idle slot owns no step. The steps
    from ``blocks`` on (``steps`` is the most a walk can take; the grid stops
    at ``blocks``) name the last block in use again, block 0 of slot 0 where no
    slot is live: what the one step of an empty walk names."""
    owned = -(-visible // LANES)                                   # blocks a slot owns
    slot = jnp.arange(visible.shape[0], dtype=jnp.int32)
    # the step after a slot's last (a cumulative sum, said as one compare-and-sum: the chip runs it as one small op)
    end = jnp.sum(jnp.where(slot[None, :] <= slot[:, None], owned[None, :], 0), axis=1)
    blocks = jnp.sum(owned)
    t = jnp.minimum(jnp.arange(steps, dtype=jnp.int32), jnp.maximum(blocks - 1, 0))
    behind = end[None, :] <= t[:, None]                            # [steps, slots]: the slots a step has passed
    slot_of = jnp.minimum(jnp.sum(behind, axis=1, dtype=jnp.int32), visible.shape[0] - 1)
    return slot_of * (blocks > 0), t - jnp.sum(behind * owned[None, :], axis=1, dtype=jnp.int32), blocks


def _kernel(at_ref, n_ref, blocks_ref, slot_ref, block_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, groups: int, width: int):
    del at_ref  # the index maps read it
    t = pl.program_id(0)
    j = block_ref[t]
    n = n_ref[slot_ref[t]]                                       # lanes this step's slot has
    live = t < blocks_ref[0]                                     # else the one step of an empty walk

    @pl.when(live & (j == 0))
    def _():
        _reset(m_ref, l_ref, acc_ref)

    @pl.when(live)
    def _():
        for i in range(groups):                                  # a column group: `width` columns of the rows
            cols = slice(i * width, (i + 1) * width)
            s = lax.dot_general(q_ref[0, i], k_ref[0, 0, :, cols], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale      # [rows, lanes]
            lane = j * LANES + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            _fold(jnp.where(lane < n, s, _NEG_INF), v_ref[0, 0, :, cols],
                  m_ref.at[i], l_ref.at[i], acc_ref.at[i])

    @pl.when(live & ((j + 1) * LANES >= n))                      # the slot's last block
    def _():
        o_ref[0] = acc_ref[...] / l_ref[...]


def lane_decode(q, keys, values, layer, visible, *, scale: float, name: str):
    """One step's queries against layer ``layer`` of the keys and values.

    q [slots, P, R, W]: per column group the R query rows that attend it (W =
    128 or a multiple: a row is zero outside the columns of its own kv-head);
    keys, values [L, slots, lanes, P x W], the whole stacks, only read;
    ``layer`` scalar int32; ``visible`` [slots] int32, how many leading lanes
    of its row a slot attends, 0 for a slot that does not decode (nothing of it
    is fetched or computed; its rows come back zeros). ``name`` is the kernel's
    name in a profile. Returns [slots, P, R, W] float32: ``softmax(scale x q .
    k^T over the visible lanes) . v`` per column group, what the callers' two
    XLA contractions compute, with the softmax running over blocks of lanes
    (float32 maximum, sum and accumulator; the unnormalised probabilities meet
    the values in the values' dtype)."""
    _, B, S, _ = keys.shape
    P, R, W = q.shape[1:]
    rows = -(-R // _ROWS) * _ROWS
    q = jnp.pad(q, ((0, 0), (0, 0), (0, rows - R), (0, 0)))
    visible = jnp.clip(visible.astype(jnp.int32), 0, S)
    slot_of, block_of, blocks = walk_tables(visible, B * (S // LANES))

    def rows_map(t, at, n, blocks, slot_of, block_of):
        return (at[0], slot_of[t], block_of[t], 0)

    def own(t, at, n, blocks, slot_of, block_of):
        return (slot_of[t], 0, 0, 0)

    out = pl.pallas_call(
        partial(_kernel, scale=scale, groups=P, width=W),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(jnp.maximum(blocks, 1),),                      # traced: as many steps as blocks in use
            in_specs=[pl.BlockSpec((1, P, rows, W), own),
                      pl.BlockSpec((1, 1, LANES, P * W), rows_map),
                      pl.BlockSpec((1, 1, LANES, P * W), rows_map)],
            out_specs=pl.BlockSpec((1, P, rows, W), own),
            scratch_shapes=[pltpu.VMEM((P, rows, 1), jnp.float32), pltpu.VMEM((P, rows, 1), jnp.float32),
                            pltpu.VMEM((P, rows, W), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, P, rows, W), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=not on_tpu(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), visible, blocks.reshape(1), slot_of, block_of, q, keys, values)
    # an idle slot owns no step, so nothing wrote its rows: they are defined, zeros
    return jnp.where((visible > 0)[:, None, None, None], out[:, :, :R], 0.0)
