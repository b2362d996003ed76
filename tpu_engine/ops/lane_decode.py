"""A decode step's attention on the chip: one Pallas TPU kernel that reads a
slot's keys and values where the pool holds them, once, and only the lanes the
slot has. A slot that does not decode is neither read nor computed.

A decode step (one query a slot) contracts each slot's query rows against the
slot's keys as the pool stores them (``[lanes, KV x HD]``, whole rows; a
COLUMN GROUP is 128 of a row's values: one kv-head of 128, or two of 64 side by
side), softmaxes over the lanes the slot has, and contracts the probabilities
with the values. XLA's form of so few rows first copies the layer out of the
carried pool (it does not stream a slice of the pool into the contraction's
fusion), or transposes it so that the LANES lie minor, and then reads EVERY
lane of EVERY slot. Here a program (one slot) walks the slot's lanes a block at
a time, flash-style: a block of keys and one of values, whole rows as they lie,
are copied into the chip's fast memory once and serve all the column groups;
the running maximum, sum and accumulator stay there; a block past the slot's
length is neither copied (its index map names the last block in use again,
which is not fetched twice) nor computed; and a slot with no lane to see names
a block that is already in flight (the last one of the live slot before it),
skips its arithmetic and leaves zeros.

The operands are the serving pool's leaves as stored, the WHOLE stacks
``[L, slots, lanes, KV x HD]``; the layer index, the slots' lane counts and the
table of blocks an idle slot names are prefetched to scalar memory and the
index maps pick the blocks. A window layer's ring (one window of lanes, every
one of them inside the window of the position just written) is read the same
way: its lanes up to the row's length, all of them once it has wrapped. One
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


def blocks_named(visible):
    """What each slot's index map names, from ``visible`` [slots] int32 (lanes
    a slot sees; 0: the slot is idle): (src, lo, hi) [slots] int32 — at grid
    step j slot b reads block ``clip(j, lo[b], hi[b])`` of slot ``src[b]``. A
    live slot walks its own blocks 0 .. its last; an idle one names ONE block
    at every step, the one in flight when the walk reaches it (the last block
    of the live slot before it; before the first live slot that slot's block 0,
    which the walk needs next), so it fetches nothing."""
    n = visible.shape[0]
    slot = jnp.arange(n, dtype=jnp.int32)
    live = visible > 0
    before = lax.cummax(jnp.where(live, slot, -1))                    # the live slot at or before b
    after = lax.cummin(jnp.where(live, slot, n), reverse=True)        # ... at or after b
    src = jnp.where(before >= 0, before, jnp.where(after < n, after, 0))
    last = jnp.maximum(visible - 1, 0) // LANES
    hi = jnp.where(before >= 0, last[src], 0)
    return src, jnp.where(live, 0, hi), hi


def _kernel(at_ref, n_ref, src_ref, lo_ref, hi_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, groups: int, width: int):
    del at_ref, src_ref, lo_ref, hi_ref  # the index maps read them
    b, j = pl.program_id(0), pl.program_id(1)
    n = n_ref[b]                                                 # lanes this slot has

    @pl.when(j == 0)
    def _():
        _reset(m_ref, l_ref, acc_ref)

    @pl.when(j * LANES < n)
    def _():
        for i in range(groups):                                  # a column group: `width` columns of the rows
            cols = slice(i * width, (i + 1) * width)
            s = lax.dot_general(q_ref[0, i], k_ref[0, 0, :, cols], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale      # [rows, lanes]
            lane = j * LANES + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            _fold(jnp.where(lane < n, s, _NEG_INF), v_ref[0, 0, :, cols],
                  m_ref.at[i], l_ref.at[i], acc_ref.at[i])

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        # an idle slot's rows are defined: zeros, not 0 / 0
        o_ref[0] = jnp.where(n > 0, acc_ref[...] / l_ref[...], 0.0)


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

    def rows_map(b, j, at, n, src, lo, hi):
        # a block past the slot's last visible one names that one again, an idle
        # slot the block in flight: neither is fetched anew
        return (at[0], src[b], jnp.clip(j, lo[b], hi[b]), 0)

    def own(b, j, *_):
        return (b, 0, 0, 0)

    out = pl.pallas_call(
        partial(_kernel, scale=scale, groups=P, width=W),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B, S // LANES),
            in_specs=[pl.BlockSpec((1, P, rows, W), own),
                      pl.BlockSpec((1, 1, LANES, P * W), rows_map),
                      pl.BlockSpec((1, 1, LANES, P * W), rows_map)],
            out_specs=pl.BlockSpec((1, P, rows, W), own),
            scratch_shapes=[pltpu.VMEM((P, rows, 1), jnp.float32), pltpu.VMEM((P, rows, 1), jnp.float32),
                            pltpu.VMEM((P, rows, W), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, P, rows, W), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=not on_tpu(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), visible, *blocks_named(visible), q, keys, values)
    return out[:, :, :R]
