"""The decode step of differential attention on the chip: one Pallas TPU kernel
that reads a slot's keys and values once, and only the lanes the slot has.

A decode step of a differential-attention layer contracts each slot's queries
(per pair of kv-heads ``2 G`` rows of 128 values: ``q1 | 0`` and ``0 | q2`` of
its G query pairs, ``generate._diff_attend``) against the slot's keys as the
pool stores them (``[lanes, KV x HD]``, a kv pair 128 values ``k1 | k2``),
softmaxes over the lanes the slot has, and contracts the probabilities with the
values. XLA lowers the two contractions of so few rows to loops that want the
LANES minor, so it transposes the layer's keys and values (1 GB each at 32
slots of 12 288 lanes) on every step, for each of the eight layers that read
the one full cache, and then reads EVERY lane of EVERY slot. Here a program
(one slot) walks the slot's lanes a block at a time, flash-style: a block of
keys and one of values, whole rows as they lie, are copied into the chip's fast
memory once and serve all the kv pairs; the running maximum, sum and
accumulator stay there; a block past the slot's length is neither copied (its
index map names the last block in use again, which is not fetched twice) nor
computed.

The operands are the serving pool's leaves as stored, the WHOLE stacks
``[L, slots, lanes, KV x HD]``; the layer index and the slots' lane counts are
prefetched to scalar memory and the index maps pick the blocks. A window
layer's ring (one window of lanes, every one of them inside the window of the
position just written) is read the same way: its lanes up to the row's length,
all of them once it has wrapped. One device's pool only (the stack is refused
under a mesh).
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
_LANES = 512
# Query rows a kv pair's block of the kernel holds (2 G padded with zeros): a
# whole bfloat16 tile of sublanes.
_ROWS = 16

# Off the TPU the kernel can only be interpreted, and XLA's two contractions
# are merely slower there, not wrong: the caller keeps them unless a test asks
# for the interpreter here.
INTERPRET_OFF_TPU = False


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def engages(keys) -> bool:
    """Whether :func:`diff_decode` runs for this stacked leaf ``[L, slots,
    lanes, width]`` on this process's devices: whole blocks of lanes, pairs of
    128 values, on a TPU (or interpreted, where a test asked). Anything else
    keeps the XLA contractions. Decided from what the trace sees; no option
    selects it."""
    return (keys.ndim == 4 and keys.shape[2] % _LANES == 0 and keys.shape[3] % 128 == 0
            and (on_tpu() or INTERPRET_OFF_TPU))


def _kernel(at_ref, n_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, lanes: int, pairs: int, width: int):
    del at_ref  # the index maps read it
    b, j = pl.program_id(0), pl.program_id(1)
    n = n_ref[b]                                                 # lanes this slot has

    @pl.when(j == 0)
    def _():
        _reset(m_ref, l_ref, acc_ref)

    @pl.when(j * lanes < n)
    def _():
        for i in range(pairs):                                   # a kv pair: `width` columns of the rows
            cols = slice(i * width, (i + 1) * width)
            s = lax.dot_general(q_ref[0, i], k_ref[0, 0, :, cols], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale      # [rows, lanes]
            lane = j * lanes + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            _fold(jnp.where(lane < n, s, _NEG_INF), v_ref[0, 0, :, cols],
                  m_ref.at[i], l_ref.at[i], acc_ref.at[i])

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = acc_ref[...] / l_ref[...]


def diff_decode(q, keys, values, layer, visible, *, scale: float):
    """One step's queries against layer ``layer`` of the keys and values.

    q [slots, P, R, W]: per kv pair the R query rows that attend it (W = 128:
    ``q1 | 0`` or ``0 | q2``); keys, values [L, slots, lanes, P x W], the whole
    stacks, only read; ``layer`` scalar int32; ``visible`` [slots] int32, how
    many leading lanes of its row a slot attends (at least 1). Returns [slots,
    P, R, W] float32: ``softmax(scale x q . k^T over the visible lanes) . v``
    per kv pair, what ``generate._diff_attend``'s two XLA contractions compute,
    with the softmax running over blocks of lanes (float32 maximum, sum and
    accumulator; the unnormalised probabilities meet the values in the values'
    dtype)."""
    _, B, S, _ = keys.shape
    P, R, W = q.shape[1:]
    rows = -(-R // _ROWS) * _ROWS
    q = jnp.pad(q, ((0, 0), (0, 0), (0, rows - R), (0, 0)))

    def rows_map(b, j, at, n):
        # a block past the slot's last visible one names that one again: not fetched anew
        return (at[0], b, jnp.minimum(j, (n[b] - 1) // _LANES), 0)

    out = pl.pallas_call(
        partial(_kernel, scale=scale, lanes=_LANES, pairs=P, width=W),
        name="diff_decode",  # the kernel's name in a profile
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, S // _LANES),
            in_specs=[pl.BlockSpec((1, P, rows, W), lambda b, j, at, n: (b, 0, 0, 0)),
                      pl.BlockSpec((1, 1, _LANES, P * W), rows_map),
                      pl.BlockSpec((1, 1, _LANES, P * W), rows_map)],
            out_specs=pl.BlockSpec((1, P, rows, W), lambda b, j, at, n: (b, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((P, rows, 1), jnp.float32), pltpu.VMEM((P, rows, 1), jnp.float32),
                            pltpu.VMEM((P, rows, W), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, P, rows, W), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=not on_tpu(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), jnp.maximum(visible.astype(jnp.int32), 1), q, keys, values)
    return out[:, :, :R]
