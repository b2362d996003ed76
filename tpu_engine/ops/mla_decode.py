"""Latent attention (MLA) on the chip: two Pallas TPU kernels, one for the
absorbed decode step (it reads a slot's latent rows once, and only the rows the
slot has) and one for a prefill chunk's expanded attention (flash-style: no
score of a row's length leaves the chip's fast memory).

A decode step of an MLA layer contracts each slot's absorbed queries ``q_a |
q_r`` ([heads, row width]) against the slot's latent rows as the pool stores
them, softmaxes over the lanes up to the slot's position, and contracts the
probabilities with the same rows (``generate._mla_block``). XLA lowers that to
two contractions over EVERY lane of EVERY slot, each reading the layer's
[slots, lanes, width] from memory: at 32 slots of 10 240 lanes that is 2 x 419
MB a layer-step where the rows in use are a quarter of it. Here a program
(one slot) walks the slot's lanes a block at a time, flash-style: a block is
copied into the chip's fast memory once and serves both contractions, the
running maximum, sum and accumulator stay there, and a block past the slot's
position is neither copied (its index map names the last block in use again,
which is not fetched twice) nor computed.

The latent operand is the serving pool's leaf as stored, the WHOLE stack
``[L, slots, lanes, width]``; the layer index and the slots' visible lane
counts are prefetched to scalar memory and the index maps pick the blocks.
One device's pool only (the leaf is replicated under a mesh and no caller
shards it).

A prefill chunk (T > 1 queries a row) attends keys and values EXPANDED from the
row's latent (``generate._mla_block``: 192-wide keys, 128-wide values per head).
XLA's form writes every head's float32 scores against every lane of the staging
row and passes over them five times (two contractions summed, the mask, the
maximum, the sum, the normalised probabilities): at 2 048 queries against 8 192
lanes that is 2 GB of traffic a layer for 0.17 TFLOP of arithmetic.
:func:`mla_chunk_attend` is the flash form: a program (one head, one tile of
queries) walks the key blocks up to its tile's last position, two copies in
flight, with the running maximum, sum and accumulator in fast memory; a block
past the tile's last position is neither fetched nor computed.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
# Lanes a step copies and scores: 512 rows of 640 bfloat16 values are 640 KiB,
# twice in flight.
_LANES = 512

# Off the TPU the kernel can only be interpreted, and XLA's two contractions
# are merely slower there, not wrong: the caller keeps them unless a test asks
# for the interpreter here.
INTERPRET_OFF_TPU = False


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def engages(latent) -> bool:
    """Whether :func:`mla_decode` runs for this stacked leaf ``[L, slots, lanes,
    width]`` on this process's devices: whole blocks of lanes, a row of whole
    128-value tile columns, on a TPU (or interpreted, where a test asked).
    Anything else keeps the XLA contractions. Decided from what the trace
    sees; no option selects it."""
    return (latent.ndim == 4 and latent.shape[2] % _LANES == 0 and latent.shape[3] % 128 == 0
            and (on_tpu() or INTERPRET_OFF_TPU))


def _fold(s, v, m_ref, l_ref, acc_ref):
    """One block's masked scores ``s`` [rows, lanes] (float32) and values ``v``
    [lanes, width] folded into the running softmax the three refs hold."""
    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_old - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _reset(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _kernel(at_ref, n_ref, q_ref, rows_ref, o_ref, m_ref, l_ref, acc_ref, *, scale: float, lanes: int):
    del at_ref  # the index maps read it
    b, j = pl.program_id(0), pl.program_id(1)
    n = n_ref[b]                                                 # lanes this slot may see

    @pl.when(j == 0)
    def _():
        _reset(m_ref, l_ref, acc_ref)

    @pl.when(j * lanes < n)
    def _():
        q, rows = q_ref[0], rows_ref[0, 0]                       # [H, W], [lanes, W]
        s = lax.dot_general(q, rows, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        lane = j * lanes + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _fold(jnp.where(lane < n, s, _NEG_INF), rows, m_ref, l_ref, acc_ref)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def mla_decode(q, latent, layer, visible, *, scale: float):
    """One query a slot against layer ``layer`` of the latent pool.

    q [slots, H, W]: a slot's absorbed queries ``q_a | q_r | zeros`` per head,
    W the pool's row width; latent [L, slots, lanes, W], the whole stack, only
    read; ``layer`` scalar int32; ``visible`` [slots] int32, how many leading
    lanes of its row a slot attends (its position + 1, at least 1: lane m of a
    slot's row holds position m, the serving pool's and the lockstep cache's
    layout without a window). Returns [slots, H, W] in q's dtype:
    ``softmax(scale x q . rows^T over the visible lanes) . rows``, what
    ``generate._mla_block``'s two XLA contractions compute, with the softmax
    running over blocks of lanes (float32 maximum, sum and accumulator; the
    unnormalised probabilities meet the rows in the rows' dtype)."""
    _, B, S, W = latent.shape
    H = q.shape[1]
    blocks = S // _LANES

    def rows_map(b, j, at, n):
        # a block past the slot's last visible one names that one again: not fetched anew
        return (at[0], b, jnp.minimum(j, (n[b] - 1) // _LANES), 0)

    return pl.pallas_call(
        partial(_kernel, scale=scale, lanes=_LANES),
        name="mla_decode",  # the kernel's name in a profile
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, blocks),
            in_specs=[pl.BlockSpec((1, H, W), lambda b, j, at, n: (b, 0, 0)),
                      pl.BlockSpec((1, 1, _LANES, W), rows_map)],
            out_specs=pl.BlockSpec((1, H, W), lambda b, j, at, n: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32), pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, W), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, W), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=not on_tpu(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), jnp.maximum(visible.astype(jnp.int32), 1), q, latent)


# A chunk program's tile of queries and block of key lanes.
_QUERY_TILE = 512


def chunk_engages(T: int, lanes: int) -> bool:
    """Whether :func:`mla_chunk_attend` runs for a chunk of ``T`` queries
    against a row of ``lanes``: whole query tiles and key blocks, on a TPU (or
    interpreted, where a test asked). Anything else keeps XLA's form."""
    return T % _QUERY_TILE == 0 and lanes % _LANES == 0 and (on_tpu() or INTERPRET_OFF_TPU)


def _chunk_kernel(first_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, scale: float, heads: int, tq: int, lanes: int):
    r, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    start = first_ref[r // heads] + i * tq                        # the tile's first query's position

    @pl.when(j == 0)
    def _():
        _reset(m_ref, l_ref, acc_ref)

    @pl.when(j * lanes <= start + tq - 1)
    def _():
        s = lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale      # [tq, lanes]
        lane = j * lanes + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        query = start + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        _fold(jnp.where(lane <= query, s, _NEG_INF), v_ref[0], m_ref, l_ref, acc_ref)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def mla_chunk_attend(q, k, v, first, *, scale: float):
    """A chunk's causal attention against its row's expanded keys and values.

    q [B, H, T, qk] (query t of row b stands at position ``first[b] + t``), k
    [B, H, M, qk], v [B, H, M, vd] (lane m holds position m; what lies past a
    query's position is never read: a block past a tile's last position is not
    fetched, a lane inside it is masked), ``first`` [B] int32. Returns
    [B, H, T, vd] in q's dtype: ``softmax(scale x q . k^T over lanes <= the
    query's position) . v``, the softmax running over blocks of lanes (float32
    maximum, sum and accumulator)."""
    B, H, T, qk = q.shape
    M, vd = k.shape[2], v.shape[3]
    tq = _QUERY_TILE
    q, k, v = (a.reshape(B * H, *a.shape[2:]) for a in (q, k, v))

    def key_map(r, i, j, first):
        # a block past the tile's last position names the last one in reach again
        return (r, jnp.minimum(j, (first[r // H] + (i + 1) * tq - 1) // _LANES), 0)

    out = pl.pallas_call(
        partial(_chunk_kernel, scale=scale, heads=H, tq=tq, lanes=_LANES),
        name="mla_chunk_attn",  # the kernel's name in a profile
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * H, T // tq, M // _LANES),
            in_specs=[pl.BlockSpec((1, tq, qk), lambda r, i, j, first: (r, i, 0)),
                      pl.BlockSpec((1, _LANES, qk), key_map),
                      pl.BlockSpec((1, _LANES, vd), key_map)],
            out_specs=pl.BlockSpec((1, tq, vd), lambda r, i, j, first: (r, i, 0)),
            scratch_shapes=[pltpu.VMEM((tq, 1), jnp.float32), pltpu.VMEM((tq, 1), jnp.float32),
                            pltpu.VMEM((tq, vd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B * H, T, vd), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=not on_tpu(),
    )(first.astype(jnp.int32), q, k, v)
    return out.reshape(B, H, T, vd)
