"""Attention over CHOSEN blocks of a KV pool: two Pallas TPU kernels, one for a
decode step (one query a row) and one for a prefill chunk (a tile of queries).

A block-sparse attention layer's decode step reads, for every row and
kv-head, a handful of blocks of the row's lanes (``generate._select_blocks``
says which) and nothing else of the row. XLA lowers such a gather of
``[block, HD]`` slices to a sequential loop, one slice an iteration; here the
block table is prefetched to scalar memory, each program (one row, one
kv-head) starts the DMAs of all its blocks from the pool where it lies in HBM
— ``k_pool[layer, row, id x block : (id + 1) x block, g x HD : (g + 1) x HD]`` —
and folds each block into a running softmax as it lands.

A prefill chunk (T > 1 queries a row) is :func:`sparse_chunk_attend`: one
program per row, kv-head and TILE of queries (all G heads of the group x the
tile's queries are the rows of one left operand), flash-style over KEY TILES of
a few blocks each. The program visits, in ascending order, the key tiles that
hold a block some query of its tile chose (:func:`tile_visits`; none lies past
the tile's last position), two copies in flight, and masks per query: a query
attends the lanes up to its position of ITS blocks and nothing else. No score
of a row's length leaves the chip's fast memory.

The pool is the serving pool's leaf as stored, ``[L, B, S, KV x HD]``; nothing
is copied out of it but the chosen blocks (decode) or the visited key tiles
(prefill).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
# A prefill program's tile: queries of one row (x the group's G heads), and
# the lanes of keys one step copies and scores (whole blocks; fewer where a
# row's blocks do not divide). 512 lanes x all 16 heads at once was the fastest
# of nine variants on a v5e at the long-document cell's widths (PERF.md, PR 32).
_QUERY_TILE = 128
_KEY_LANES = 512
_STEP_HEADS = 16  # heads of the group whose scores one contraction holds (x the tile's queries: its rows)
_SLAB = 128  # block columns of the chosen map a program reads at a time (a register's lanes)

# Off the TPU the kernels can only be interpreted, orders of magnitude slower. A
# caller that means that says so here (the CPU's tests, the benchmark's
# rehearsal); a serving process that finds itself on another device is refused.
INTERPRET_OFF_TPU = False


def interpret_here() -> bool:
    """Whether the kernels must be interpreted on this process's devices."""
    platform = jax.devices()[0].platform
    if platform == "tpu":
        return False
    if not INTERPRET_OFF_TPU:
        raise RuntimeError(
            f"sparse_block_attn and sparse_chunk_attn are TPU kernels and this process runs on {platform!r}; "
            "set tpu_engine.ops.sparse_block_attention.INTERPRET_OFF_TPU = True to interpret them "
            "(tests and rehearsals only)")
    return True


def _kernel(ids_ref, pos_ref, at_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem,
            *, n: int, block: int, hd: int, scale: float):
    b, g = pl.program_id(0), pl.program_id(1)
    layer, pos = at_ref[0], pos_ref[b]
    first = (b * pl.num_programs(1) + g) * n

    def copies(j):
        lanes = pl.ds(ids_ref[first + j] * block, block)
        heads = pl.ds(g * hd, hd)
        return (pltpu.make_async_copy(k_hbm.at[layer, b, lanes, heads], k_buf.at[j], sem.at[0, j]),
                pltpu.make_async_copy(v_hbm.at[layer, b, lanes, heads], v_buf.at[j], sem.at[1, j]))

    def start(j, _):
        for c in copies(j):
            c.start()
        return 0

    lax.fori_loop(0, n, start, 0)
    q = q_ref[0, 0]                                                # [G, HD]
    G = q.shape[0]

    def fold(j, carry):
        m, l, acc = carry
        for c in copies(j):
            c.wait()
        s = lax.dot_general(q, k_buf[j], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale     # [G, block]
        lane = ids_ref[first + j] * block + lax.broadcasted_iota(jnp.int32, (G, block), 1)
        seen = lane <= pos
        m_new = jnp.maximum(m, jnp.max(jnp.where(seen, s, _NEG_INF), axis=-1, keepdims=True))
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        acc = alpha * acc + lax.dot_general(p.astype(v_buf.dtype), v_buf[j], (((1,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32)
        return m_new, alpha * l + jnp.sum(p, axis=-1, keepdims=True), acc

    m0 = jnp.full((G, 1), _NEG_INF, jnp.float32)
    _, l, acc = lax.fori_loop(0, n, fold, (m0, jnp.zeros((G, 1), jnp.float32),
                                           jnp.zeros((G, hd), jnp.float32)))
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)


def sparse_block_attend(q, k_pool, v_pool, ids, layer, pos, *, block: int, scale: float,
                        interpret: bool = False):
    """Attention of one query per row over chosen blocks of the pool.

    q [B, KV, G, HD]; k_pool, v_pool [L, B, S, KV x HD] (read where they lie);
    ids [B, KV, n] int32, the blocks (of ``block`` lanes) each row and kv-head
    attends; ``layer`` scalar int32; pos [B] int32, each row's position (a
    lane past it is masked; a row's own block must be among its ids, so that
    something is seen). Softmax in float32 over all lanes of all ``n`` blocks.
    Returns [B, KV, G, HD] in q's dtype.

    ``interpret=True`` is Pallas interpret mode (:func:`interpret_here`)."""
    B, KV, G, HD = q.shape
    n = ids.shape[-1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV),
        in_specs=[
            pl.BlockSpec((1, 1, G, HD), lambda b, g, *_: (b, g, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, G, HD), lambda b, g, *_: (b, g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n, block, HD), k_pool.dtype),
            pltpu.VMEM((n, block, HD), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, n)),
        ],
    )
    return pl.pallas_call(
        partial(_kernel, n=n, block=block, hd=HD, scale=scale),
        name="sparse_block_attn",  # the kernel's name in a profile
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(ids.reshape(-1).astype(jnp.int32), pos.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, k_pool, v_pool)


def tile_visits(chosen, tq: int, nb: int):
    """Which key tiles each tile of queries visits.

    chosen [B, KV, T, n_blocks] bool (what each query attends, no block past
    its own; T a multiple of ``tq``, n_blocks of ``nb``). A key tile is ``nb``
    blocks; a query tile visits it iff SOME query of the tile chose SOME block
    of it. Returns (tiles [B, KV, T/tq, n_blocks/nb] int32: the visited key
    tiles first, ascending; count [B, KV, T/tq] int32: how many those are)."""
    B, KV, T, n_blocks = chosen.shape
    visit = chosen.reshape(B, KV, T // tq, tq, n_blocks // nb, nb).any(axis=(3, 5))
    tiles = jnp.argsort(~visit, axis=-1, stable=True)
    return tiles.astype(jnp.int32), jnp.sum(visit, axis=-1, dtype=jnp.int32)


def _chunk_kernel(at_ref, count_ref, tiles_ref, q_ref, pos_ref, c_ref, k_hbm, v_hbm, o_ref,
                  k_buf, v_buf, sem, m_ref, l_ref, acc_ref,
                  *, nk: int, nb: int, block: int, hd: int, scale: float, heads: int):
    b, g, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    layer = at_ref[0]
    prog = (b * pl.num_programs(1) + g) * pl.num_programs(2) + i
    n = count_ref[prog]
    lanes = nb * block
    G, tq = q_ref.shape[2], q_ref.shape[3]

    def copies(j, slot):
        src = pl.ds(tiles_ref[prog * nk + j] * lanes, lanes)
        cols = pl.ds(g * hd, hd)
        return (pltpu.make_async_copy(k_hbm.at[layer, b, src, cols], k_buf.at[slot], sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, b, src, cols], v_buf.at[slot], sem.at[1, slot]))

    m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(n > 0)
    def _():
        for c in copies(0, 0):
            c.start()

    pos = pos_ref[0]                                               # [tq, 1]
    # sel[c, l]: lane l of a key tile lies in the tile's block c (before the slab's offset)
    col = lax.broadcasted_iota(jnp.int32, (_SLAB, lanes), 0)
    lane = lax.broadcasted_iota(jnp.int32, (_SLAB, lanes), 1)

    def step(j, _):
        slot = j % 2

        @pl.when(j + 1 < n)
        def _():
            for c in copies(j + 1, 1 - slot):
                c.start()

        tile = tiles_ref[prog * nk + j]
        # The tile's columns of the chosen map, spread over its lanes: a query
        # attends a lane iff it chose the lane's block and the lane is not
        # past it. One [tq, lanes] mask for all G heads.
        first = tile * nb
        at_col = (col - first % _SLAB) * block
        sel = ((lane >= at_col) & (lane < at_col + block)).astype(c_ref.dtype)
        chose = lax.dot_general(c_ref[0, 0, first // _SLAB], sel, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32) > 0.5
        seen = chose & (tile * lanes + lax.broadcasted_iota(jnp.int32, (tq, lanes), 1) <= pos)
        for c in copies(j, slot):
            c.wait()
        k, v = k_buf[slot], v_buf[slot]
        for h in range(0, G, heads):
            hs = slice(h, h + heads)
            q = q_ref[0, 0, hs].reshape(heads * tq, hd)
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            # A query that has seen nothing yet keeps m = _NEG_INF and sums
            # ones; its first seen lane's alpha = exp(_NEG_INF - m) = 0 wipes them.
            s = jnp.where(seen[None], s.reshape(heads, tq, lanes), _NEG_INF)
            m = m_ref[hs]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            pv = lax.dot_general(p.astype(v.dtype).reshape(heads * tq, lanes), v,
                                 (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            m_ref[hs] = m_new
            l_ref[hs] = alpha * l_ref[hs] + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[hs] = alpha * acc_ref[hs] + pv.reshape(heads, tq, hd)
        return 0

    lax.fori_loop(0, n, step, 0)
    o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def chunk_geometry(T: int, n_blocks: int, block: int) -> tuple[int, int]:
    """(queries a tile, blocks a key tile) for a chunk of T queries against a
    row of ``n_blocks`` blocks of ``block`` lanes."""
    tq = min(_QUERY_TILE, -(-T // 8) * 8)
    return tq, math.gcd(n_blocks, max(1, _KEY_LANES // block), _SLAB)


def sparse_chunk_attend(qg, k_pool, v_pool, chosen, layer, positions, *, block: int, scale: float,
                        interpret: bool = False):
    """Attention of a chunk of queries a row over the blocks each query chose.

    qg [B, T, KV, G, HD]; k_pool, v_pool [L, B, S, KV x HD] (read where they
    lie); chosen [B, KV, T, S / block] bool, the blocks each query and kv-head
    attends (none past the query's own; its own among them, so that something
    is seen); ``layer`` scalar int32; positions [B, T] int32 (a lane past a
    query's position is masked). Softmax in float32 over the lanes a query
    attends, probabilities in the values' dtype, float32 accumulation.
    Returns [B, T, KV, G, HD] in qg's dtype.

    ``interpret=True`` is Pallas interpret mode (:func:`interpret_here`)."""
    B, T, KV, G, HD = qg.shape
    n_blocks = k_pool.shape[2] // block
    tq, nb = chunk_geometry(T, n_blocks, block)
    pad = -T % tq
    nq, nk, slabs = (T + pad) // tq, n_blocks // nb, -(-n_blocks // _SLAB)
    q = jnp.pad(jnp.transpose(qg, (0, 2, 3, 1, 4)), ((0, 0),) * 3 + ((0, pad), (0, 0)))
    pos = jnp.pad(positions.astype(jnp.int32), ((0, 0), (0, pad)))[..., None]
    chosen = jnp.pad(chosen, ((0, 0), (0, 0), (0, pad), (0, 0)))   # a pad query chooses nothing
    tiles, count = tile_visits(chosen, tq, nb)
    # the chosen map by slabs of block columns: [B, KV, slabs, T, _SLAB]
    cmap = jnp.pad(chosen, ((0, 0),) * 3 + ((0, slabs * _SLAB - n_blocks),)).astype(jnp.bfloat16)
    cmap = jnp.moveaxis(cmap.reshape(B, KV, T + pad, slabs, _SLAB), 3, 2)
    heads = math.gcd(G, _STEP_HEADS)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV, nq),
        in_specs=[
            pl.BlockSpec((1, 1, G, tq, HD), lambda b, g, i, *_: (b, g, 0, i, 0)),
            pl.BlockSpec((1, tq, 1), lambda b, g, i, *_: (b, i, 0)),
            pl.BlockSpec((1, 1, slabs, tq, _SLAB), lambda b, g, i, *_: (b, g, 0, i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, G, tq, HD), lambda b, g, i, *_: (b, g, 0, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, nb * block, HD), k_pool.dtype),
            pltpu.VMEM((2, nb * block, HD), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((G, tq, 1), jnp.float32),       # running maximum
            pltpu.VMEM((G, tq, 1), jnp.float32),       # running sum
            pltpu.VMEM((G, tq, HD), jnp.float32),      # accumulator
        ],
    )
    out = pl.pallas_call(
        partial(_chunk_kernel, nk=nk, nb=nb, block=block, hd=HD, scale=scale, heads=heads),
        name="sparse_chunk_attn",  # the kernel's name in a profile (not the decode kernel's)
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), count.reshape(-1), tiles.reshape(-1),
      q, pos, cmap, k_pool, v_pool)
    return jnp.transpose(out, (0, 3, 1, 2, 4))[:, :T]
