"""Decode attention over CHOSEN blocks of a KV pool: a Pallas TPU kernel.

A block-sparse attention layer's decode step reads, for every row and
kv-head, a handful of blocks of the row's lanes (``generate._select_blocks``
says which) and nothing else of the row. XLA lowers such a gather of
``[block, HD]`` slices to a sequential loop, one slice an iteration; here the
block table is prefetched to scalar memory, each program (one row, one
kv-head) starts the DMAs of all its blocks from the pool where it lies in HBM
— ``k_pool[layer, row, id x block : (id + 1) x block, g x HD : (g + 1) x HD]`` —
and folds each block into a running softmax as it lands.

The pool is the serving pool's leaf as stored, ``[L, B, S, KV x HD]``; nothing
is copied out of it but the chosen blocks.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

# Off the TPU the kernel can only be interpreted, orders of magnitude slower. A
# caller that means that says so here (the CPU's tests, the benchmark's
# rehearsal); a serving process that finds itself on another device is refused.
INTERPRET_OFF_TPU = False


def interpret_here() -> bool:
    """Whether the kernel must be interpreted on this process's devices."""
    platform = jax.devices()[0].platform
    if platform == "tpu":
        return False
    if not INTERPRET_OFF_TPU:
        raise RuntimeError(
            f"sparse_block_attn is a TPU kernel and this process runs on {platform!r}; set "
            "tpu_engine.ops.sparse_block_attention.INTERPRET_OFF_TPU = True to interpret it "
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
