"""First-party Pallas TPU flash attention (causal), with a memory-bounded
blockwise backward pass.

Forward: grid (batch·head, Q-block, K-block) with the K dimension innermost;
each program sees one [BLOCK_Q, D] query tile and one [BLOCK_K, D] key/value
tile (never the whole sequence), and online-softmax state (m/l/acc) lives in
VMEM scratch that persists across the K iterations. Peak VMEM is
O(BLOCK_Q · D + BLOCK_K · D + BLOCK_Q · BLOCK_K) regardless of sequence
length — the S×S score matrix is never materialised, and neither is a full
[S, D] K/V copy (``_xla_mha`` materialises S×S).

Backward: custom_vjp over two Pallas kernels. The forward saves the
log-sum-exp rows; the backward reconstructs attention probabilities
block-by-block from (q, k, lse) and never materialises anything larger
than a [BLOCK, BLOCK] tile. A dQ kernel iterates K-blocks innermost
(accumulating dq in VMEM scratch) and a dK/dV kernel iterates Q-blocks
innermost — both skip the causally-masked block pairs entirely (compute
*and* DMA), so the backward does half the work of a dense S×S pass.

Layout: q/k/v [B, S, H, D] (GQA expanded by the caller, ``flash_attention.mha``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


class FlashUnsupported(ValueError):
    """Raised (at trace time) when a shape/config can't use the flash kernel."""


# Backward tile cap for LONG sequences (see _flash_bwd); module-level so
# a probe can sweep it. Swept on a v5e in round 4 (pre-ledger, through a
# runtime since replaced; no ledger line bears it): at seq >= 4096 the 1024 tile beats the old blanket 512
# cap (fwd+bwd 4.87->4.64 ms @ seq4096, 14.57->14.44 @ 8192, 12.39->
# 12.31 windowed — the 4-tile f32 working set is 16 MiB, inside v5e
# VMEM), but at seq 2048 the bigger tile LOSES 8.7% (1.80->1.96 ms — a
# 2x2 outer grid leaves the pipeline too few blocks), so short
# sequences keep 512.
_BWD_BLOCK_CAP = 1024


def _pick_block(s: int) -> int:
    """Forward tile for sequence length ``s``; 0 = no tiling exists.

    A tile is a multiple of 128 or the whole sequence: the lse rows leave
    the kernel as ``(1, 1, block)`` blocks, and Mosaic requires a block's
    last dimension to be 128-divisible or equal to the array's (a 64-wide
    tile of a 128-token sequence is refused on the chip — PR 21)."""
    for b in (1024, 512, 256, 128):
        if s % b == 0 and s // b >= 2:
            return b
    if s in (64, 128):
        return s
    return 0


def tiling_obstacle(seq_len: int) -> str | None:
    """Why the kernel cannot tile ``seq_len`` (None = it can). The one
    predicate shared by :func:`flash_mha` and the train program's
    attention resolution, so "flash" is only ever reported for a shape the
    kernel accepts."""
    if _pick_block(seq_len) == 0:
        return (
            f"no flash tiling for seq_len={seq_len} (needs 64 or a multiple "
            "of 128)"
        )
    return None


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _visible(q_pos, k_pos, window: int):
    """The causal (optionally sliding-window) visibility predicate."""
    vis = q_pos >= k_pos
    if window:
        vis &= q_pos - k_pos < window
    return vis


def _lo_block(q_idx, block: int, window: int):
    """Lowest K-block index visible to Q-block ``q_idx`` under ``window``
    (floor division handles the negative early-sequence case)."""
    return (q_idx * block - (window - 1)) // block


def _n_kv_blocks(n_blk: int, block: int, window: int) -> int:
    """Inner-grid length for Q-major (fwd / dQ) kernels: with a window only
    ceil((W-1)/block)+1 K-blocks can be visible to any Q-block, so the grid
    itself shrinks — windowed cost is O(S·W) in *programs*, not just in
    skipped compute."""
    if not window:
        return n_blk
    return min(n_blk, (window + block - 2) // block + 1)


def _n_q_blocks(n_blk: int, block: int, window: int) -> int:
    """Inner-grid length for the K-major (dK/dV) kernel: at most
    (block+W-2)//block + 1 Q-blocks can see any K-block."""
    if not window:
        return n_blk
    return min(n_blk, (block + window - 2) // block + 1)


def _k_index(q_idx, j, block: int, window: int):
    """Map the inner grid coordinate ``j`` to an actual K-block index. With
    a window the inner grid is shortened and offset to start at the lowest
    visible block; without one it is the K-block index itself."""
    if not window:
        return j
    return jnp.maximum(_lo_block(q_idx, block, window), 0) + j


_LOG2E = 1.4426950408889634
# Running-max floor, in base-2 logit units. Any REAL logit sits far above
# it, and a fully-masked row (all scores _NEG_INF) clamps here, pushing
# every exp2(s2 - m) to exactly 0.0 (fp32 flushes below 2^-149) — which is
# what makes the masked-probability select unnecessary (see _fwd_tile).
_M2_FLOOR = -1e6


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                q_scr, *, block_q: int, block_k: int, scale: float,
                window: int, causal: bool = True):
    q_idx = pl.program_id(1)
    j = pl.program_id(2)
    n_j = pl.num_programs(2)
    k_idx = _k_index(q_idx, j, block_q, window) if causal else j

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        # Fold scale·log2(e) into the Q tile ONCE per (bh, q_block): the
        # kernel then works entirely in base-2 logit units — jnp.exp2
        # instead of exp, and no [BQ, BK]-wide scale multiply per K tile.
        q_scr[...] = (
            q_ref[0].astype(jnp.float32) * (scale * _LOG2E)
        ).astype(q_scr.dtype)

    # Causal with BLOCK_Q == BLOCK_K: only K blocks with k_idx <= q_idx
    # contribute; the rest are skipped entirely. (The windowed lower bound
    # is built into the grid offset — k_idx never starts below it.)
    # Non-causal (ring attention's fully-visible hops): every block is
    # active and no visibility mask is computed at all.
    active = (k_idx <= q_idx) if causal else (j >= 0)

    def _tile(masked: bool):
        """One K-block of online softmax, in base-2 units.

        ``masked=False`` skips the visibility iota/compare/select entirely
        — correct for every tile strictly inside the visible band, which
        is MOST tiles at long sequence (the diagonal tile always masks;
        with a window, so do the tiles straddling its lower edge)."""
        q2 = q_scr[...]                         # [BQ, D] pre-scaled
        k_blk = k_ref[0]                        # [BK, D]
        v_blk = v_ref[0]                        # [BK, D]
        # bf16 operands, fp32 accumulation: the MXU's native contract.
        s2 = jax.lax.dot_general(
            q2, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [BQ, BK] base-2 logits
        if masked:
            q_pos = q_idx * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_idx * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s2 = jnp.where(_visible(q_pos, k_pos, window), s2, _NEG_INF)
        m = m_scr[...]
        # The _M2_FLOOR clamp replaces the old masked-p select: masked
        # entries hold -1e30, so exp2(-1e30 - floor) underflows to 0.0
        # without a [BQ, BK] where().
        m_new = jnp.maximum(jnp.maximum(m, jnp.max(s2, axis=-1)), _M2_FLOOR)
        p = jnp.exp2(s2 - m_new[:, None])
        corr = jnp.exp2(m - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1)
        # p rounds to the storage dtype for the second MXU dot (standard
        # flash practice); l/m/acc stay fp32 so the normalisation is exact.
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # A tile needs the visibility mask iff it touches the causal
        # diagonal or the window's lower edge; interior tiles are fully
        # visible and skip the iota/compare/select. (window is static:
        # without one this reduces to k_idx == q_idx.)
        needs_mask = k_idx == q_idx
        if window:
            needs_mask |= (q_idx - k_idx + 1) * block_q - 1 >= window

        @pl.when(active & needs_mask)
        def _compute_masked():
            _tile(True)

        @pl.when(active & jnp.logical_not(needs_mask))
        def _compute_interior():
            _tile(False)
    else:
        @pl.when(active)
        def _compute():
            _tile(False)

    @pl.when(j == n_j - 1)
    def _finalize():
        l_safe = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l_safe[:, None]).astype(o_ref.dtype)
        # lse leaves the kernel in NATURAL-log units (ring-attention merges
        # and the backward recompute consume it): m2/log2(e) + ln(l).
        lse_ref[0, 0] = m_scr[...] * (1.0 / _LOG2E) + jnp.log(l_safe)


def _kv_clamp(block: int, window: int, causal: bool = True):
    """Index map for K/V blocks in Q-major grids: map the inner coordinate
    to the actual K-block, clamped into the active range so causally-masked
    iterations repeat an index the pipeline has already fetched — no
    bandwidth is spent on blocks the kernel won't read. Non-causal grids
    visit every block, so the coordinate maps straight through."""
    if not causal:
        return lambda bh, i, j: (bh, j, 0)
    return lambda bh, i, j: (bh, jnp.minimum(_k_index(i, j, block, window), i), 0)


def _flash_fwd(q, k, v, block: int, interpret: bool, window: int,
               causal: bool = True):
    """q/k/v: [BH, S, D] → (o [BH, S, D], lse [BH, S])."""
    BH, S, D = q.shape
    n_blk = S // block
    scale = 1.0 / (D ** 0.5)
    # Inner dim = K blocks (sequential); with a window it is shortened to
    # the max number of visible K-blocks per Q-block.
    grid = (BH, n_blk, _n_kv_blocks(n_blk, block, window) if causal else n_blk)
    kernel = partial(_fwd_kernel, block_q=block, block_k=block, scale=scale,
                     window=window, causal=causal)
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",  # the kernel's name in a profile
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block, D), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block, D), _kv_clamp(block, window, causal)),
            pl.BlockSpec((1, block, D), _kv_clamp(block, window, causal)),
        ],
        out_specs=[
            pl.BlockSpec((1, block, D), lambda bh, i, j: (bh, i, 0)),
            # lse as [BH, 1, S]: TPU block tiling needs the last two block
            # dims (1, block) to be (equal-to-array, 128-divisible).
            pl.BlockSpec((1, 1, block), lambda bh, i, j: (bh, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, S), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block,), jnp.float32),      # running max m (base-2)
            pltpu.VMEM((block,), jnp.float32),      # running sum l
            pltpu.VMEM((block, D), jnp.float32),    # output accumulator
            pltpu.VMEM((block, D), q.dtype),        # scale·log2e-folded Q
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return o, lse.reshape(BH, S)


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------


def _recompute_p(q, k, lse_row, q_idx, k_idx, block_q, block_k, scale, window,
                 causal=True):
    """Rebuild one [BQ, BK] tile of attention probabilities from saved lse."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if not causal:
        return jnp.exp(s - lse_row[:, None])
    q_pos = q_idx * block_q + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = k_idx * block_k + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = _visible(q_pos, k_pos, window)
    return jnp.where(mask, jnp.exp(s - lse_row[:, None]), 0.0)


def _p_ds_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               q_idx, k_idx, block_q, block_k, scale, window, causal=True):
    """Shared gradient-tile math for both backward kernels: load the four
    blocks and return (p, ds, q, k, do) — ds = p ∘ (dO·Vᵀ − Δ) · scale.

    Blocks stay in their storage dtype (bf16) so every dot feeds the MXU
    its native input width; products/softmax math accumulate in fp32 via
    ``preferred_element_type``. ``p``/``ds`` are returned fp32 — callers
    round them to the storage dtype at their own MXU dots."""
    q = q_ref[0]                                # [BQ, D] storage dtype
    k_blk = k_ref[0]                            # [BK, D]
    v_blk = v_ref[0]                            # [BK, D]
    do = do_ref[0]                              # [BQ, D]
    p = _recompute_p(q, k_blk, lse_ref[0, 0], q_idx, k_idx,
                     block_q, block_k, scale, window, causal)
    dp = jax.lax.dot_general(
        do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                           # [BQ, BK] fp32
    ds = p * (dp - delta_ref[0, 0][:, None]) * scale
    return p, ds, q, k_blk, do


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, block_q: int, block_k: int, scale: float,
                   window: int, causal: bool = True):
    q_idx = pl.program_id(1)
    j = pl.program_id(2)
    n_j = pl.num_programs(2)
    k_idx = _k_index(q_idx, j, block_q, window) if causal else j

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when((k_idx <= q_idx) if causal else (j >= 0))
    def _compute():
        _, ds, _, k_blk, _ = _p_ds_tile(q_ref, k_ref, v_ref, do_ref,
                                        lse_ref, delta_ref, q_idx, k_idx,
                                        block_q, block_k, scale, window,
                                        causal)
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == n_j - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _q_index(k_idx, j, window: int):
    """Inner grid coordinate → actual Q-block index for the K-major kernel:
    with a window the grid starts at the diagonal (lowest visible Q-block
    is the K-block itself)."""
    return k_idx + j if window else j


def _bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *,
                    block_q: int, block_k: int, scale: float, window: int,
                    n_blk: int, causal: bool = True):
    k_idx = pl.program_id(1)
    j = pl.program_id(2)
    n_j = pl.num_programs(2)
    q_idx = _q_index(k_idx, j, window) if causal else j

    @pl.when(j == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    if causal:
        active = q_idx >= k_idx
        if window:
            active &= q_idx < n_blk  # offset grid can run past the last Q-block
    else:
        active = j >= 0

    @pl.when(active)
    def _compute():
        p, ds, q, _, do = _p_ds_tile(q_ref, k_ref, v_ref, do_ref,
                                     lse_ref, delta_ref, q_idx, k_idx,
                                     block_q, block_k, scale, window, causal)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                           # [BK, D]
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == n_j - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd(block: int, interpret: bool, window: int, res, do,
               causal: bool = True, dlse=None):
    """dq/dk/dv from the output cotangent ``do`` and, optionally, an LSE
    cotangent ``dlse`` [BH, S] (ring attention's hop merge differentiates
    through the returned lse). The kernels need no change for it: with
    cotangents (dO, dlse), the score gradient is
    ds = p ∘ (dO·Vᵀ − Δ + dlse), i.e. exactly the standard form with
    Δ' = rowsum(dO ∘ O) − dlse substituted for Δ."""
    q, k, v, o, lse = res  # q/k/v/o: [BH, S, D]; lse: [BH, S]
    BH, S, D = q.shape
    scale = 1.0 / (D ** 0.5)
    # The backward holds ~4 [BQ, BK] f32 tiles live at once (s/p, dp, ds)
    # plus four input blocks and two accumulators. Tile choice is
    # sequence-dependent (swept on chip, see _BWD_BLOCK_CAP): long
    # sequences take the big tile, short ones keep enough outer-grid
    # blocks to fill the pipeline.
    bb = min(block, _BWD_BLOCK_CAP if S >= 4096 else 512)
    # Power-of-two floor: ``block`` is a power of two dividing S, so any
    # power of two <= block divides S too. A swept/overridden cap that is
    # not a power of two (e.g. --bwd-block 768) would otherwise truncate
    # the grid and leave tail rows of dq/dk/dv unwritten.
    bb = 1 << (bb.bit_length() - 1)
    n_blk = S // bb

    do32 = do.astype(jnp.float32)
    # D_i = rowsum(dO ∘ O) — the softmax-jacobian diagonal term.
    delta = jnp.sum(do32 * o.astype(jnp.float32), axis=-1)  # [BH, S]
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    lse3 = lse.reshape(BH, 1, S)
    delta3 = delta.reshape(BH, 1, S)

    qkv_spec = pl.BlockSpec((1, bb, D), lambda bh, i, j: (bh, i, 0))
    row_spec = pl.BlockSpec((1, 1, bb), lambda bh, i, j: (bh, 0, i))

    # The clamped index maps below pin the moving operand's index on
    # causally- or window-skipped iterations to a block already fetched,
    # so the pipeline elides the DMA.
    dq = pl.pallas_call(
        partial(_bwd_dq_kernel, block_q=bb, block_k=bb, scale=scale,
                window=window, causal=causal),
        name="flash_bwd_dq",
        # (bh, q-block, k-block innermost) — inner dim shortened by a window
        grid=(BH, n_blk, _n_kv_blocks(n_blk, bb, window) if causal else n_blk),
        in_specs=[
            qkv_spec,  # q
            pl.BlockSpec((1, bb, D), _kv_clamp(bb, window, causal)),  # k
            pl.BlockSpec((1, bb, D), _kv_clamp(bb, window, causal)),  # v
            qkv_spec,  # do
            row_spec,  # lse
            row_spec,  # delta
        ],
        out_specs=qkv_spec,
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bb, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, do, lse3, delta3)

    if not causal:
        def _q_blk(i, j):
            return j
    elif window:
        # Offset inner grid: q-block = i + j, clamped to the last real block
        # for the tail iterations past the end of the sequence.
        def _q_blk(i, j):
            return jnp.minimum(_q_index(i, j, window), n_blk - 1)
    else:
        def _q_blk(i, j):
            return jnp.maximum(i, j)

    moving = pl.BlockSpec((1, bb, D), lambda bh, i, j: (bh, _q_blk(i, j), 0))
    moving_row = pl.BlockSpec((1, 1, bb), lambda bh, i, j: (bh, 0, _q_blk(i, j)))
    dk, dv = pl.pallas_call(
        partial(_bwd_dkv_kernel, block_q=bb, block_k=bb, scale=scale,
                window=window, n_blk=n_blk, causal=causal),
        name="flash_bwd_dkv",
        # (bh, k-block, q-block innermost) — inner dim shortened by a window
        grid=(BH, n_blk, _n_q_blocks(n_blk, bb, window) if causal else n_blk),
        in_specs=[
            qkv_spec,    # k
            qkv_spec,    # v
            moving,      # q
            moving,      # do
            moving_row,  # lse
            moving_row,  # delta
        ],
        out_specs=[qkv_spec, qkv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), k.dtype),
            jax.ShapeDtypeStruct((BH, S, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bb, D), jnp.float32),
            pltpu.VMEM((bb, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(k, v, q, do, lse3, delta3)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public entry (custom_vjp over [BH, S, D] layout)
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_bhsd(q, k, v, block: int, interpret: bool, window: int):
    o, _ = _flash_fwd(q, k, v, block, interpret, window)
    return o


def _flash_bhsd_fwd(q, k, v, block, interpret, window):
    o, lse = _flash_fwd(q, k, v, block, interpret, window)
    return o, (q, k, v, o, lse)


def _flash_bhsd_bwd(block, interpret, window, res, do):
    return _flash_bwd(block, interpret, window, res, do)


_flash_bhsd.defvjp(_flash_bhsd_fwd, _flash_bhsd_bwd)


# ---------------------------------------------------------------------------
# (o, lse) entry for ring attention's per-hop blocks
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_fwd_lse(q, k, v, block: int, interpret: bool, causal: bool):
    """Flash attention on [BH, S, D] returning ``(o, lse)`` — the entry ring
    attention calls per K/V hop. ``lse`` is differentiable: its cotangent
    from the hop merge folds into the standard backward via the Δ' trick
    (see :func:`_flash_bwd`). ``causal=False`` runs the unmasked kernels
    (a ring hop strictly in the past is fully visible)."""
    return _flash_fwd(q, k, v, block, interpret, 0, causal=causal)


def _flash_fwd_lse_fwd(q, k, v, block, interpret, causal):
    o, lse = _flash_fwd(q, k, v, block, interpret, 0, causal=causal)
    return (o, lse), (q, k, v, o, lse)


def _flash_fwd_lse_bwd(block, interpret, causal, res, cts):
    do, dlse = cts
    return _flash_bwd(block, interpret, 0, res, do, causal=causal, dlse=dlse)


flash_fwd_lse.defvjp(_flash_fwd_lse_fwd, _flash_fwd_lse_bwd)


def flash_mha(q, k, v, causal: bool = True, interpret: bool = False,
              window: int = 0):
    """Flash attention on [B, S, H, D]; returns [B, S, H, D].

    ``window > 0`` restricts each query to the trailing ``window`` keys
    (sliding-window attention, Mistral-style): block pairs wholly outside
    the window are skipped — compute and DMA — so cost is O(S·W), not O(S²).

    ``interpret=True`` is Pallas interpret mode, for CPU meshes only; the
    default compiles the Mosaic kernel and fails on a non-TPU backend.

    Raises :class:`FlashUnsupported` (at trace time) when the shape doesn't
    tile or attention is non-causal.
    """
    B, S, H, D = q.shape
    KV = k.shape[2]
    obstacle = tiling_obstacle(S)
    if obstacle is not None:
        raise FlashUnsupported(obstacle)
    if not causal:
        raise FlashUnsupported("the flash kernel entry is causal-only")
    block = _pick_block(S)
    if window < 0:
        raise ValueError(f"window must be >= 0, got {window}")
    if window >= S:
        window = 0  # a window covering the whole sequence is plain causal
    if KV != H:
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)

    def to_bhsd(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)

    o = _flash_bhsd(to_bhsd(q), to_bhsd(k), to_bhsd(v), block, interpret, window)
    return o.reshape(B, H, S, D).transpose(0, 2, 1, 3)
