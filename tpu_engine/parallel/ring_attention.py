"""Ring attention: sequence/context parallelism over the ``sequence`` mesh axis.

Long-context capability absent from the reference entirely (SURVEY.md §5:
"no ring attention, context parallel, blockwise attention, or Ulysses
anywhere"; sequence length is not even a config field). First-class here:

Each device holds a shard of the sequence. Q stays put; K/V shards rotate
around the ring via ``lax.ppermute`` while every device accumulates its
queries' attention over each visiting K/V block with an online
(flash-style) log-sum-exp update. After ``ring_size`` hops every Q block has
attended to every K/V block — peak memory is O(S_local²·ring) score blocks
instead of O(S²), and the ring hops ride neighbouring ICI links.

Differentiable end-to-end: the loop is a ``lax.scan`` (reverse-mode safe)
and ``ppermute`` transposes to the reverse rotation.

Layout convention matches ``tpu_engine.ops``: q/k/v are [B, S, H, D]
(GQA allowed: KV heads < Q heads).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tpu_engine.mesh_runtime import BATCH_AXES
from tpu_engine.ops._flash_pallas import _pick_block, flash_fwd_lse

_NEG_INF = -1e30


def _ring_flash_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool,
    interpret: bool,
    block: int,
) -> jax.Array:
    """Flash-kernel ring body: each hop's K/V block goes through the Pallas
    kernel (``flash_fwd_lse``), and hops merge via their log-sum-exps —
    no [Sq, Sk] score tensor is ever materialised, per hop or in total.

    Hop cases under causality (kv_idx = global block index held this hop):
    strictly-future blocks are SKIPPED entirely (``lax.switch`` runs one
    branch — no wasted kernel launch), the diagonal block runs the causal
    kernel, and strictly-past blocks run the unmasked kernel. The merge
    differentiates end-to-end: the kernel's lse output is a custom_vjp
    primal whose cotangent folds into the standard backward
    (``_flash_bwd``'s Δ' substitution).
    """
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    ring = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)

    def to_bhsd(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)

    def expand_kv(x):
        # GQA: the ring rotates COMPACT [B, Sk, KV, D] blocks (KV/H of the
        # inter-chip bytes); heads expand per hop, just before the kernel.
        if KV != H:
            x = jnp.repeat(x, H // KV, axis=2)
        return to_bhsd(x)

    qb = to_bhsd(q)
    BH = B * H

    m0 = jnp.full((BH, Sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((BH, Sq), jnp.float32)
    o0 = jnp.zeros((BH, Sq, D), jnp.float32)

    perm = [(j, (j + 1) % ring) for j in range(ring)]

    def skip(qb, kb, vb):
        return (jnp.zeros((BH, Sq, D), qb.dtype),
                jnp.full((BH, Sq), -jnp.inf, jnp.float32))

    def diag(qb, kb, vb):
        return flash_fwd_lse(qb, kb, vb, block, interpret, True)

    def full_blk(qb, kb, vb):
        return flash_fwd_lse(qb, kb, vb, block, interpret, False)

    def attend(m, l, o, k_blk, v_blk, i):
        kv_idx = (my_idx - i) % ring
        kb, vb = expand_kv(k_blk), expand_kv(v_blk)
        if causal:
            case = jnp.where(kv_idx > my_idx, 0,
                             jnp.where(kv_idx == my_idx, 1, 2))
            o_i, lse_i = lax.switch(case, (skip, diag, full_blk), qb, kb, vb)
        else:
            o_i, lse_i = full_blk(qb, kb, vb)
        # LSE merge: out = Σ_i exp(lse_i)·o_i / Σ_i exp(lse_i), online with
        # a running max. Skipped hops carry lse = -inf and contribute 0
        # (guarded — exp(-inf - -inf) would be NaN before any real hop).
        m_new = jnp.maximum(m, lse_i)
        c_old = jnp.where(jnp.isfinite(m), jnp.exp(m - m_new), 0.0)
        c_new = jnp.where(jnp.isfinite(lse_i), jnp.exp(lse_i - m_new), 0.0)
        l = l * c_old + c_new
        o = o * c_old[..., None] + o_i.astype(jnp.float32) * c_new[..., None]
        return m_new, l, o

    def hop(carry, i):
        m, l, o, k_blk, v_blk = carry
        m, l, o = attend(m, l, o, k_blk, v_blk, i)
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return (m, l, o, k_next, v_next), None

    (m, l, o, k_last, v_last), _ = lax.scan(
        hop, (m0, l0, o0, k, v), jnp.arange(ring - 1)
    )
    m, l, o = attend(m, l, o, k_last, v_last, ring - 1)

    out = o / jnp.maximum(l, 1e-30)[..., None]          # [BH, Sq, D]
    return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3).astype(q.dtype)


def _ring_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = True,
    interpret: bool = False,
    use_flash: bool = True,
) -> jax.Array:
    """Per-shard ring attention body (runs inside shard_map).

    q: [B, Sq, H, D] local query shard; k/v: [B, Sk, KV, D] local shards.
    Returns [B, Sq, H, D]. Tileable shards route per-hop blocks through the
    Pallas flash kernel (``_ring_flash_local``); anything else falls back
    to the dense einsum body below.
    """
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]

    block = _pick_block(Sq) if use_flash else 0
    if block and Sq >= 64 and Sk == Sq:
        # Kernel path rotates COMPACT GQA K/V and expands per hop.
        return _ring_flash_local(q, k, v, axis_name, causal, interpret, block)

    if KV != H:  # dense fallback: expand so every hop is one einsum
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)

    ring = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    scale = 1.0 / (D ** 0.5)

    q32 = q.astype(jnp.float32)
    q_pos = my_idx * Sq + jnp.arange(Sq)  # global query positions

    # Online-softmax accumulators (fp32).
    m0 = jnp.full((B, H, Sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    o0 = jnp.zeros((B, H, Sq, D), jnp.float32)

    perm = [(j, (j + 1) % ring) for j in range(ring)]

    def attend(m, l, o, k_blk, v_blk, i):
        """Online-softmax update of (m, l, o) with the K/V block held at hop i."""
        kv_idx = (my_idx - i) % ring  # which global block we hold this hop
        s = jnp.einsum("bqhd,bkhd->bhqk", q32, k_blk.astype(jnp.float32)) * scale
        if causal:
            k_pos = kv_idx * Sk + jnp.arange(Sk)
            mask = q_pos[:, None] >= k_pos[None, :]  # [Sq, Sk]
            s = jnp.where(mask[None, None, :, :], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        # Rows that have seen no valid key yet: m_new == _NEG_INF → p ≈ e^0 = 1
        # for masked entries; zero them explicitly.
        p = jnp.where(s <= _NEG_INF / 2, 0.0, p)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        o = o * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32)
        )
        return m_new, l, o

    def hop(carry, i):
        m, l, o, k_blk, v_blk = carry
        m, l, o = attend(m, l, o, k_blk, v_blk, i)
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return (m, l, o, k_next, v_next), None

    # ring-1 hops rotate K/V after attending; the final block is consumed
    # outside the scan so no wasted ppermute pair is issued on the last hop.
    (m, l, o, k_last, v_last), _ = lax.scan(
        hop, (m0, l0, o0, k, v), jnp.arange(ring - 1)
    )
    m, l, o = attend(m, l, o, k_last, v_last, ring - 1)

    out = o / jnp.maximum(l, 1e-30)[..., None]  # [B, H, Sq, D]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ring_mha(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    causal: bool = True,
    axis_name: str = "sequence",
) -> jax.Array:
    """Sequence-parallel attention over ``mesh``'s ``sequence`` axis.

    Call with *global* [B, S, H, D] arrays from inside (or outside) jit; the
    shard_map distributes: batch over (data, fsdp), sequence over
    ``sequence``, heads over ``model``.
    """
    # Off-TPU (CPU dry-run/test meshes) the kernel runs in interpret mode —
    # same custom_vjp wrapping as the TPU build (cf. ulysses/flash paths).
    interpret = mesh.devices.flat[0].platform != "tpu"
    spec = P(BATCH_AXES, axis_name, "model", None)
    # check_vma off: the checker cannot verify a fully sharded output
    # through a Pallas call.
    f = jax.shard_map(
        partial(_ring_attention_local, axis_name=axis_name, causal=causal,
                interpret=interpret),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return f(q, k, v)
