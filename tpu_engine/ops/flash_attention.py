"""Attention entry point: the Pallas TPU flash kernel or plain XLA attention.

The caller chooses; nothing here substitutes one for the other. A shape
the kernel cannot tile raises :class:`FlashUnsupported`.

Layout convention: q [B, S, H, D], k/v [B, S, KV, D] (GQA when KV < H),
causal masking only (decoder-only LM).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _xla_mha(q, k, v, causal: bool = True, window: int = 0):
    B, S, H, D = q.shape
    KV = k.shape[2]
    if KV != H:
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    scale = 1.0 / (D ** 0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if causal:
        pos = jnp.arange(S)
        mask = pos[:, None] >= pos[None, :]
        if window:
            mask &= pos[:, None] - pos[None, :] < window
        scores = jnp.where(mask[None, None, :, :], scores, -1e9)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def mha(q, k, v, causal: bool = True, force_xla: bool = False, window: int = 0,
        interpret: bool = False):
    """Multi-head attention: the Pallas flash kernel, or XLA attention when
    ``force_xla``.

    ``window > 0`` is sliding-window (Mistral-style) attention: each query
    sees only the trailing ``window`` keys.

    ``interpret=True`` runs the kernel in Pallas interpret mode — for CPU
    meshes only, where it exercises the kernel's real custom_vjp wrapping.
    Callers derive it from the devices their arrays live on.
    """
    if window < 0:
        raise ValueError(f"window must be >= 0, got {window}")
    if window and not causal:
        raise ValueError("sliding-window attention requires causal=True")
    if force_xla:
        return _xla_mha(q, k, v, causal=causal, window=window)
    from tpu_engine.ops._flash_pallas import flash_mha

    return flash_mha(q, k, v, causal=causal, window=window, interpret=interpret)
