"""Ulysses attention: all-to-all sequence parallelism over the ``sequence`` axis.

The second first-class long-context strategy next to ring attention
(``tpu_engine/parallel/ring_attention.py``) — both are absent from the
reference entirely (SURVEY.md §5: "no ring attention, context parallel,
blockwise attention, or Ulysses anywhere").

Where ring attention keeps the sequence sharded and rotates K/V blocks hop
by hop, the all-to-all (DeepSpeed-Ulysses-style) formulation swaps the
sharded dimension for the duration of attention:

    [B, S/P, H, D]  --all_to_all-->  [B, S, H/P, D]
        (sequence-sharded)              (head-sharded)

Each device then runs ordinary *full-sequence* causal attention over its
head group — reusing the Pallas flash kernel unchanged — and a second
all-to-all swaps back. Two all-to-alls per layer ride ICI, versus ring's
P-1 ppermute hops; Ulysses wins when the head count is large relative to
the sequence axis (attention arithmetic is done at full MXU tile sizes),
ring wins when S is so long that even one head's full-sequence scores
overflow VMEM/HBM.

Layout convention matches ``tpu_engine.ops``: q [B, S, H, D], k/v
[B, S, KV, D] (GQA allowed). Differentiable end-to-end: ``lax.all_to_all``
is linear, so reverse-mode AD transposes it to the opposite swap.
"""

from __future__ import annotations

from functools import partial

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tpu_engine.mesh_runtime import BATCH_AXES
from tpu_engine.ops import flash_attention
from tpu_engine.ops._flash_pallas import tiling_obstacle


def _ulysses_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool,
    interpret: bool,
) -> jax.Array:
    """Per-shard body (runs inside shard_map).

    q: [B, Sq_local, H, D]; k/v: [B, Sk_local, KV, D]. Returns the local
    output shard [B, Sq_local, H, D].
    """
    P_sz = lax.psum(1, axis_name)
    H, KV = q.shape[2], k.shape[2]
    if H % P_sz != 0:
        raise ValueError(
            f"ulysses attention needs local head count {H} divisible by the "
            f"sequence axis size {P_sz}"
        )
    if KV % P_sz != 0:  # GQA with too few KV heads: expand before the swap
        k = jax.numpy.repeat(k, H // KV, axis=2)
        v = jax.numpy.repeat(v, H // KV, axis=2)

    # Swap shards: sequence-sharded → head-sharded (full sequence local).
    a2a = partial(lax.all_to_all, axis_name=axis_name, tiled=True)
    q = a2a(q, split_axis=2, concat_axis=1)
    k = a2a(k, split_axis=2, concat_axis=1)
    v = a2a(v, split_axis=2, concat_axis=1)

    # Full-sequence attention over this device's head group: the flash
    # kernel where the (static) sequence length tiles, XLA attention for
    # the rest — chosen from the shape, like ring attention's per-hop body.
    out = flash_attention.mha(
        q, k, v, causal=causal, interpret=interpret,
        force_xla=not causal or tiling_obstacle(q.shape[1]) is not None,
    )

    # Swap back: head-sharded → sequence-sharded.
    return a2a(out, split_axis=1, concat_axis=2)


def ulysses_mha(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    causal: bool = True,
    axis_name: str = "sequence",
) -> jax.Array:
    """Sequence-parallel attention via head↔sequence all-to-all.

    Call with *global* [B, S, H, D] arrays from inside (or outside) jit; the
    shard_map distributes batch over (data, fsdp), sequence over
    ``axis_name``, heads over ``model``. The per-device head count (after
    any tensor-parallel split) must be divisible by the sequence axis size.
    """
    # Off-TPU (CPU dry-run/test meshes) the kernel runs in interpret mode so
    # the same custom_vjp wrapping that ships on TPU is what gets exercised
    # — not XLA attention's different backward graph.
    on_tpu = mesh.devices.flat[0].platform == "tpu"
    spec = P(BATCH_AXES, axis_name, "model", None)
    # check_vma off: the checker cannot verify a fully sharded output
    # through a Pallas call.
    f = jax.shard_map(
        partial(
            _ulysses_local,
            axis_name=axis_name,
            causal=causal,
            interpret=not on_tpu,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return f(q, k, v)
