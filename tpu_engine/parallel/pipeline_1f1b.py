"""1F1B (one-forward-one-backward) pipeline schedule with manual stage vjp.

GPipe-by-autodiff (``pipeline.py``) forwards every microbatch and lets
autodiff replay the reverse pipeline: simple, but the scan saves a stage
boundary buffer per tick — activation residency O((M + P) · P · B·S·D).
1F1B interleaves: in steady state every stage performs exactly one forward
and one backward per tick, and a microbatch's backward starts as soon as
its forward leaves the last stage, so at most ``2(P-1)+1`` stage inputs are
ever in flight per stage — residency O(P²·B·S·D), independent of the
microbatch count M. That is the schedule's classic value (Narayanan et al.,
PipeDream-Flush / Megatron-LM): grow M to amortise the (P-1)/M bubble
without activation blowup. Bubble TIME is the same as GPipe's — in the
masked-SPMD formulation warmup/drain lanes still burn compute — so 1F1B
here is the memory lever, measured as such (benchmarks/RESULTS.md
§Pipeline: AOT memory, no chip run).

Implementation notes:

- One ``lax.scan`` over ``M + 2(P-1)`` ticks; stages run under
  ``jax.vmap(..., spmd_axis_name="pipe")`` (the same trick that lets the
  Pallas flash kernel's shard_map nest under the stage vmap).
- No autodiff across the schedule: each tick recomputes the stage forward
  from its saved INPUT via ``jax.vjp`` (full per-stage rematerialisation —
  the standard 1F1B memory/compute trade, and exactly what
  ``activation_checkpointing`` means on the non-pipelined path).
- The per-microbatch exit loss and its cotangent are computed inside the
  scan, the tick the microbatch leaves the last stage (``exit_fn``,
  supplied by the train-step builder so the CE/z-loss/global-denominator
  semantics stay in one place).
- Bubble lanes are masked by zeroing cotangents/activations — a zero
  cotangent through ``vjp`` yields zero parameter gradients, so garbage
  can never poison the accumulators (same invariant as ``pipeline_apply``).

Schedule indices (P stages, M microbatches, tick t):
  forward:  stage p computes microbatch  fm = t - p            (0 <= fm < M)
  exit:     microbatch em = t - (P-1) leaves stage P-1; its loss gradient
            feeds stage P-1's backward THIS tick
  backward: stage p computes microbatch  bm = t - 2(P-1) + p   (0 <= bm < M)
  ring:     stage p's input for fm is stored at slot fm % K and consumed
            2(P-1-p) ticks later; K = 2(P-1)+1 slots suffice for every stage.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from tpu_engine.models import transformer as tfm


def pipeline_1f1b_grads(
    staged_params: Any,
    x_mb: jax.Array,
    loss_tokens_mb: jax.Array,
    cfg: tfm.ModelConfig,
    *,
    positions: jax.Array,
    exit_fn: Callable[[jax.Array, jax.Array], tuple[jax.Array, jax.Array, Any]],
    outer_grad_zero: Any,
    mesh=None,
    remat: bool = False,
    remat_policy: str = "nothing_saveable",
    buf_sharding: Optional[NamedSharding] = None,
    aux_cotangent: float = 0.0,
    layer_constraint=None,
) -> tuple[jax.Array, jax.Array, Any, Any, jax.Array]:
    """Run the 1F1B schedule; returns gradients, no autodiff required above.

    Args:
      staged_params: [P, L/P, ...] leaves, stage dim sharded over ``pipe``.
      x_mb: embedded microbatches [M, B, S, D].
      loss_tokens_mb: target tokens [M, B, S] (mask-encoded) fed to exit_fn.
      exit_fn(y, toks) -> (loss_sum_contrib, dy, d_outer): one microbatch's
        summed loss, its cotangent w.r.t. y, and the cotangent tree for the
        outer (unembed/head) params. Must already be denominator-scaled so
        summing over microbatches gives the global objective.
      outer_grad_zero: zero-initialised accumulator tree matching exit_fn's
        d_outer (fp32 leaves).
      aux_cotangent: cotangent for each stage call's summed MoE aux loss
        (router_aux_coef / (n_layers · M) on the training path; 0 disables).

    Returns:
      (loss_sum, aux_sum, dstaged fp32 [P, L/P, ...], d_outer, dx_mb):
      ``dx_mb`` is the cotangent of ``x_mb`` (feed the embedding vjp);
      ``aux_sum`` is the masked sum of per-stage aux losses (divide by
      n_layers · M for the mean the GPipe path reports).
    """
    some_leaf = jax.tree.leaves(staged_params)[0]
    n_stages = some_leaf.shape[0]
    M = x_mb.shape[0]
    K = 2 * (n_stages - 1) + 1
    ticks = M + 2 * (n_stages - 1)
    stage_ids = jnp.arange(n_stages)

    body = tfm.remat_scan_body(cfg, positions, mesh, remat, remat_policy,
                               layer_constraint=layer_constraint)

    def stage_fn(x, stage_layers):
        y, aux = lax.scan(body, x, stage_layers)
        return y, jnp.sum(aux)

    def stage_vjp(x, w, dy, d_aux):
        # Recompute the stage forward from its saved input and pull the
        # cotangent back through it (per-stage remat).
        _, vjp = jax.vjp(stage_fn, x, w)
        dx, dw = vjp((dy, d_aux))
        return dx, dw

    vfwd = jax.vmap(stage_fn, spmd_axis_name="pipe")
    vbwd = jax.vmap(stage_vjp, spmd_axis_name="pipe")

    def constrain(buf):
        if buf_sharding is not None:
            buf = lax.with_sharding_constraint(buf, buf_sharding)
        return buf

    ring_sharding = None
    if buf_sharding is not None:
        spec = tuple(buf_sharding.spec) + (None,) * 4
        ring_sharding = NamedSharding(
            buf_sharding.mesh, P(spec[0], None, *spec[1:4])
        )

    def constrain_ring(ring):
        if ring_sharding is not None:
            ring = lax.with_sharding_constraint(ring, ring_sharding)
        return ring

    B, S, D = x_mb.shape[1:]
    zeros_buf = constrain(jnp.zeros((n_stages, B, S, D), x_mb.dtype))
    ring0 = constrain_ring(jnp.zeros((n_stages, K, B, S, D), x_mb.dtype))
    dstaged0 = jax.tree.map(
        lambda a: jnp.zeros(a.shape, jnp.float32), staged_params
    )
    dx_mb0 = jnp.zeros_like(x_mb)

    def tick(carry, t):
        buf_f, ring, buf_b, dstaged, d_outer, dx_mb, loss_acc, aux_acc = carry

        # ---- forward wave -------------------------------------------------
        fm = t - stage_ids                                   # [P]
        fvalid = (fm >= 0) & (fm < M)
        x_in = lax.dynamic_index_in_dim(
            x_mb, jnp.clip(t, 0, M - 1), axis=0, keepdims=False
        )
        buf_f = constrain(buf_f.at[0].set(x_in))
        # Save each stage's input before computing (the ring is the bwd's
        # remat source). Slot = fm % K per stage.
        slots_f = jnp.where(fvalid, fm % K, 0)
        ring = constrain_ring(
            ring.at[stage_ids, slots_f].set(
                jnp.where(fvalid[:, None, None, None], buf_f, ring[stage_ids, slots_f])
            )
        )
        y, aux = vfwd(buf_f, staged_params)
        y = jnp.where(fvalid[:, None, None, None], y, jnp.zeros((), y.dtype))
        aux_acc = aux_acc + jnp.sum(jnp.where(fvalid, aux, 0.0))

        # ---- exit: microbatch em leaves the last stage --------------------
        em = t - (n_stages - 1)
        evalid = (em >= 0) & (em < M)
        toks = lax.dynamic_index_in_dim(
            loss_tokens_mb, jnp.clip(em, 0, M - 1), axis=0, keepdims=False
        )
        loss_m, dy_m, d_outer_m = exit_fn(y[n_stages - 1], toks)
        loss_acc = loss_acc + jnp.where(evalid, loss_m, 0.0)
        dy_m = jnp.where(evalid, dy_m, jnp.zeros((), dy_m.dtype))
        d_outer = jax.tree.map(
            lambda acc, g: acc + jnp.where(evalid, g, 0.0).astype(acc.dtype),
            d_outer, d_outer_m,
        )

        # ---- backward wave ------------------------------------------------
        bm = t - 2 * (n_stages - 1) + stage_ids              # [P]
        bvalid = (bm >= 0) & (bm < M)
        g_in = constrain(buf_b.at[n_stages - 1].set(dy_m.astype(buf_b.dtype)))
        # Zero cotangents on bubble lanes: vjp then yields zero grads.
        g_in = jnp.where(bvalid[:, None, None, None], g_in, jnp.zeros((), g_in.dtype))
        slots_b = jnp.where(bvalid, bm % K, 0)
        x_saved = ring[stage_ids, slots_b]
        d_aux = jnp.where(bvalid, jnp.float32(aux_cotangent), 0.0)
        dx, dw = vbwd(x_saved, staged_params, g_in, d_aux)
        dstaged = jax.tree.map(
            lambda acc, g: acc + g.astype(jnp.float32), dstaged, dw
        )
        # Stage 0's dx is the embedding cotangent for microbatch bm[0].
        dx_mb = lax.cond(
            bvalid[0],
            lambda d: lax.dynamic_update_index_in_dim(
                d, dx[0].astype(d.dtype), bm[0], axis=0
            ),
            lambda d: d,
            dx_mb,
        )

        # ---- rotate -------------------------------------------------------
        # Forward: stage p+1 receives stage p's output (CollectivePermute).
        buf_f = constrain(jnp.roll(y, 1, axis=0))
        # Backward: stage p receives stage p+1's input-cotangent; lane P-1
        # is refilled by the next tick's exit gradient.
        buf_b = constrain(jnp.roll(dx, -1, axis=0))
        return (buf_f, ring, buf_b, dstaged, d_outer, dx_mb, loss_acc, aux_acc), None

    carry0 = (
        zeros_buf, ring0, zeros_buf, dstaged0, outer_grad_zero, dx_mb0,
        jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
    )
    (_, _, _, dstaged, d_outer, dx_mb, loss_sum, aux_sum), _ = lax.scan(
        tick, carry0, jnp.arange(ticks)
    )
    return loss_sum, aux_sum, dstaged, d_outer, dx_mb
