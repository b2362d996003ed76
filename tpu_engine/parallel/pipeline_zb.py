"""Zero-bubble-style pipeline schedule: B/W-split backward fills bubble lanes.

1F1B (``pipeline_1f1b.py``) is the *memory* lever — O(P) in-flight stage
inputs — but in the masked-SPMD formulation its warmup and drain ticks run
the full forward+backward lane program with most lanes masked: every one of
the ``2(P-1)`` bubble ticks burns a forward wave, an exit loss, AND a
combined backward wave of compute that is thrown away. Zero-bubble
schedules (Qi et al., ZB-H1) observe that a stage's backward factors into
two independent halves — **B**, the input-cotangent chain the *previous*
stage is waiting for, and **W**, the weight gradient nobody is waiting
for — so W can be deferred into otherwise-idle lanes.

Here that insight is applied to the masked-SPMD ``lax.scan`` +
``vmap(spmd_axis_name="pipe")`` formulation by segmenting the schedule into
four phases, each its own scan whose per-tick lane program carries only the
ops the host-side op table (:func:`zb_op_table`) says any lane can need:

  warmup  ticks ``[0, P-2]``            forward lane only
  steady  ticks ``[P-1, M+P-2]``        forward + exit + combined backward
  drain   ticks ``[M+P-1, M+2(P-1)-1]`` B-only backward, W deferred
  W-tail  ticks ``[M+2(P-1), ...]``     deferred W retired from the stash

The steady phase keeps the *combined* per-stage vjp: splitting there would
duplicate the per-stage remat for every microbatch and lose at large M.
Only the drain's backwards — the ones whose W nobody downstream needs this
tick — are split: the drain lane runs the input-cotangent vjp alone
(no weight-gradient einsums are even traced), stashing each deferred
output-cotangent (≤ P-1 entries per stage, stage p defers exactly
``P-1-p``), and the W-tail retires the stash against stage inputs still
live in the 1F1B ring.

Per-stage lane cost in F-units (F = 1; combined backward = 3 with per-stage
remat; B-only = 2; W-only = 3, the intra-stage cotangent chain is still
needed to reach inner layers' weights):

  1F1B        4M + 8(P-1)   (every tick pays F + exit + combined BW)
  zero-bubble 4M + 6(P-1)   (warmup 1, steady 4, drain 2, tail 3)

— strictly cheaper for every M at P > 1, with the same O(P) activation
residency plus the bounded [P, P-1, B, S, D] stash. Raw tick count rises
to M + 3(P-1) (the tail), but ticks are not equal-cost: the burned
(masked-lane) compute drops from 8(P-1) to 6(P-1) F-units per stage. The
analytic account (:func:`schedule_account`) is what the profiler's
bubble-adjusted MFU reports.

Masking invariants are inherited from 1F1B: bubble lanes carry zero
activations/cotangents, and a zero cotangent through ``jax.vjp`` yields
zero parameter gradients, so masked lanes can never poison an accumulator.

Schedule indices (P stages, M microbatches, tick t, K = 2(P-1)+1):
  forward:   stage p computes fm = t - p             (0 <= fm < M)
  exit:      em = t - (P-1) leaves stage P-1          (steady only)
  backward:  stage p computes bm = t - 2(P-1) + p     (0 <= bm < M);
             immediate (combined) iff t <= M+P-2, else drain/B-only
  stash:     drain tick d = t - (M+P-1) stores stage p's output-cotangent
             at stash[p, d]; entry valid iff 0 <= M-(P-1)+d+p <= M-1
  W-tail:    tail tick u retires stash[p, u] for bm = M-(P-1)+u+p; the
             stage input is still at ring slot bm % K — no forward has
             written the ring since tick M+P-2, and any microbatch whose
             W is deferred satisfies bm + K > M-1, so its slot was never
             reused even in steady state.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from tpu_engine.models import transformer as tfm

# Per-op lane costs in F-units (forward = 1). The combined backward
# recomputes the stage forward (remat), runs the input-cotangent chain and
# the weight-gradient einsums: 3. B-only drops the weight einsums: 2.
# W-only still pays remat + the intra-stage cotangent chain (inner layers'
# weight grads need the cotangent at their output): 3.
OP_COST = {"F": 1.0, "BW": 3.0, "B": 2.0, "W": 3.0}


def zb_op_table(n_stages: int, microbatches: int) -> list[list[tuple[str, ...]]]:
    """Host-side per-tick op table: ``table[t][p]`` is the tuple of ops
    stage ``p``'s lanes perform at tick ``t`` — drawn from ``"F"``,
    ``"BW"`` (combined backward), ``"B"`` (input-cotangent only) and
    ``"W"`` (deferred weight gradient); ``()`` is an idle (masked) lane.

    This is the ground truth the four scan phases are segmented by, and
    what the schedule tests audit (per-stage op counts, stash bound).
    """
    P_, M = n_stages, microbatches
    ticks = M + 3 * (P_ - 1)
    table: list[list[tuple[str, ...]]] = []
    for t in range(ticks):
        row: list[tuple[str, ...]] = []
        for p in range(P_):
            ops: list[str] = []
            if 0 <= t - p < M:
                ops.append("F")
            bm = t - 2 * (P_ - 1) + p
            if 0 <= bm < M:
                if t <= M + P_ - 2:
                    ops.append("BW")          # steady: combined backward
                elif t <= M + 2 * (P_ - 1) - 1:
                    ops.append("B")           # drain: W deferred
            if t >= M + 2 * (P_ - 1):
                u = t - (M + 2 * (P_ - 1))
                wm = M - (P_ - 1) + u + p
                if u + p <= P_ - 2 and wm >= 0:
                    ops.append("W")           # tail: retire the stash
            row.append(tuple(ops))
        table.append(row)
    return table


def _phase_ticks(schedule: str, n_stages: int, microbatches: int) -> dict[str, int]:
    P_, M = n_stages, microbatches
    if schedule == "gpipe":
        # GPipe-by-autodiff: a forward scan of M+P-1 ticks, then autodiff
        # replays the reverse pipeline over the same tick count.
        return {"forward": M + P_ - 1, "backward": M + P_ - 1}
    if schedule == "1f1b":
        return {"steady": M + 2 * (P_ - 1)}
    if schedule == "zb":
        return {
            "warmup": P_ - 1,
            "steady": M,
            "drain": P_ - 1,
            "tail": P_ - 1,
        }
    raise ValueError(f"unknown pipeline schedule {schedule!r}")


# Per-tick cost of one lane in each phase, in F-units. Every lane of a
# masked-SPMD tick executes the phase's full program whether masked or not
# — that is precisely what makes bubble lanes expensive.
_PHASE_LANE_COST = {
    "forward": OP_COST["F"],
    "backward": OP_COST["BW"],
    "steady": OP_COST["F"] + OP_COST["BW"],
    "warmup": OP_COST["F"],
    "drain": OP_COST["B"],
    "tail": OP_COST["W"],
}


def schedule_account(
    schedule: str, n_stages: int, microbatches: int
) -> dict[str, Any]:
    """Analytic tick / busy-lane account for one schedule.

    Costs are per-stage lane F-units (forward of one microbatch through
    one stage = 1). ``useful`` is the work the objective requires — one F
    and one combined backward per (microbatch, stage), 4M per stage
    regardless of schedule; everything else a lane executes (masked bubble
    compute, split-backward remat duplication) is ``burned``. The busy
    fraction is what divides raw MFU into bubble-adjusted MFU
    (``tpu_engine/profiler.py``).
    """
    P_, M = n_stages, microbatches
    if P_ < 2:
        return {
            "schedule": schedule, "n_stages": P_, "microbatches": M,
            "ticks": 0, "lane_cost": 0.0, "useful_cost": 0.0,
            "burned_cost": 0.0, "busy_fraction": 1.0, "bubble_fraction": 0.0,
            "phases": {},
        }
    phases = _phase_ticks(schedule, P_, M)
    lane_cost = sum(_PHASE_LANE_COST[ph] * n for ph, n in phases.items())
    useful = 4.0 * M
    burned = lane_cost - useful
    ticks = sum(phases.values())
    return {
        "schedule": schedule,
        "n_stages": P_,
        "microbatches": M,
        "ticks": ticks,
        "lane_cost": lane_cost,
        "useful_cost": useful,
        "burned_cost": burned,
        "busy_fraction": useful / lane_cost if lane_cost else 1.0,
        "bubble_fraction": burned / lane_cost if lane_cost else 0.0,
        "phases": phases,
    }


def pipeline_zb_grads(
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
    """Run the zero-bubble schedule; same contract as ``pipeline_1f1b_grads``.

    Args and returns are identical to
    :func:`tpu_engine.parallel.pipeline_1f1b.pipeline_1f1b_grads` — the
    train-step builder swaps the two functions by name. The schedule is a
    pure reordering of the same per-stage vjps, so losses and gradients
    match 1F1B (and GPipe) bit-for-role; the gradient-parity test enforces
    ``allclose`` across all three.
    """
    some_leaf = jax.tree.leaves(staged_params)[0]
    n_stages = some_leaf.shape[0]
    M = x_mb.shape[0]
    K = 2 * (n_stages - 1) + 1
    stage_ids = jnp.arange(n_stages)

    body = tfm.remat_scan_body(cfg, positions, mesh, remat, remat_policy,
                               layer_constraint=layer_constraint)

    def stage_fn(x, stage_layers):
        y, aux = lax.scan(body, x, stage_layers)
        return y, jnp.sum(aux)

    def stage_vjp(x, w, dy, d_aux):
        # Combined backward (steady state): per-stage remat, then both
        # cotangents in one pull.
        _, vjp = jax.vjp(stage_fn, x, w)
        dx, dw = vjp((dy, d_aux))
        return dx, dw

    def stage_b_vjp(x, w, dy, d_aux):
        # B phase: differentiate w.r.t. the stage INPUT only — the weight
        # gradient einsums are never traced, so the drain lane program is
        # remat + the input-cotangent chain and nothing else.
        _, vjp = jax.vjp(lambda xx: stage_fn(xx, w), x)
        (dx,) = vjp((dy, d_aux))
        return dx

    def stage_w_vjp(x, w, dy, d_aux):
        # W phase: differentiate w.r.t. the stage WEIGHTS only. The
        # intra-stage cotangent chain still runs (inner layers' weight
        # grads need it) but the cross-stage input cotangent is never
        # formed.
        _, vjp = jax.vjp(lambda ww: stage_fn(x, ww), w)
        (dw,) = vjp((dy, d_aux))
        return dw

    vfwd = jax.vmap(stage_fn, spmd_axis_name="pipe")
    vbwd = jax.vmap(stage_vjp, spmd_axis_name="pipe")
    vbwd_b = jax.vmap(stage_b_vjp, spmd_axis_name="pipe")
    vbwd_w = jax.vmap(stage_w_vjp, spmd_axis_name="pipe")

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
    # Deferred-W stash: stage p defers the last P-1-p backwards' output
    # cotangents — at most P-1 live entries per stage, by construction.
    stash0 = constrain_ring(
        jnp.zeros((n_stages, n_stages - 1, B, S, D), x_mb.dtype)
    )
    dstaged0 = jax.tree.map(
        lambda a: jnp.zeros(a.shape, jnp.float32), staged_params
    )
    dx_mb0 = jnp.zeros_like(x_mb)

    # Carry shared by all four phase scans (unused slots pass through).
    # (buf_f, ring, buf_b, stash, dstaged, d_outer, dx_mb, loss, aux)

    def forward_wave(carry, t):
        """F lane: feed, save to ring, compute, mask — warmup & steady."""
        buf_f, ring, buf_b, stash, dstaged, d_outer, dx_mb, loss_acc, aux_acc = carry
        fm = t - stage_ids
        fvalid = (fm >= 0) & (fm < M)
        x_in = lax.dynamic_index_in_dim(
            x_mb, jnp.clip(t, 0, M - 1), axis=0, keepdims=False
        )
        buf_f = constrain(buf_f.at[0].set(x_in))
        slots_f = jnp.where(fvalid, fm % K, 0)
        ring = constrain_ring(
            ring.at[stage_ids, slots_f].set(
                jnp.where(fvalid[:, None, None, None], buf_f, ring[stage_ids, slots_f])
            )
        )
        y, aux = vfwd(buf_f, staged_params)
        y = jnp.where(fvalid[:, None, None, None], y, jnp.zeros((), y.dtype))
        aux_acc = aux_acc + jnp.sum(jnp.where(fvalid, aux, 0.0))
        return (
            (buf_f, ring, buf_b, stash, dstaged, d_outer, dx_mb, loss_acc, aux_acc),
            y,
        )

    def warmup_tick(carry, t):
        carry, y = forward_wave(carry, t)
        buf_f, ring, buf_b, stash, dstaged, d_outer, dx_mb, loss_acc, aux_acc = carry
        buf_f = constrain(jnp.roll(y, 1, axis=0))
        return (buf_f, ring, buf_b, stash, dstaged, d_outer, dx_mb, loss_acc, aux_acc), None

    def steady_tick(carry, t):
        # Identical lane program to a 1F1B tick: F + exit + combined BW.
        # Every backward here is "immediate" — its consumer is one tick
        # away — so the combined vjp is the right call (splitting would
        # duplicate the remat for every one of the M microbatches).
        carry, y = forward_wave(carry, t)
        buf_f, ring, buf_b, stash, dstaged, d_outer, dx_mb, loss_acc, aux_acc = carry

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

        bm = t - 2 * (n_stages - 1) + stage_ids
        bvalid = (bm >= 0) & (bm < M)
        g_in = constrain(buf_b.at[n_stages - 1].set(dy_m.astype(buf_b.dtype)))
        g_in = jnp.where(bvalid[:, None, None, None], g_in, jnp.zeros((), g_in.dtype))
        slots_b = jnp.where(bvalid, bm % K, 0)
        x_saved = ring[stage_ids, slots_b]
        d_aux = jnp.where(bvalid, jnp.float32(aux_cotangent), 0.0)
        dx, dw = vbwd(x_saved, staged_params, g_in, d_aux)
        dstaged = jax.tree.map(
            lambda acc, g: acc + g.astype(jnp.float32), dstaged, dw
        )
        dx_mb = lax.cond(
            bvalid[0],
            lambda d: lax.dynamic_update_index_in_dim(
                d, dx[0].astype(d.dtype), bm[0], axis=0
            ),
            lambda d: d,
            dx_mb,
        )

        buf_f = constrain(jnp.roll(y, 1, axis=0))
        buf_b = constrain(jnp.roll(dx, -1, axis=0))
        return (buf_f, ring, buf_b, stash, dstaged, d_outer, dx_mb, loss_acc, aux_acc), None

    def drain_tick(carry, t):
        # B-only: no forward wave, no exit (every microbatch has left the
        # last stage by tick M+P-2). The lane runs the input-cotangent
        # vjp alone and stashes its incoming cotangent for the W-tail.
        buf_f, ring, buf_b, stash, dstaged, d_outer, dx_mb, loss_acc, aux_acc = carry
        bm = t - 2 * (n_stages - 1) + stage_ids
        bvalid = (bm >= 0) & (bm < M)
        g_in = jnp.where(
            bvalid[:, None, None, None], buf_b, jnp.zeros((), buf_b.dtype)
        )
        d = t - (M + n_stages - 1)  # drain tick index = stash slot
        stash = constrain_ring(
            lax.dynamic_update_slice_in_dim(stash, g_in[:, None], d, axis=1)
        )
        slots_b = jnp.where(bvalid, bm % K, 0)
        x_saved = ring[stage_ids, slots_b]
        d_aux = jnp.where(bvalid, jnp.float32(aux_cotangent), 0.0)
        dx = vbwd_b(x_saved, staged_params, g_in, d_aux)
        dx_mb = lax.cond(
            bvalid[0],
            lambda dd: lax.dynamic_update_index_in_dim(
                dd, dx[0].astype(dd.dtype), bm[0], axis=0
            ),
            lambda dd: dd,
            dx_mb,
        )
        buf_b = constrain(jnp.roll(dx, -1, axis=0))
        return (buf_f, ring, buf_b, stash, dstaged, d_outer, dx_mb, loss_acc, aux_acc), None

    def tail_tick(carry, u):
        # W-only: retire stash entry u against the ring's saved input.
        buf_f, ring, buf_b, stash, dstaged, d_outer, dx_mb, loss_acc, aux_acc = carry
        wm = M - (n_stages - 1) + u + stage_ids
        wvalid = (u + stage_ids <= n_stages - 2) & (wm >= 0)
        dy = lax.dynamic_index_in_dim(stash, u, axis=1, keepdims=False)
        dy = jnp.where(wvalid[:, None, None, None], dy, jnp.zeros((), dy.dtype))
        slots_w = jnp.where(wvalid, wm % K, 0)
        x_saved = ring[stage_ids, slots_w]
        d_aux = jnp.where(wvalid, jnp.float32(aux_cotangent), 0.0)
        dw = vbwd_w(x_saved, staged_params, dy, d_aux)
        dstaged = jax.tree.map(
            lambda acc, g: acc + g.astype(jnp.float32), dstaged, dw
        )
        return (buf_f, ring, buf_b, stash, dstaged, d_outer, dx_mb, loss_acc, aux_acc), None

    carry = (
        zeros_buf, ring0, zeros_buf, stash0, dstaged0, outer_grad_zero,
        dx_mb0, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
    )
    carry, _ = lax.scan(warmup_tick, carry, jnp.arange(0, n_stages - 1))
    carry, _ = lax.scan(
        steady_tick, carry, jnp.arange(n_stages - 1, M + n_stages - 1)
    )
    carry, _ = lax.scan(
        drain_tick, carry,
        jnp.arange(M + n_stages - 1, M + 2 * (n_stages - 1)),
    )
    carry, _ = lax.scan(tail_tick, carry, jnp.arange(0, n_stages - 1))
    (_, _, _, _, dstaged, d_outer, dx_mb, loss_sum, aux_sum) = carry
    return loss_sum, aux_sum, dstaged, d_outer, dx_mb
