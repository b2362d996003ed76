"""Serving-fleet sim: autoscaled replicas vs a static single replica.

Thin scenario definition over the digital twin (``tpu_engine/twin.py``):
the seeded traces come from the twin's synthetic traffic generators, the
fleet loop is the twin's open-loop tick driver, and the autoscaled lane
is :func:`tpu_engine.twin.replay_serving_fleet` — CLI flags, exit gates
and JSON metric lines are unchanged from the pre-twin benchmark.

Deterministic discrete-event comparison (virtual clock — no threads, no
JAX, identical numbers every run) of two fleet policies on the same
seeded bursty open-loop request trace:

- **static-1** — what ``tpu_engine/serving.py`` alone gives you: one
  decode replica; every burst queues behind its slot pool.
- **autoscaled** — this repo's :class:`ServingFleet` control plane: the
  REAL :class:`~tpu_engine.serving_fleet.FleetRouter` (throughput ×
  free-slot smooth WRR + shared-prefix affinity) and the REAL
  :class:`~tpu_engine.serving_fleet.ReplicaAutoscaler` (sliding-window
  queue depth + p99 SLO, scale-down hysteresis) drive replica count
  between min and max. New replicas pay a startup delay (scheduler
  admission + weight load + compile), exactly the lag hysteresis exists
  to hide.

Replicas are capacity models, not transformers: ``SLOTS`` concurrent
requests each decoding ``per-slot tokens/sec`` (one replica runs on a
degraded host at a fraction of that — the router's weights, not a
health-check binary, decide how much traffic it still deserves). A
request's prompt opens with one of a few shared system prefixes;
replica-side prefix caches skip the prefill for resident prefixes, which
is what router affinity is for.

Reports the MODEL's aggregate tokens/sec (and per chip-second, so extra
replicas don't get their throughput for free), p50/p99 latency vs the SLO,
the replica-count trace, router weights and affinity hit rate, all at the
assumed rates of ``twin.REPLICA_*``: gates on the router's and the
autoscaler's logic, not speeds of the chip.

A second experiment (PR 12) A/Bs **symmetric vs disaggregated** serving
at EQUAL total chips on a long-prefill-heavy bursty trace. The symmetric
fleet models the real ``ContinuousBatcher`` interference: a chunked
prefill monopolizes the MXU, so co-resident decode slots crawl while any
prefill is in flight — slots stay occupied longer, admission stalls, and
p99 TTFT compounds. The disaggregated fleet (``tpu_engine/disagg.py``)
runs planner-placed pools — prefill layout ranked by the compute
roofline, decode by KV-pool capacity, both from the REAL
:func:`tpu_engine.placement.plan_serving_pool` — with a host-side KV
handoff between them; decode never stalls and TTFT is the prefill-pool
latency. ``main()`` exit-gates the A/B: disaggregated must beat
symmetric p99 TTFT with tokens/sec no worse, and the JSON records both
configurations' planner-chosen layouts.

Run: ``python -m benchmarks.serving_fleet_sim [--seed N]``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpu_engine.serving_fleet import (  # noqa: E402
    AutoscalerConfig,
    FleetRouter,
)
from tpu_engine.twin import (  # noqa: E402
    ServingTwinParams,
    bursty_arrivals,
    replay_serving_fleet,
    run_open_loop,
    serving_metrics,
)

# The shipped scenario parameters; the twin's dataclass carries them, the
# module-level constants remain the stable public surface tests import.
SERVING = ServingTwinParams()

SIM_DURATION_S = SERVING.duration_s
DT_S = SERVING.dt_s               # sim tick
CONTROL_PERIOD_S = SERVING.control_period_s  # autoscaler / router cadence
SLOTS = SERVING.slots             # decode slots per replica
TOKENS_PER_SLOT_S = SERVING.tokens_per_slot_s  # healthy per-slot decode rate
DEGRADED_FRACTION = SERVING.degraded_fraction  # replica 0's slow-host rate
PREFILL_S = SERVING.prefill_s     # full prefill latency (cold prefix)
PREFILL_HIT_S = SERVING.prefill_hit_s  # prefix-cache hit remainder
STARTUP_DELAY_S = SERVING.startup_delay_s  # admission + load + compile
CHIPS_PER_REPLICA = SERVING.chips_per_replica
BASE_RATE_RPS = 1.0          # open-loop arrivals outside bursts
BURST_RATE_RPS = 14.0        # arrivals inside a burst window
BURST_EVERY_S = 120.0
BURST_LEN_S = 35.0
N_PREFIXES = 4               # shared system prompts
PREFIX_LEN = SERVING.prefix_len
MEAN_NEW_TOKENS = 96
P99_SLO_MS = SERVING.p99_slo_ms
# Latency percentiles are steady-state: the first burst cycle is warmup
# (it lands on the min fleet by construction — what it measures is the
# startup delay, not the policy). Throughput counts everything.
WARMUP_S = SERVING.warmup_s

AUTOSCALER = AutoscalerConfig(
    min_replicas=1,
    max_replicas=8,
    target_queue_per_replica=4.0,
    low_water_queue_per_replica=0.5,
    p99_slo_ms=P99_SLO_MS,
    window_s=20.0,
    scale_up_cooldown_s=3.0,
    scale_down_cooldown_s=90.0,
)

def request_trace(seed: int) -> list[dict]:
    """Seeded bursty open-loop arrivals: [{t, prefix_id, prompt, n_new}]."""
    return bursty_arrivals(
        seed,
        duration_s=SIM_DURATION_S,
        base_rps=BASE_RATE_RPS,
        burst_rps=BURST_RATE_RPS,
        burst_every_s=BURST_EVERY_S,
        burst_len_s=BURST_LEN_S,
        n_prefixes=N_PREFIXES,
        prefix_len=PREFIX_LEN,
        mean_new_tokens=MEAN_NEW_TOKENS,
    )


def _simulate(trace: list[dict], autoscale: bool) -> dict:
    return replay_serving_fleet(trace, autoscale, AUTOSCALER, SERVING)


def run_trace(seed: int = 0) -> dict:
    trace = request_trace(seed)
    auto = _simulate(trace, autoscale=True)
    static = _simulate(trace, autoscale=False)
    return {
        "seed": seed,
        "n_requests": len(trace),
        "autoscaled": auto,
        "static_1_replica": static,
        "throughput_improvement": round(
            auto["tokens_per_sec"] / max(static["tokens_per_sec"], 1e-9), 2
        ),
        "p99_improvement": round(
            static["p99_ms"] / max(auto["p99_ms"], 1e-9), 2
        ),
        "p99_slo_ms": P99_SLO_MS,
    }


# ---------------------------------------------------------------------------
# Symmetric vs disaggregated A/B (PR 12) — equal chips, long-prefill trace
# ---------------------------------------------------------------------------

TOTAL_CHIPS = 8              # equal-chips budget for BOTH configurations
PREFILL_CHIPS = 6            # disagg split: prefill-heavy trace → prefill-heavy pool
DECODE_CHIPS = TOTAL_CHIPS - PREFILL_CHIPS
LONG_PREFILL_MEAN_S = 1.5    # one prompt's prefill seconds on ONE chip (tp=1)
LONG_PREFILL_MIN_S = 0.3
LONG_MEAN_NEW = 96
LONG_BASE_RPS = 0.4
LONG_BURST_RPS = 3.0
HANDOFF_S = 0.05             # host-side KV wire latency (not on the TTFT path)
# Chunked-prefill interference in a SYMMETRIC replica: while a prefill
# chunk owns the MXU, co-resident decode steps run at this fraction of
# their clean cadence (a decode step is ~an order of magnitude shorter
# than a prefill chunk), and the prefill itself loses the decode share.
INTERFERENCE_DECODE = 0.15
INTERFERENCE_PREFILL = 0.85
PLAN_MODEL = "llama-7b"
PLAN_MAX_LEN = 2048
PLAN_HBM_GIB = 24.0
PLAN_INFLIGHT = 4            # prefill pool's in-flight handoff window


def long_prefill_trace(seed: int) -> list[dict]:
    """Seeded bursty arrivals with heavy, variable prefill cost:
    [{t, prompt, prefill_units, n_new}] — ``prefill_units`` is seconds of
    prefill work at tp=1."""
    return bursty_arrivals(
        seed,
        duration_s=SIM_DURATION_S,
        base_rps=LONG_BASE_RPS,
        burst_rps=LONG_BURST_RPS,
        burst_every_s=BURST_EVERY_S,
        burst_len_s=BURST_LEN_S,
        n_prefixes=N_PREFIXES,
        prefix_len=PREFIX_LEN,
        mean_new_tokens=LONG_MEAN_NEW,
        prefill_mean_s=LONG_PREFILL_MEAN_S,
        prefill_min_s=LONG_PREFILL_MIN_S,
        seed_offset=7919,
    )


class SymReplica:
    """One chip, both phases. Prefills serialize (one chunked prefill at a
    time owns the MXU); while one is in flight every decoding slot crawls
    at the interference rate — the slot-starvation feedback that kills
    symmetric p99 TTFT under prefill bursts."""

    def __init__(self, rid: str):
        self.rid = rid
        self.active: list[dict] = []

    def free_slots(self) -> int:
        return SLOTS - len(self.active)

    def admit(self, req: dict, now: float) -> None:
        self.active.append({
            "req": req, "prefill_left": req["prefill_units"],
            "tokens_left": float(req["n_new"]),
        })

    def step(self, now: float, dt: float, done: list[dict],
             ttfts: list[float]) -> None:
        pre = next((s for s in self.active if s["prefill_left"] > 0), None)
        decode_rate = TOKENS_PER_SLOT_S
        if pre is not None:
            pre["prefill_left"] -= dt * INTERFERENCE_PREFILL
            if pre["prefill_left"] <= 0:
                pre["req"]["first_token_at"] = now + dt
                ttfts.append((now + dt - pre["req"]["t"]) * 1000.0)
            decode_rate *= INTERFERENCE_DECODE
        for sl in list(self.active):
            if sl["prefill_left"] > 0 or sl is pre:
                continue
            sl["tokens_left"] -= decode_rate * dt
            if sl["tokens_left"] <= 0:
                sl["req"]["done_at"] = now + dt
                done.append(sl["req"])
                self.active.remove(sl)

    def router_stats(self) -> dict:
        busy = sum(1 for s in self.active if s["prefill_left"] <= 0)
        return {
            "tokens_per_sec": TOKENS_PER_SLOT_S * max(busy, 0.2),
            "free_slots": self.free_slots(),
            "slots": SLOTS,
        }


def _simulate_symmetric_long(trace: list[dict]) -> dict:
    router = FleetRouter(affinity_tokens=PREFIX_LEN)
    replicas = [SymReplica(f"s{i}") for i in range(TOTAL_CHIPS)]
    by_id = {r.rid: r for r in replicas}
    queue: list[dict] = []
    done: list[dict] = []
    ttfts: list[float] = []

    def control(t: float) -> None:
        router.update({r.rid: r.router_stats() for r in replicas})

    def tick(t: float) -> None:
        while queue and any(r.free_slots() > 0 for r in replicas):
            rid = router.route(queue[0]["prompt"])
            rep = by_id.get(rid) if rid else None
            if rep is None or rep.free_slots() <= 0:
                break  # router picked a full replica; weights refresh next tick
            rep.admit(queue.pop(0), t)
        for r in replicas:
            r.step(t, DT_S, done, ttfts)

    run_open_loop(
        trace, dt=DT_S, duration_s=SIM_DURATION_S,
        pending=lambda: queue or any(r.active for r in replicas),
        arrive=queue.append, tick=tick, control=control,
        control_period_s=CONTROL_PERIOD_S, safety_factor=6.0,
    )
    return _ab_metrics(done, ttfts)


def _simulate_disagg(trace: list[dict], prefill_plan, decode_plan,
                     prefill_speedup: float) -> dict:
    """Planner-placed pools: ``prefill_plan.replicas`` serial prefill
    servers (each ``prefill_speedup`` × one chip, the roofline ratio the
    planner predicted for its tensor-parallel choice) feeding
    ``decode_plan.replicas`` decode-only replicas through a ``HANDOFF_S``
    KV wire. Decode never shares the MXU with a prefill."""
    # Per-slot decode rate: the pool's chips stream the same aggregate
    # HBM bandwidth as the symmetric fleet's per-chip 8×30 tok/s; more
    # slots trade per-slot speed for concurrency (the KV-capacity axis).
    dec_rate = (TOKENS_PER_SLOT_S * SLOTS * decode_plan.tensor_parallel
                / decode_plan.max_slots)
    prefill_router = FleetRouter(affinity_tokens=PREFIX_LEN)
    decode_router = FleetRouter(affinity_tokens=PREFIX_LEN)
    pre = [{"rid": f"p{i}", "job": None} for i in range(prefill_plan.replicas)]
    dec = [{"rid": f"d{i}", "active": []} for i in range(decode_plan.replicas)]
    queue: list[dict] = []          # awaiting a prefill server
    handoff: list[dict] = []        # KV on the wire / awaiting a decode slot
    done: list[dict] = []
    ttfts: list[float] = []

    def control(t: float) -> None:
        prefill_router.update({
            p["rid"]: {
                "tokens_per_sec": prefill_speedup * TOKENS_PER_SLOT_S,
                "free_slots": 0 if p["job"] else 1, "slots": 1,
            } for p in pre
        })
        decode_router.update({
            d["rid"]: {
                "tokens_per_sec": dec_rate * max(len(d["active"]), 0.2),
                "free_slots": decode_plan.max_slots - len(d["active"]),
                "slots": decode_plan.max_slots,
            } for d in dec
        })

    def tick(t: float) -> None:
        # Route waiting prompts onto idle prefill servers.
        while queue and any(p["job"] is None for p in pre):
            rid = prefill_router.route(queue[0]["prompt"])
            srv = next((p for p in pre if p["rid"] == rid), None)
            if srv is None or srv["job"] is not None:
                break
            req = queue.pop(0)
            srv["job"] = {
                "req": req,
                "left": req["prefill_units"] / prefill_speedup,
            }
        # Advance prefills; completion IS the first token (prefill logits).
        for p in pre:
            job = p["job"]
            if job is None:
                continue
            job["left"] -= DT_S
            if job["left"] <= 0:
                req = job["req"]
                req["first_token_at"] = t + DT_S
                ttfts.append((t + DT_S - req["t"]) * 1000.0)
                req["handoff_ready"] = t + DT_S + HANDOFF_S
                handoff.append(req)
                p["job"] = None
        # Deliver arrived handoffs into reserved decode slots.
        for req in list(handoff):
            if req["handoff_ready"] > t:
                continue
            rid = decode_router.route(req["prompt"])
            rep = next((d for d in dec if d["rid"] == rid), None)
            if rep is None or len(rep["active"]) >= decode_plan.max_slots:
                break
            handoff.remove(req)
            rep["active"].append({"req": req, "tokens_left": float(req["n_new"])})
        for d in dec:
            for sl in list(d["active"]):
                sl["tokens_left"] -= dec_rate * DT_S
                if sl["tokens_left"] <= 0:
                    sl["req"]["done_at"] = t + DT_S
                    done.append(sl["req"])
                    d["active"].remove(sl)

    run_open_loop(
        trace, dt=DT_S, duration_s=SIM_DURATION_S,
        pending=lambda: (queue or handoff or any(p["job"] for p in pre)
                         or any(d["active"] for d in dec)),
        arrive=queue.append, tick=tick, control=control,
        control_period_s=CONTROL_PERIOD_S, safety_factor=6.0,
    )
    return _ab_metrics(done, ttfts)


def _ab_metrics(done: list[dict], ttfts: list[float],
                t_end: float = 0.0) -> dict:
    return serving_metrics(
        done, ttfts, warmup_s=WARMUP_S, total_chips=TOTAL_CHIPS, dt_s=DT_S
    )


def run_disagg_ab(seed: int = 0) -> dict:
    """Symmetric vs disaggregated at TOTAL_CHIPS on the long-prefill
    trace; layouts chosen by the real planner and recorded in the output."""
    from tpu_engine.placement import plan_serving_pool

    pre_plans = plan_serving_pool(
        PLAN_MODEL, "prefill", PREFILL_CHIPS, hbm_free_gib=PLAN_HBM_GIB,
        max_len=PLAN_MAX_LEN, inflight_handoffs=PLAN_INFLIGHT)
    dec_plans = plan_serving_pool(
        PLAN_MODEL, "decode", DECODE_CHIPS, hbm_free_gib=PLAN_HBM_GIB,
        max_len=PLAN_MAX_LEN)
    sym_plans = plan_serving_pool(
        PLAN_MODEL, "decode", TOTAL_CHIPS, hbm_free_gib=PLAN_HBM_GIB,
        max_len=PLAN_MAX_LEN)
    pre_plan = next(p for p in pre_plans if p.feasible)
    dec_plan = next(p for p in dec_plans if p.feasible)
    sym_plan = next(p for p in sym_plans if p.feasible)
    # The planner's own roofline ratio: how much faster the chosen prefill
    # layout runs one prompt than a single tp=1 chip would.
    tp1 = next(p for p in pre_plans if p.tensor_parallel == 1)
    prefill_speedup = tp1.predicted_prefill_s / pre_plan.predicted_prefill_s

    trace = long_prefill_trace(seed)
    sym = _simulate_symmetric_long(trace)
    dis = _simulate_disagg(trace, pre_plan, dec_plan, prefill_speedup)
    gates = {
        "disagg_beats_symmetric_p99_ttft": dis["ttft_p99_ms"] < sym["ttft_p99_ms"],
        # "No worse" with a 1% deterministic-sim tolerance.
        "disagg_tokens_per_sec_no_worse": (
            dis["tokens_per_sec"] >= 0.99 * sym["tokens_per_sec"]),
    }
    return {
        "seed": seed,
        "total_chips": TOTAL_CHIPS,
        "n_requests": len(trace),
        "layouts": {
            "symmetric": sym_plan.label,
            "disagg_prefill": pre_plan.label,
            "disagg_decode": dec_plan.label,
            "prefill_speedup": round(prefill_speedup, 2),
        },
        "symmetric": sym,
        "disagg": dis,
        "ttft_p99_improvement": round(
            sym["ttft_p99_ms"] / max(dis["ttft_p99_ms"], 1e-9), 2),
        "gates": gates,
        "gates_pass": all(gates.values()),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    out = {
        "autoscale_vs_static": run_trace(args.seed),
        "disagg_ab": run_disagg_ab(args.seed),
    }
    print(json.dumps(out, indent=2))
    if not out["disagg_ab"]["gates_pass"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
