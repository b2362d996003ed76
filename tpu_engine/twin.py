"""Trace-replay digital twin: one virtual-clock fleet engine, and the lanes.

The scenario scripts that stay under ``benchmarks/`` as test fixtures
(``scheduler_sim.py``, ``serving_fleet_sim.py``, ``chaos.py``) are thin
scenario definitions over this engine, which also replays a *recorded* run.

A lane drives the REAL control-plane components through their
explicit-timestamp APIs under one :class:`VirtualClock` and returns its own
result with ``gates`` and ``ok``: tier-1 tests assert those. A lane gates
control-plane LOGIC. Its replicas and jobs are capacity models at assumed
rates (``REPLICA_*`` below), so no number a lane returns is a speed of the
chip; those are ``benchmarks/onchip/run.py``'s and ``PERF_LEDGER.jsonl``'s.

Layers:

- **Trace ingestion** (:func:`read_recorder_jsonl`,
  :class:`ReplayWorkload`): parse flight-recorder JSONL (spans, events,
  explicit timestamps, parent links) into a replayable workload — job
  submissions with their observed priorities/durations, serving request
  arrivals, fault timelines — tolerating rotated files, a torn partial
  last line, and unknown ``schema_version`` lines (skipped and counted,
  never raised mid-replay). Composable synthetic generators
  (:func:`bursty_arrivals`, :func:`diurnal_arrivals`,
  :func:`heavy_tail_prefill_arrivals`) cover scenarios never yet
  observed; the bursty generator reproduces the legacy sims' seeded
  traces draw-for-draw.

- **Replay core** (:class:`TwinEngine`): records the replayed run back
  onto a fresh :class:`FlightRecorder` with deterministic span ids, so
  every twin run is itself Perfetto-exportable and byte-for-byte diffable
  against the source trace (or a previous replay).
  :func:`replay_fidelity` and :func:`twin_replay_gates` hold it to the
  recorded run.

- **A/B scorecard** (:func:`ab_scorecard`,
  :func:`default_policy_scorecard`, :func:`admission_policy_scorecard`):
  N policy variants over the same ingested trace, one JSON artifact with
  per-variant goodput decomposition, queue-wait, MTTR and SLO-burn deltas
  against the first (baseline) variant.

- **The lanes**, each with the component it gates:
  training self-heal vs die-and-restart (:func:`replay_self_heal`,
  :func:`replay_die_and_restart`, :func:`goodput_lane`: ``GoodputLedger``,
  ``CompileCacheIndex``); slow-host rebalancing (:func:`replay_hetero`,
  :func:`run_hetero_ab`: ``HeteroRebalancer``); the autoscaled serving
  fleet (:func:`replay_serving_fleet`: ``FleetRouter``,
  ``ReplicaAutoscaler``); warm admission (:func:`warm_admission_lane`);
  :func:`historian_lane` (``MetricHistorian``, ``IncidentCorrelator``);
  :func:`autopilot_lane` (``FleetAutopilot``); :func:`scale_lane` /
  :func:`ctl_scale_profile` (control overhead as history grows 100x);
  :func:`prefix_plane_lane` / :func:`prefix_plane_ab` (``PrefixPlane``,
  ``HostKVTier``); :func:`reshard_ab` (``reshard``);
  :func:`spec_pool_lane` / :func:`spec_pool_ab` (``SpecSpillController``);
  :func:`ctl_crash_lane` / :func:`ctl_crash_ab` (``ControlPlaneJournal``).
  The three serving lanes share ONE replica model, :class:`SlotReplica`.

Health counters for the ``tpu_engine_twin_*`` Prometheus families live
in module state (:func:`twin_stats`); ``POST /api/v1/twin/replay`` is
the dry-run HTTP entry (``backend/routers/twin.py``).
"""

from __future__ import annotations

import collections
import dataclasses
import heapq
import json
import math
import os
import random
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from tpu_engine import hetero as hetero_mod
from tpu_engine import historian as historian_mod
from tpu_engine.autopilot import AutopilotConfig, FleetAutopilot
from tpu_engine.compile_index import CompileCacheIndex
from tpu_engine.faults import FaultInjector, FaultKind, FaultPlan, FaultSpec
from tpu_engine.goodput import CATEGORIES, GoodputLedger, SLOBurnRateAlerter
from tpu_engine.tracing import SCHEMA_VERSION, FlightRecorder

__all__ = [
    "VirtualClock",
    "deterministic_ids",
    "read_recorder_jsonl",
    "ReplayWorkload",
    "TwinEngine",
    "decomposition_diff",
    "bursty_arrivals",
    "diurnal_arrivals",
    "heavy_tail_prefill_arrivals",
    "TrainTwinParams",
    "HeteroTwinParams",
    "ServingTwinParams",
    "chip_fault_timeline",
    "replay_self_heal",
    "replay_die_and_restart",
    "goodput_lane",
    "host_slow_plan",
    "replay_hetero",
    "run_hetero_ab",
    "SlotReplica",
    "run_open_loop",
    "replay_serving_fleet",
    "serving_metrics",
    "percentile",
    "warm_admission_lane",
    "ab_scorecard",
    "default_policy_scorecard",
    "admission_policy_scorecard",
    "replay_fidelity",
    "twin_replay_gates",
    "historian_lane",
    "replay_autopilot",
    "autopilot_lane",
    "ScaleLaneParams",
    "scale_lane",
    "ctl_scale_profile",
    "PrefixPlaneLaneParams",
    "prefix_plane_lane",
    "prefix_plane_ab",
    "ReshardLaneParams",
    "replay_reshard_resume",
    "reshard_roundtrip_report",
    "reshard_migration_report",
    "reshard_ab",
    "CtlCrashLaneParams",
    "ctl_crash_lane",
    "ctl_crash_ab",
    "twin_stats",
]


# -- virtual clock / deterministic ids ----------------------------------------


class VirtualClock:
    """A callable simulated clock: pass as any component's ``clock=``."""

    __slots__ = ("t",)

    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def now(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += float(dt)
        return self.t

    def set(self, t: float) -> float:
        self.t = float(t)
        return self.t


def deterministic_ids(prefix: str = "twin") -> Callable[[], str]:
    """A counter-based id factory for :class:`FlightRecorder` — replays
    get byte-stable span/event ids instead of uuid4."""
    n = 0

    def _next() -> str:
        nonlocal n
        n += 1
        return f"{prefix}-{n:08d}"

    return _next


# -- module health counters (tpu_engine_twin_* Prometheus families) -----------

SKIP_REASONS = ("torn_tail", "parse_error", "unknown_schema", "unknown_record")

_STATS_LOCK = threading.Lock()
_STATS: Dict[str, Any] = {
    "replays_total": 0,
    "ab_runs_total": 0,
    "ingest_files_total": 0,
    "ingest_lines_total": 0,
    "ingest_skipped_lines_total": 0,
    "ingest_skipped_by_reason": {r: 0 for r in SKIP_REASONS},
    "replayed_spans_total": 0,
    "replayed_events_total": 0,
    "fleet_seconds_total": 0.0,
    "cpu_seconds_total": 0.0,
    "last_fleet_seconds_per_cpu_second": 0.0,
}


def twin_stats() -> Dict[str, Any]:
    """Snapshot of the twin's monotonic health counters."""
    with _STATS_LOCK:
        out = dict(_STATS)
        out["ingest_skipped_by_reason"] = dict(_STATS["ingest_skipped_by_reason"])
    return out


def _reset_stats_for_tests() -> None:
    with _STATS_LOCK:
        for k, v in list(_STATS.items()):
            if isinstance(v, dict):
                _STATS[k] = {r: 0 for r in SKIP_REASONS}
            else:
                _STATS[k] = 0 if isinstance(v, int) else 0.0


def _bump(**deltas: float) -> None:
    with _STATS_LOCK:
        for k, v in deltas.items():
            _STATS[k] += v


# -- trace ingestion ----------------------------------------------------------


def read_recorder_jsonl(path: str) -> Tuple[List[dict], Dict[str, Any]]:
    """Read flight-recorder JSONL at ``path`` (plus its rotated ``.1``
    generation, oldest first) into record dicts.

    Hardened for mid-write capture: an undecodable *final* line of the
    live file is a torn tail (the recorder was mid-append), any other bad
    line is a parse error, a ``schema_version`` above this build's
    :data:`SCHEMA_VERSION` is an unknown future format — all are skipped
    and counted (``twin_ingest_skipped_lines_total``), never raised."""
    files = [p for p in (path + ".1", path) if os.path.exists(p)]
    records: List[dict] = []
    stats: Dict[str, Any] = {
        "files": len(files),
        "lines": 0,
        "accepted": 0,
        "skipped": 0,
        "skipped_by_reason": {},
        "legacy_lines": 0,
        "schema_version": SCHEMA_VERSION,
    }

    def _skip(reason: str) -> None:
        stats["skipped"] += 1
        by = stats["skipped_by_reason"]
        by[reason] = by.get(reason, 0) + 1

    for fi, fp in enumerate(files):
        with open(fp, encoding="utf-8", errors="replace") as f:
            lines = f.read().split("\n")
        if lines and lines[-1] == "":
            lines.pop()
        for li, line in enumerate(lines):
            if not line.strip():
                continue
            stats["lines"] += 1
            # Only the live file's final line can be a torn partial write;
            # rotation happens on line boundaries.
            torn_candidate = fi == len(files) - 1 and li == len(lines) - 1
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                _skip("torn_tail" if torn_candidate else "parse_error")
                continue
            if not isinstance(rec, dict):
                _skip("parse_error")
                continue
            sv = rec.get("schema_version")
            if sv is None:
                stats["legacy_lines"] += 1  # pre-versioning trace: accepted
            elif not isinstance(sv, int) or sv < 1 or sv > SCHEMA_VERSION:
                _skip("unknown_schema")
                continue
            if rec.get("record") not in ("span", "event"):
                _skip("unknown_record")
                continue
            records.append(rec)
            stats["accepted"] += 1

    with _STATS_LOCK:
        _STATS["ingest_files_total"] += stats["files"]
        _STATS["ingest_lines_total"] += stats["lines"]
        _STATS["ingest_skipped_lines_total"] += stats["skipped"]
        for r, n in stats["skipped_by_reason"].items():
            by = _STATS["ingest_skipped_by_reason"]
            by[r] = by.get(r, 0) + n
    return records, stats


class ReplayWorkload:
    """Ingested recorder records plus the reconstructed fleet views:
    job submissions (kind ``job`` roots + their ``submit`` events),
    serving request arrivals (kind ``request``), and the fault timeline
    (kind ``fault`` spans/events)."""

    def __init__(self, records: List[dict], ingest_stats: Optional[dict] = None):
        self.records = list(records)
        self.ingest = dict(ingest_stats or {})
        self.spans = [r for r in self.records if r.get("record") == "span"]
        self.events = [r for r in self.records if r.get("record") == "event"]
        submit_by_trace: Dict[Any, dict] = {}
        self.faults: List[dict] = []
        self.requests: List[dict] = []
        self.jobs: List[dict] = []
        for e in self.events:
            if e.get("name") == "submit" and e.get("kind") == "scheduler":
                submit_by_trace.setdefault(e.get("trace_id"), e)
            elif e.get("kind") == "fault":
                self.faults.append({
                    "t": float(e.get("ts") or 0.0),
                    "name": e.get("name"),
                    "trace_id": e.get("trace_id"),
                    "attrs": dict(e.get("attrs") or {}),
                })
        for s in self.spans:
            kind = s.get("kind")
            attrs = dict(s.get("attrs") or {})
            if kind == "job":
                sub = submit_by_trace.get(s.get("trace_id"))
                sub_attrs = dict((sub or {}).get("attrs") or {})
                self.jobs.append({
                    "trace_id": s.get("trace_id"),
                    "name": s.get("name"),
                    "t0": float(s.get("t0") or 0.0),
                    "t1": s.get("t1"),
                    "duration_s": s.get("duration_s"),
                    "priority": attrs.get("priority") or sub_attrs.get("priority"),
                    "workload": attrs.get("workload") or sub_attrs.get("workload"),
                    "gang": attrs.get("n_chips") or attrs.get("gang")
                    or attrs.get("full_gang"),
                    "attrs": attrs,
                })
            elif kind == "fault":
                self.faults.append({
                    "t": float(s.get("t0") or 0.0),
                    "name": s.get("name"),
                    "trace_id": s.get("trace_id"),
                    "attrs": attrs,
                })
            elif kind == "request":
                self.requests.append({
                    "t": float(s.get("t0") or 0.0),
                    "name": s.get("name"),
                    "trace_id": s.get("trace_id"),
                    "duration_s": s.get("duration_s"),
                    "attrs": attrs,
                })
        self.faults.sort(key=lambda f: f["t"])
        self.requests.sort(key=lambda r: r["t"])
        self.jobs.sort(key=lambda j: (j["t0"], str(j["name"])))

    @classmethod
    def from_jsonl(cls, path: str) -> "ReplayWorkload":
        records, stats = read_recorder_jsonl(path)
        return cls(records, stats)

    @property
    def t_range(self) -> Tuple[float, float]:
        lo, hi = math.inf, -math.inf
        for s in self.spans:
            t0 = float(s.get("t0") or 0.0)
            t1 = float(s.get("t1") if s.get("t1") is not None else t0)
            lo, hi = min(lo, t0), max(hi, t1)
        for e in self.events:
            ts = float(e.get("ts") or 0.0)
            lo, hi = min(lo, ts), max(hi, ts)
        if lo is math.inf:
            return 0.0, 0.0
        return lo, hi


# -- replay core --------------------------------------------------------------


class TwinEngine:
    """Replays a :class:`ReplayWorkload` onto a fresh deterministic-id
    :class:`FlightRecorder` under one :class:`VirtualClock`, then accounts
    every job trace through the real :class:`GoodputLedger`.

    The replayed recorder (``self.recorder``) carries the same spans,
    events, timestamps and parent links as the source run, so it exports
    the same Perfetto document and decomposes to the same goodput
    categories — the diffability contract the determinism tests gate."""

    def __init__(
        self,
        max_spans: int = 65536,
        max_events: int = 65536,
        id_prefix: str = "twin",
    ):
        self.max_spans = int(max_spans)
        self.max_events = int(max_events)
        self.id_prefix = id_prefix
        self.clock = VirtualClock(0.0)
        self.recorder: Optional[FlightRecorder] = None

    def replay(
        self,
        workload: ReplayWorkload,
        bucket_s: float = 60.0,
        history_buckets: int = 256,
    ) -> Dict[str, Any]:
        t_cpu0 = time.perf_counter()
        self.clock = VirtualClock(0.0)
        # Stream-order ids: record i gets "<prefix>-<i+1>". Every replayed
        # record consumes exactly one factory call (span records always
        # pass an explicit trace_id below, so new_trace_id never fires),
        # which lets parent links be remapped without a dry run.
        n = len(workload.records)
        new_ids = {
            r["span_id"]: f"{self.id_prefix}-{i + 1:08d}"
            for i, r in enumerate(workload.records)
            if r.get("record") == "span" and r.get("span_id")
        }
        counter = {"n": 0}

        def _factory() -> str:
            counter["n"] += 1
            return f"{self.id_prefix}-{counter['n']:08d}"

        rec = FlightRecorder(
            max_spans=self.max_spans,
            max_events=self.max_events,
            clock=self.clock,
            id_factory=_factory,
        )
        self.recorder = rec
        spans_n = events_n = 0
        for r in workload.records:
            parent = r.get("parent_id")
            parent = new_ids.get(parent, parent)
            attrs = dict(r.get("attrs") or {})
            if r.get("record") == "span":
                t0 = float(r.get("t0") or 0.0)
                t1 = r.get("t1")
                t1 = t0 if t1 is None else float(t1)
                self.clock.t = max(self.clock.t, t1)
                rec.record_span(
                    str(r.get("name") or "span"),
                    kind=str(r.get("kind") or "span"),
                    trace_id=r.get("trace_id") or f"{self.id_prefix}-orphan",
                    parent=parent,
                    t0=t0,
                    t1=t1,
                    attrs=attrs,
                )
                spans_n += 1
            else:
                ts = float(r.get("ts") or 0.0)
                self.clock.t = max(self.clock.t, ts)
                rec.event(
                    str(r.get("name") or "event"),
                    kind=str(r.get("kind") or "event"),
                    trace_id=r.get("trace_id"),
                    parent=parent,
                    ts=ts,
                    attrs=attrs,
                )
                events_n += 1

        # Account every job trace through the REAL ledger — the same
        # decomposition live submissions get.
        ledger = GoodputLedger(
            clock=self.clock, bucket_s=bucket_s, history_buckets=history_buckets
        )
        traces: Dict[str, Any] = {}
        for job in workload.jobs:
            tid = job["trace_id"]
            if tid is None or tid in traces:
                continue
            gang = job.get("gang")
            ledger.track(
                tid,
                tenant=str(job["attrs"].get("submitter") or "twin"),
                workload=str(job.get("workload") or "training"),
                full_gang=int(gang) if gang else None,
            )
            now = job["t1"] if job["t1"] is not None else self.clock.t
            d = ledger.finalize(rec, tid, now=float(now))
            if d is None:
                continue
            traces[tid] = {
                "root": job["name"],
                "wall_s": d["wall_s"],
                "goodput_fraction": d["goodput_fraction"],
                "categories": dict(d["categories"]),
                "compile_split": dict(d.get("compile_split") or {}),
            }
        cpu_s = max(time.perf_counter() - t_cpu0, 1e-9)
        t_lo, t_hi = workload.t_range
        fleet_s = max(0.0, t_hi - t_lo)
        speedup = fleet_s / cpu_s
        _bump(
            replays_total=1,
            replayed_spans_total=spans_n,
            replayed_events_total=events_n,
            fleet_seconds_total=fleet_s,
            cpu_seconds_total=cpu_s,
        )
        with _STATS_LOCK:
            _STATS["last_fleet_seconds_per_cpu_second"] = round(speedup, 1)
        return {
            "spans_replayed": spans_n,
            "events_replayed": events_n,
            "records": n,
            "traces": traces,
            "ingest": dict(workload.ingest),
            "fleet_seconds": round(fleet_s, 3),
            "cpu_seconds": round(cpu_s, 6),
            "fleet_seconds_per_cpu_second": round(speedup, 1),
        }


def decomposition_diff(
    source: Dict[str, float], replayed: Dict[str, float], wall_s: float
) -> Dict[str, Any]:
    """Per-category |source − replay| as % of the wall clock (the
    fidelity acceptance metric: every category within 1%)."""
    per = {
        c: round(
            abs(float(source.get(c, 0.0)) - float(replayed.get(c, 0.0)))
            / max(wall_s, 1e-9)
            * 100.0,
            4,
        )
        for c in CATEGORIES
    }
    return {
        "per_category_pct": per,
        "max_error_pct": max(per.values()) if per else 0.0,
    }


# -- synthetic traffic generators ---------------------------------------------


def _open_loop_arrivals(
    rng: random.Random,
    rate_fn: Callable[[float], float],
    duration_s: float,
    n_prefixes: int,
    prefix_len: int,
    mean_new_tokens: float,
    min_new_tokens: int,
    prefill_fn: Optional[Callable[[random.Random], float]],
) -> List[dict]:
    """Shared open-loop arrival core. The draw order (interarrival →
    prefix → [prefill] → n_new) matches the legacy sims' generators
    exactly, so seeded traces reproduce byte-for-byte."""
    out: List[dict] = []
    t = 0.0
    while t < duration_s:
        t += rng.expovariate(rate_fn(t))
        if t >= duration_s:
            break
        pid = rng.randrange(n_prefixes)
        # Prompt = shared prefix tokens + a unique tail (router affinity
        # keys on the first tokens; the tail keeps requests distinct).
        prompt = [pid * prefix_len + i for i in range(prefix_len)]
        prompt.append(10_000 + len(out))
        req: Dict[str, Any] = {"t": t, "prefix_id": pid, "prompt": prompt}
        if prefill_fn is not None:
            req["prefill_units"] = prefill_fn(rng)
        req["n_new"] = max(
            min_new_tokens, int(rng.expovariate(1.0 / mean_new_tokens))
        )
        out.append(req)
    return out


def bursty_arrivals(
    seed: int,
    duration_s: float = 600.0,
    base_rps: float = 1.0,
    burst_rps: float = 14.0,
    burst_every_s: float = 120.0,
    burst_len_s: float = 35.0,
    n_prefixes: int = 4,
    prefix_len: int = 32,
    mean_new_tokens: float = 96,
    min_new_tokens: int = 8,
    prefill_mean_s: Optional[float] = None,
    prefill_min_s: float = 0.3,
    seed_offset: int = 0,
) -> List[dict]:
    """Seeded bursty open-loop arrivals: [{t, prefix_id, prompt, n_new}]
    (+ ``prefill_units`` seconds when ``prefill_mean_s`` is set)."""
    rng = random.Random(seed + seed_offset)

    def rate(t: float) -> float:
        return burst_rps if (t % burst_every_s) < burst_len_s else base_rps

    prefill = None
    if prefill_mean_s is not None:
        def prefill(r: random.Random) -> float:
            return max(prefill_min_s, r.expovariate(1.0 / prefill_mean_s))

    return _open_loop_arrivals(
        rng, rate, duration_s, n_prefixes, prefix_len,
        mean_new_tokens, min_new_tokens, prefill,
    )


def diurnal_arrivals(
    seed: int,
    duration_s: float = 600.0,
    trough_rps: float = 0.5,
    peak_rps: float = 4.0,
    period_s: float = 300.0,
    n_prefixes: int = 4,
    prefix_len: int = 32,
    mean_new_tokens: float = 96,
    min_new_tokens: int = 8,
) -> List[dict]:
    """Sinusoidal day/night arrival rate between trough and peak."""
    rng = random.Random(seed)

    def rate(t: float) -> float:
        phase = 0.5 * (1.0 - math.cos(2.0 * math.pi * t / period_s))
        return trough_rps + (peak_rps - trough_rps) * phase

    return _open_loop_arrivals(
        rng, rate, duration_s, n_prefixes, prefix_len,
        mean_new_tokens, min_new_tokens, None,
    )


def heavy_tail_prefill_arrivals(
    seed: int,
    duration_s: float = 600.0,
    base_rps: float = 0.4,
    burst_rps: float = 3.0,
    burst_every_s: float = 120.0,
    burst_len_s: float = 35.0,
    alpha: float = 1.5,
    prefill_min_s: float = 0.3,
    n_prefixes: int = 4,
    prefix_len: int = 32,
    mean_new_tokens: float = 96,
    min_new_tokens: int = 8,
) -> List[dict]:
    """Bursty arrivals whose prefill cost is Pareto(``alpha``) — the
    heavy-tail regime where a single huge prompt can wedge a symmetric
    replica's slot pool."""
    rng = random.Random(seed)

    def rate(t: float) -> float:
        return burst_rps if (t % burst_every_s) < burst_len_s else base_rps

    def prefill(r: random.Random) -> float:
        return prefill_min_s * r.paretovariate(alpha)

    return _open_loop_arrivals(
        rng, rate, duration_s, n_prefixes, prefix_len,
        mean_new_tokens, min_new_tokens, prefill,
    )


# -- training lane: self-heal vs die-and-restart under chip faults ------------


@dataclasses.dataclass(frozen=True)
class TrainTwinParams:
    """The chaos training-gang scenario knobs (defaults = the seeded
    benchmark; ``benchmarks/chaos.py`` re-exports
    them as module constants)."""

    n_chips: int = 8
    model_axis: int = 2
    min_chips: int = 2
    total_steps: int = 1_000
    step_time_s: float = 0.5
    ckpt_interval_steps: int = 100
    ckpt_save_s: float = 5.0
    resume_admit_s: float = 5.0
    cold_compile_s: float = 15.0
    warm_compile_s: float = 1.5
    die_detect_s: float = 30.0
    die_restart_s: float = 120.0
    chip_recovery_base_s: float = 60.0
    chip_recovery_per_duration_s: float = 30.0
    layout_prefix: str = "chaos"


def chip_fault_timeline(
    seed: int, n_faults: int = 12, params: TrainTwinParams = TrainTwinParams()
) -> List[dict]:
    """Chip-unhealthy events from a seeded plan: (step, device, recovery_s).

    Draws a larger random plan and keeps the chip faults — same seed,
    same trace, every policy replays it identically."""
    plan = FaultPlan.random(
        seed,
        n_faults=n_faults * 3,
        max_step=params.total_steps,
        n_devices=params.n_chips,
    )
    events, seen_steps = [], set()
    for s in plan.specs:
        if s.kind is not FaultKind.CHIP_UNHEALTHY or s.at_step is None:
            continue
        if s.at_step in seen_steps:  # one fault per step keeps the sim simple
            continue
        seen_steps.add(s.at_step)
        events.append({
            "step": int(s.at_step),
            "device": int(s.device_index or 0),
            "recovery_s": params.chip_recovery_base_s
            + params.chip_recovery_per_duration_s * float(s.duration_steps or 1),
        })
    events.sort(key=lambda e: e["step"])
    return events[:n_faults]


def _usable(healthy: int, params: TrainTwinParams) -> int:
    return max(params.min_chips, (healthy // params.model_axis) * params.model_axis)


def _layout_key(use: int, params: TrainTwinParams) -> str:
    """Index key for the shrunk-mesh layout running on ``use`` chips."""
    return f"{params.layout_prefix}|data{use // params.model_axis}xfsdp{params.model_axis}"


def seed_initial_compile(
    index: CompileCacheIndex, params: TrainTwinParams = TrainTwinParams()
) -> None:
    """The job's own startup compile put the full-mesh layout in the cache."""
    key = _layout_key(params.n_chips, params)
    index.record(
        key, params.cold_compile_s, cache_hit=False,
        label=key.split("|", 1)[1], model=params.layout_prefix,
        via=params.layout_prefix,
    )


def _resume_compile(
    index: Optional[CompileCacheIndex], use: int, params: TrainTwinParams
) -> Tuple[float, bool]:
    """Compile cost of a shrink-resume onto ``use`` chips: (seconds, warm)."""
    if index is None:  # index off: a fresh process always compiles cold
        return params.cold_compile_s, False
    key = _layout_key(use, params)
    if index.is_warm(key):
        index.record(key, params.warm_compile_s, cache_hit=True,
                     via=params.layout_prefix)
        return params.warm_compile_s, True
    index.record(key, params.cold_compile_s, cache_hit=False,
                 label=key.split("|", 1)[1], model=params.layout_prefix,
                 via=params.layout_prefix)
    return params.cold_compile_s, False


def _grow_compile(
    index: Optional[CompileCacheIndex], use: int, params: TrainTwinParams
) -> Tuple[float, bool]:
    """Compile cost of a grow-back preempt-resume onto ``use`` chips.

    With the index on, the scheduler precompiles the target layout in the
    background *before* preempting, so the cold compile never lands on
    the critical path — the resume pays only the warm relink either way;
    a never-seen layout is recorded as a background precompile."""
    if index is None:
        return params.cold_compile_s, False
    key = _layout_key(use, params)
    if not index.is_warm(key):
        index.record(key, params.cold_compile_s, cache_hit=False,
                     label=key.split("|", 1)[1], model=params.layout_prefix,
                     via="precompile")
    index.record(key, params.warm_compile_s, cache_hit=True,
                 via=params.layout_prefix)
    return params.warm_compile_s, True


def replay_self_heal(
    events: List[dict],
    params: TrainTwinParams = TrainTwinParams(),
    recorder: Optional[FlightRecorder] = None,
    trace_id: Optional[str] = None,
    compile_index: Optional[CompileCacheIndex] = None,
) -> dict:
    """Self-heal policy over a chip-fault timeline on the virtual clock:
    in-band detect, emergency save, shrink re-admit (zero lost steps),
    grow back when the chip recovers. Records the causal recovery chain
    (detect → emergency_save → requeue → shrink_admit → compile → resume)
    when given a recorder."""
    clock = 0.0
    healthy = params.n_chips
    pending: List[float] = []  # clocks at which a failed chip becomes healthy
    mttrs: List[float] = []
    grow_backs = 0
    degraded_s = 0.0
    warm_resumes = 0
    cold_resumes = 0
    compile_s_total = 0.0
    i = 0
    # Flight-recorder lane (virtual-clock timestamps — the recorder takes
    # explicit t0/t1 everywhere for exactly this). Each fault's recovery
    # chain links causally; a later grow_back chains off the resume.
    root = chain_tail = None
    if recorder is not None:
        trace_id = trace_id or recorder.new_trace_id()
        root = recorder.start_span(
            "job:chaos-self-heal", kind="job", trace_id=trace_id, t0=0.0,
            attrs={"n_chips": params.n_chips, "total_steps": params.total_steps},
        )
    for step in range(1, params.total_steps + 1):
        # Grow back as soon as a chip has recovered: preempt-save-resume at
        # the larger mesh (the scheduler's _maybe_grow pass).
        while pending and pending[0] <= clock and healthy < params.n_chips:
            pending.pop(0)
            healthy += 1
            if _usable(healthy, params) > _usable(healthy - 1, params):
                g_compile_s, g_warm = _grow_compile(
                    compile_index, _usable(healthy, params), params
                )
                g_admit_end = clock + params.ckpt_save_s + params.resume_admit_s
                if recorder is not None:
                    recorder.record_span(
                        "grow_back", kind="admission", trace_id=trace_id,
                        parent=chain_tail or root, t0=clock, t1=g_admit_end,
                        attrs={"step": step, "mesh": _usable(healthy, params)},
                    )
                    recorder.record_span(
                        "compile", kind="compile", trace_id=trace_id,
                        parent=chain_tail or root, t0=g_admit_end,
                        t1=g_admit_end + g_compile_s,
                        attrs={"cache_hit": g_warm,
                               "compile_s": g_compile_s,
                               "layout": _layout_key(_usable(healthy, params), params)},
                    )
                clock = g_admit_end + g_compile_s
                compile_s_total += g_compile_s
                warm_resumes += 1 if g_warm else 0
                cold_resumes += 0 if g_warm else 1
                grow_backs += 1
        use = _usable(healthy, params)
        step_t = params.step_time_s * params.n_chips / use
        clock += step_t
        if use < params.n_chips:
            degraded_s += step_t
        if step % params.ckpt_interval_steps == 0:
            if recorder is not None:
                recorder.record_span(
                    "checkpoint_save", kind="checkpoint_save",
                    trace_id=trace_id, parent=root, t0=clock,
                    t1=clock + params.ckpt_save_s, attrs={"step": step},
                )
            clock += params.ckpt_save_s
        if i < len(events) and step >= events[i]["step"]:
            ev = events[i]
            i += 1
            healthy -= 1
            # Detection is the in-band health check on this very step;
            # emergency save persists `step`, shrink-resume follows. The
            # compile leg is warm iff the index has seen this layout.
            compile_s, warm = _resume_compile(
                compile_index, _usable(healthy, params), params
            )
            down = params.ckpt_save_s + params.resume_admit_s + compile_s
            admit_end = clock + params.ckpt_save_s + params.resume_admit_s
            if recorder is not None:
                detect = recorder.record_span(
                    "detect", kind="fault", trace_id=trace_id, parent=root,
                    t0=clock, t1=clock,
                    attrs={"step": step, "device": ev["device"]},
                )
                save = recorder.record_span(
                    "emergency_save", kind="emergency_save",
                    trace_id=trace_id, parent=detect, t0=clock,
                    t1=clock + params.ckpt_save_s, attrs={"step": step},
                )
                requeue = recorder.record_span(
                    "requeue", kind="scheduler", trace_id=trace_id,
                    parent=save, t0=clock + params.ckpt_save_s,
                    t1=clock + params.ckpt_save_s, attrs={"step": step},
                )
                admit = recorder.record_span(
                    "shrink_admit", kind="admission", trace_id=trace_id,
                    parent=requeue, t0=clock + params.ckpt_save_s, t1=admit_end,
                    attrs={"step": step, "mesh": _usable(healthy, params)},
                )
                comp = recorder.record_span(
                    "compile", kind="compile", trace_id=trace_id,
                    parent=admit, t0=admit_end, t1=admit_end + compile_s,
                    attrs={"cache_hit": warm, "compile_s": compile_s,
                           "layout": _layout_key(_usable(healthy, params), params)},
                )
                chain_tail = recorder.record_span(
                    "resume", kind="supervisor", trace_id=trace_id,
                    parent=comp, t0=clock + down, t1=clock + down,
                    attrs={"from_step": step},
                )
            clock += down
            compile_s_total += compile_s
            warm_resumes += 1 if warm else 0
            cold_resumes += 0 if warm else 1
            mttrs.append(step_t + down)
            pending.append(clock + ev["recovery_s"])
            pending.sort()
    wall = clock
    if root is not None:
        root.end(t1=wall, faults=len(mttrs), grow_backs=grow_backs)
    return {
        "policy": "self-heal",
        "compile_index": compile_index is not None,
        "wall_s": round(wall, 1),
        "steps_run": params.total_steps,
        "lost_steps": 0,
        "faults": len(mttrs),
        "grow_backs": grow_backs,
        "degraded_step_s": round(degraded_s, 1),
        "warm_resumes": warm_resumes,
        "cold_resumes": cold_resumes,
        "compile_s_total": round(compile_s_total, 1),
        "mttr_mean_s": round(sum(mttrs) / len(mttrs), 2) if mttrs else 0.0,
        "mttr_max_s": round(max(mttrs), 2) if mttrs else 0.0,
        "goodput": round(params.total_steps * params.step_time_s / wall, 4),
    }


def replay_die_and_restart(
    events: List[dict], params: TrainTwinParams = TrainTwinParams()
) -> dict:
    """Die-and-restart policy: external poll detect, wait for the chip,
    cold restart from the last periodic checkpoint (steps lost)."""
    clock = 0.0
    step = 0
    last_ckpt = 0
    lost_steps = 0
    steps_run = 0
    mttrs: List[float] = []
    i = 0
    while step < params.total_steps:
        clock += params.step_time_s
        step += 1
        steps_run += 1
        if step % params.ckpt_interval_steps == 0:
            last_ckpt = step
            clock += params.ckpt_save_s
        if i < len(events) and step >= events[i]["step"]:
            ev = events[i]
            i += 1  # each fault fires once, even though step rolls back
            lost = step - last_ckpt
            lost_steps += lost
            # Nothing runs until the chip is replaced (full mesh required),
            # then a cold restart replays everything since the checkpoint.
            down = params.die_detect_s + ev["recovery_s"] + params.die_restart_s
            clock += down
            mttrs.append(down + lost * params.step_time_s)
            step = last_ckpt
    wall = clock
    return {
        "policy": "die-and-restart",
        "wall_s": round(wall, 1),
        "steps_run": steps_run,
        "lost_steps": lost_steps,
        "faults": len(mttrs),
        "grow_backs": 0,
        "degraded_step_s": 0.0,
        "mttr_mean_s": round(sum(mttrs) / len(mttrs), 2) if mttrs else 0.0,
        "mttr_max_s": round(max(mttrs), 2) if mttrs else 0.0,
        "goodput": round(params.total_steps * params.step_time_s / wall, 4),
    }


def goodput_lane(
    recorder: FlightRecorder,
    trace_id: str,
    wall: float,
    full_gang: int = 8,
    tenant: str = "chaos",
    goodput_target: float = 0.88,
    short_window_s: float = 120.0,
    long_window_s: float = 600.0,
    warning_burn: float = 1.5,
    page_burn: float = 3.0,
) -> dict:
    """Account a recorded training trace through the REAL goodput ledger
    (the same decomposition live submissions get), then replay the SLO
    burn-rate alerter over the run's virtual clock.

    Alert transitions land as ``slo_alert`` events on the recorder's
    ``fleet`` timeline and per-window counter samples as a Perfetto
    counter track — both ride the same Chrome-trace export as the
    recovery chains they explain."""
    ledger = GoodputLedger(clock=lambda: wall, bucket_s=60.0,
                           history_buckets=256)
    ledger.track(trace_id, tenant=tenant, workload="training",
                 full_gang=full_gang)
    d = ledger.finalize(recorder, trace_id, now=wall)
    assert d is not None
    cats = d["categories"]
    sum_error_pct = abs(sum(cats.values()) - d["wall_s"]) / d["wall_s"] * 100
    alerter = SLOBurnRateAlerter(
        ledger,
        goodput_target=goodput_target,
        short_window_s=short_window_s,
        long_window_s=long_window_s,
        warning_burn=warning_burn,
        page_burn=page_burn,
        recorder=recorder,
        clock=lambda: wall,
    )
    progression = ["ok"]
    t = 0.0
    while t <= wall + 60.0:
        out = alerter.evaluate(now=t)
        g = out["goodput"]
        if g["state"] != progression[-1]:
            progression.append(g["state"])
        recorder.counter(
            "goodput_burn",
            {
                "goodput_fraction_short": g["short_fraction"] or 1.0,
                "burn_short": g["short_burn"] or 0.0,
                "burn_long": g["long_burn"] or 0.0,
            },
            trace_id=trace_id,
            ts=t,
        )
        t += 60.0
    split = d.get("compile_split") or {}
    return {
        "breakdown_s": {c: round(cats[c], 2) for c in CATEGORIES},
        "breakdown_pct": {
            c: round(100.0 * cats[c] / d["wall_s"], 2) for c in CATEGORIES
        },
        "compile_split_s": {
            "warm_s": round(float(split.get("warm_s", 0.0)), 2),
            "cold_s": round(float(split.get("cold_s", 0.0)), 2),
        },
        "wall_s": round(d["wall_s"], 1),
        "goodput_fraction": round(d["goodput_fraction"], 4),
        "sum_error_pct": round(sum_error_pct, 6),
        "slo": {
            "target": alerter.goodput_target,
            "warning_burn": alerter.warning_burn,
            "page_burn": alerter.page_burn,
            "progression": progression,
            "alert_count": len(alerter.alerts),
            "alerts": list(alerter.alerts),
        },
    }


# -- heterogeneous sharding lane ----------------------------------------------


@dataclasses.dataclass(frozen=True)
class HeteroTwinParams:
    """Slow-host gang scenario: one host runs sustained-slow; the
    synchronous gang gates every step on it unless the heterogeneity
    plane reweights the per-process row assignment."""

    hosts: int = 8
    global_micro: int = 128
    steps: int = 400
    tail_steps: int = 100       # steady-state window: the last N steps
    check_every: int = 10       # rebalance consult cadence (steps)
    shrink_at_step: int = 25    # when the shrink policy evicts the slow host
    step_time_s: float = 0.5
    # Reported per-step stall while uniformly loaded; the slow host's true
    # rate is STEP/(STEP+stall) = 0.75 — the headline 25%-degraded host.
    slow_s: float = 0.5 / 3.0
    ckpt_save_s: float = 5.0
    resume_admit_s: float = 5.0
    cold_compile_s: float = 15.0


def host_slow_plan(
    seed: int, params: HeteroTwinParams = HeteroTwinParams()
) -> FaultPlan:
    """Sustained host-slow on one seeded host: fires every step."""
    host = random.Random(seed).randrange(params.hosts)
    return FaultPlan(seed=seed, specs=[
        FaultSpec(
            kind=FaultKind.HOST_SLOW, at_step=1, device_index=host,
            slow_s=round(params.slow_s, 6), count=params.steps,
        )
    ])


def replay_hetero(
    policy: str,
    plan: FaultPlan,
    params: HeteroTwinParams = HeteroTwinParams(),
    recorder: Optional[FlightRecorder] = None,
    trace_id: Optional[str] = None,
) -> dict:
    """Replay ``plan`` under one policy on the virtual clock.

    The injector is the only degradation source: a consumed HOST_SLOW spec
    both slows the simulated host (truth) and feeds the ThroughputTracker
    (signal) — exactly the supervisor's ``take_host_slow`` seam."""
    inj = FaultInjector(plan)
    inj.arm()
    rate = [1.0] * params.hosts        # ground-truth relative rates
    rows_u = params.global_micro // params.hosts
    vclock = 0.0
    tracker = hetero_mod.ThroughputTracker(params.hosts)
    reb = hetero_mod.HeteroRebalancer(
        tracker, params.global_micro, dry_run=False, cooldown_s=30.0,
        min_gain=0.01, clock=lambda: vclock,
        recorder=recorder, trace_id=trace_id,
    )
    assignment = list(reb.assignment)
    active = list(range(params.hosts))
    shrunk = False
    downtime_s = 0.0
    rebalance_step: Optional[int] = None
    ideal_wall = 0.0
    tail_wall = tail_ideal = 0.0
    for step in range(1, params.steps + 1):
        spec = inj.take_host_slow(step)
        if spec is not None:
            idx = int(spec.device_index or 0)
            rate[idx] = params.step_time_s / (params.step_time_s + float(spec.slow_s))
            tracker.note_host_slow(idx, float(spec.slow_s), params.step_time_s)
        if policy == "shrink" and not shrunk and step >= params.shrink_at_step:
            # Evict the slow host: emergency save + re-admit + cold compile,
            # then a smaller uniform gang carries the full global batch.
            shrunk = True
            slow_host = min(range(params.hosts), key=lambda h: rate[h])
            active = [h for h in range(params.hosts) if h != slow_host]
            assignment = hetero_mod.uniform_assignment(
                params.global_micro, len(active)
            )
            downtime_s = params.ckpt_save_s + params.resume_admit_s + params.cold_compile_s
            vclock += downtime_s
        # Synchronous gang: the step ends when the slowest member finishes
        # its rows; a host's nominal pace is rows_u rows per step_time_s.
        step_s = max(
            assignment[j] * params.step_time_s / (rows_u * rate[h])
            for j, h in enumerate(active)
        )
        ideal_s = params.global_micro * params.step_time_s / (rows_u * sum(rate))
        vclock += step_s
        ideal_wall += ideal_s
        tracker.observe_step(step_s)
        if policy == "rebalance-on" and step % params.check_every == 0:
            r_plan = reb.maybe_rebalance(step)
            if r_plan is not None:
                assignment = list(r_plan.assignment)
                if rebalance_step is None:
                    rebalance_step = step
        if step > params.steps - params.tail_steps:
            tail_wall += step_s
            tail_ideal += ideal_s
    return {
        "policy": policy,
        "wall_s": round(vclock, 1),
        "ideal_wall_s": round(ideal_wall, 1),
        "downtime_s": round(downtime_s, 1),
        "goodput": round(ideal_wall / vclock, 4),
        "steady_goodput": round(tail_ideal / tail_wall, 4),
        "assignment": list(assignment),
        "active_hosts": len(active),
        "rebalance_step": rebalance_step,
        "rebalancer": reb.stats() if policy == "rebalance-on" else None,
    }


def run_hetero_ab(
    seed: int = 0,
    params: HeteroTwinParams = HeteroTwinParams(),
    recorder: Optional[FlightRecorder] = None,
) -> dict:
    """Rebalance-on vs rebalance-off vs shrink on one seeded slow-host plan."""
    plan = host_slow_plan(seed, params)
    trace_id = recorder.new_trace_id() if recorder is not None else None
    on = replay_hetero("rebalance-on", plan, params, recorder=recorder,
                       trace_id=trace_id)
    off = replay_hetero("rebalance-off", plan, params)
    shrink = replay_hetero("shrink", plan, params)
    return {
        "seed": seed,
        "params": {
            "n_hosts": params.hosts,
            "global_micro": params.global_micro,
            "steps": params.steps,
            "slow_host_rate": round(
                params.step_time_s / (params.step_time_s + params.slow_s), 4
            ),
            "slow_host": int(plan.specs[0].device_index or 0),
            "check_every_steps": params.check_every,
        },
        "rebalance_on": on,
        "rebalance_off": off,
        "shrink": shrink,
        "steady_goodput_on": on["steady_goodput"],
        "steady_goodput_off": off["steady_goodput"],
        "steady_goodput_shrink": shrink["steady_goodput"],
        "goodput_recovered": round(
            on["steady_goodput"] - off["steady_goodput"], 4
        ),
    }


# -- serving lane: open-loop tick driver + autoscaled fleet -------------------


def percentile(vals: List[float], q: float) -> float:
    if not vals:
        return 0.0
    vals = sorted(vals)
    return vals[min(int(q * (len(vals) - 1)), len(vals) - 1)]


def run_open_loop(
    trace: List[dict],
    dt: float,
    duration_s: float,
    pending: Callable[[], Any],
    arrive: Callable[[dict], None],
    tick: Callable[[float], None],
    control: Optional[Callable[[float], None]] = None,
    control_period_s: float = 1.0,
    safety_factor: float = 3.0,
) -> float:
    """The shared open-loop discrete-event driver every serving scenario
    runs on: deliver arrivals due by ``t``, run the control-plane closure
    on its cadence, advance the world one ``dt`` tick — until the trace
    is exhausted AND ``pending()`` is falsy. ``safety_factor`` bounds a
    sim bug from spinning forever. Returns the final virtual time."""
    idx, t, next_control = 0, 0.0, 0.0
    while t < duration_s or pending():
        if t > duration_s * safety_factor:
            break
        while idx < len(trace) and trace[idx]["t"] <= t:
            arrive(trace[idx])
            idx += 1
        if control is not None and t >= next_control:
            next_control = t + control_period_s
            control(t)
        tick(t)
        t += dt
    return t


# The replica model's rates, one place for the three serving lanes that
# share them. ASSUMED, not measured: the lanes gate control-plane LOGIC
# (routing, autoscaling, residency, spill) on a virtual clock, and a ratio
# one of them reports is that model's output at these rates, never a speed
# of the chip. (What the chip reads is PERF_LEDGER.jsonl's: its
# mistral-7b.serve-chat cell decodes ~144 tokens/s a slot and has a p90
# time to first token of ~0.35 s on PR 45's line.)
REPLICA_SLOTS = 8
REPLICA_TOKENS_PER_SLOT_S = 30.0
REPLICA_PREFILL_S = 1.2
REPLICA_PREFILL_HIT_S = 0.15


@dataclasses.dataclass(frozen=True)
class ServingTwinParams:
    """Autoscaled serving-fleet scenario knobs (defaults = the seeded
    benchmark; ``benchmarks/serving_fleet_sim.py`` re-exports them)."""

    duration_s: float = 600.0
    dt_s: float = 0.05
    control_period_s: float = 1.0
    slots: int = REPLICA_SLOTS
    tokens_per_slot_s: float = REPLICA_TOKENS_PER_SLOT_S
    degraded_fraction: float = 0.4
    prefill_s: float = REPLICA_PREFILL_S
    prefill_hit_s: float = REPLICA_PREFILL_HIT_S
    startup_delay_s: float = 25.0
    chips_per_replica: int = 1
    prefix_len: int = 32
    p99_slo_ms: float = 25_000.0
    warmup_s: float = 120.0


class SlotReplica:
    """Capacity model of one serving replica, the one every serving lane
    runs: a slot pool, a prefill leg that drains, then a per-slot decode
    rate. The LANE decides each admission's prefill leg (cold, resident,
    host-rehydrated, plus a draft's propose leg) and its rate multiple
    (a speculative speedup) — residency and spill are the lanes' policies
    under test — so the replica only runs slots and stamps
    ``first_token_at`` / ``done_at`` on the request."""

    def __init__(self, rid: str, slots: int, rate: float,
                 ready_at: float = 0.0):
        self.rid = rid
        self.slots = slots
        self.rate = rate                  # tokens/s of one decoding slot
        self.ready_at = ready_at
        self.active: List[dict] = []      # {req, prefill_left, tokens_left, rate_mult}
        self.tokens_out = 0.0
        self.draining = False

    def ready(self, now: float) -> bool:
        return now >= self.ready_at

    def free_slots(self, now: float) -> int:
        if not self.ready(now) or self.draining:
            return 0
        return self.slots - len(self.active)

    def admit(self, req: dict, prefill_s: float, rate_mult: float = 1.0) -> None:
        self.active.append({
            "req": req,
            "prefill_left": float(prefill_s),
            "tokens_left": float(req["n_new"]),
            "rate_mult": float(rate_mult),
        })

    def step(self, now: float, dt: float, done: List[dict]) -> None:
        if not self.ready(now):
            return
        for sl in list(self.active):
            if sl["prefill_left"] > 0:
                sl["prefill_left"] -= dt
                if sl["prefill_left"] <= 0:
                    # First token lands as prefill drains (the prefill
                    # logits seed it) — the TTFT stamp the A/Bs gate on.
                    sl["req"]["first_token_at"] = now
                continue
            produced = min(self.rate * sl["rate_mult"] * dt,
                           sl["tokens_left"])
            sl["tokens_left"] -= produced
            self.tokens_out += produced
            if sl["tokens_left"] <= 0:
                sl["req"]["done_at"] = now
                sl["req"]["replica"] = self.rid
                done.append(sl["req"])
                self.active.remove(sl)

    def router_stats(self, now: float) -> dict:
        # tokens/sec the router would measure: rate × busy slots (plus a
        # trickle when idle so a fresh replica is not weight-zero).
        busy = sum(1 for s in self.active if s["prefill_left"] <= 0)
        return {
            "tokens_per_sec": self.rate * max(busy, 0.2),
            "free_slots": self.free_slots(now),
            "slots": self.slots,
        }


def replay_serving_fleet(
    trace: List[dict],
    autoscale: bool,
    autoscaler_cfg,
    params: ServingTwinParams = ServingTwinParams(),
) -> dict:
    """Autoscaled (or static-1) fleet over an open-loop trace, driven by
    the REAL FleetRouter + ReplicaAutoscaler on the twin's tick driver."""
    from tpu_engine.serving_fleet import FleetRouter, ReplicaAutoscaler

    router = FleetRouter(affinity_tokens=params.prefix_len)
    scaler = ReplicaAutoscaler(autoscaler_cfg)
    def replica(rid: str, rate_fraction: float, ready_at: float) -> SlotReplica:
        return SlotReplica(rid, params.slots,
                           params.tokens_per_slot_s * rate_fraction, ready_at)

    replicas: Dict[str, SlotReplica] = {
        # Replica 0 is the degraded host — present from t=0 in both modes;
        # in static mode it is the whole fleet.
        "r0": replica("r0", params.degraded_fraction, 0.0)
    }
    # The lane's residency policy: a replica skips most of the prefill of
    # a prefix it has seen before (unbounded — the prefix-plane lane is
    # the one that bounds it).
    seen: Dict[str, set] = collections.defaultdict(set)
    state = {"next_rid": 1, "chip_seconds": 0.0}
    queue: List[dict] = []
    done: List[dict] = []
    replica_trace: List[tuple] = []

    def control(t: float) -> None:
        up = {
            r.rid: r.router_stats(t)
            for r in replicas.values()
            if r.ready(t) and not r.draining
        }
        router.update(up)
        ready_n = len(up)
        # Change-point trace: one entry per replica-count transition
        # keeps the result readable.
        if not replica_trace or replica_trace[-1][1] != ready_n:
            replica_trace.append((round(t, 1), ready_n))
        if autoscale and ready_n > 0:
            lat = [(r["done_at"] - r["t"]) * 1000.0 for r in done[-256:]]
            desired = scaler.observe(
                t, len(queue), percentile(lat, 0.99) if lat else None, ready_n
            )
            booting = sum(
                1 for r in replicas.values()
                if not r.ready(t) and not r.draining
            )
            while desired > ready_n + booting:
                rid = f"r{state['next_rid']}"
                replicas[rid] = replica(rid, 1.0, t + params.startup_delay_s)
                state["next_rid"] += 1
                booting += 1
            if desired < ready_n:
                # Drain the emptiest ready replica (never the last one).
                cands = sorted(
                    (r for r in replicas.values()
                     if r.ready(t) and not r.draining and r.rid != "r0"),
                    key=lambda r: len(r.active),
                )
                for r in cands[: ready_n - desired]:
                    r.draining = True

    def tick(t: float) -> None:
        # Dispatch through the real router (affinity keys on the prefix).
        # Route only while the fleet has a free slot — an overloaded fleet
        # must queue, not spin the router on unplaceable requests.
        free_total = sum(r.free_slots(t) for r in replicas.values())
        placed = 0
        while queue and free_total > 0:
            req = queue[0]
            rid = router.route(req["prompt"])
            rep = replicas.get(rid) if rid else None
            if rep is not None and rep.free_slots(t) > 0:
                queue.pop(0)
                req["prefix_hit"] = req["prefix_id"] in seen[rid]
                seen[rid].add(req["prefix_id"])
                rep.admit(req, params.prefill_hit_s if req["prefix_hit"]
                          else params.prefill_s)
                free_total -= 1
                placed += 1
            else:
                # Router picked a full/draining replica: stop this tick,
                # weights refresh at the next control period.
                break
            if placed > params.slots * len(replicas):
                break
        for r in list(replicas.values()):
            r.step(t, params.dt_s, done)
            if r.draining and not r.active:
                del replicas[r.rid]
        state["chip_seconds"] += params.dt_s * params.chips_per_replica * sum(
            1 for r in replicas.values() if r.ready(t)
        )

    run_open_loop(
        trace,
        dt=params.dt_s,
        duration_s=params.duration_s,
        pending=lambda: queue or any(r.active for r in replicas.values()),
        arrive=queue.append,
        tick=tick,
        control=control,
        control_period_s=params.control_period_s,
        safety_factor=3.0,
    )

    lat_ms = [
        (r["done_at"] - r["t"]) * 1000.0 for r in done
        if r["t"] >= params.warmup_s
    ]
    # Count tokens from completed requests, not replica counters — drained
    # replicas leave the dict and would take their counters with them.
    total_tokens = float(sum(req["n_new"] for req in done))
    makespan = max((r["done_at"] for r in done), default=params.dt_s)
    p99 = percentile(lat_ms, 0.99)
    return {
        "completed": len(done),
        "total_tokens": total_tokens,
        "tokens_per_sec": total_tokens / makespan,
        "tokens_per_sec_per_chip": total_tokens
        / max(state["chip_seconds"], params.dt_s),
        "p50_ms": round(percentile(lat_ms, 0.50), 1),
        "p99_ms": round(p99, 1),
        "p99_within_slo": p99 <= params.p99_slo_ms,
        "makespan_s": round(makespan, 1),
        "replica_trace": replica_trace,
        "max_replicas_used": max(n for _, n in replica_trace),
        "prefix_hit_rate": round(
            sum(1 for r in done if r.get("prefix_hit")) / max(len(done), 1), 3
        ),
        "router": router.stats(),
        "autoscaler": scaler.stats(),
    }


def serving_metrics(
    done: List[dict],
    ttfts: List[float],
    warmup_s: float = 120.0,
    total_chips: int = 8,
    dt_s: float = 0.05,
) -> dict:
    """Steady-state latency/TTFT percentiles + throughput of one serving
    run (the symmetric-vs-disagg A/B's shared report shape)."""
    lat_ms = [(r["done_at"] - r["t"]) * 1000.0 for r in done
              if r["t"] >= warmup_s]
    steady_ttfts = [
        (r["first_token_at"] - r["t"]) * 1000.0 for r in done
        if r["t"] >= warmup_s and "first_token_at" in r
    ]
    total_tokens = float(sum(r["n_new"] for r in done))
    makespan = max((r["done_at"] for r in done), default=dt_s)
    return {
        "completed": len(done),
        "total_tokens": total_tokens,
        "tokens_per_sec": round(total_tokens / makespan, 2),
        "tokens_per_sec_per_chip": round(
            total_tokens / (makespan * total_chips), 2),
        "ttft_p50_ms": round(percentile(steady_ttfts, 0.50), 1),
        "ttft_p99_ms": round(percentile(steady_ttfts, 0.99), 1),
        "p50_ms": round(percentile(lat_ms, 0.50), 1),
        "p99_ms": round(percentile(lat_ms, 0.99), 1),
        "makespan_s": round(makespan, 1),
    }


# -- warm-admission lane ------------------------------------------------------


def warm_admission_lane(
    jobs: List[Tuple[str, float]],
    prefer_warm: bool,
    cold_compile_s: float = 15.0,
    warm_compile_s: float = 1.5,
) -> dict:
    """Serve ``jobs`` (layout key, work seconds) through one slot.

    Every job's service time is compile + work; the compile leg consults a
    fresh :class:`CompileCacheIndex` — cold the first time a layout is
    seen, warm after. ``prefer_warm`` is the cache-aware admission policy:
    among queued jobs, the first whose layout the index says is warm is
    admitted ahead of the FIFO head (ties broken FIFO)."""
    index = CompileCacheIndex(path=None, default_cold_s=cold_compile_s)
    queue = list(range(len(jobs)))
    clock = 0.0
    waits: List[float] = []
    cold_compiles = 0
    while queue:
        pick = 0
        if prefer_warm:
            pick = next(
                (qi for qi, j in enumerate(queue)
                 if index.is_warm(jobs[j][0])),
                0,
            )
        j = queue.pop(pick)
        layout, work_s = jobs[j]
        waits.append(clock)
        if index.is_warm(layout):
            compile_s = warm_compile_s
            index.record(layout, compile_s, cache_hit=True, via="sim")
        else:
            compile_s = cold_compile_s
            cold_compiles += 1
            index.record(layout, compile_s, cache_hit=False,
                         label=layout.split("|", 1)[1], model="sim", via="sim")
        clock += compile_s + work_s
    return {
        "mean_wait_s": round(sum(waits) / len(waits), 2),
        "makespan_s": round(clock, 2),
        "cold_compiles": cold_compiles,
        "warm_hits": len(jobs) - cold_compiles,
    }


# -- A/B scorecard layer ------------------------------------------------------


def _flatten_numeric(d: Dict[str, Any]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for k, v in d.items():
        if isinstance(v, bool) or isinstance(v, (int, float)):
            out[k] = float(v)
    return out


def ab_scorecard(
    variants: Dict[str, Any],
    runner: Callable[[str, Any], Dict[str, Any]],
    label: str = "twin-ab",
) -> Dict[str, Any]:
    """Run ``runner(name, cfg)`` once per variant over the same ingested
    workload; the first variant is the baseline. One JSON artifact:
    per-variant metrics plus numeric deltas vs the baseline."""
    results: Dict[str, Dict[str, Any]] = {}
    cpu_s: Dict[str, float] = {}
    for name, cfg in variants.items():
        c0 = time.perf_counter()
        results[name] = runner(name, cfg)
        cpu_s[name] = round(time.perf_counter() - c0, 4)
    base_name = next(iter(results))
    base = _flatten_numeric(results[base_name])
    deltas: Dict[str, Dict[str, float]] = {}
    for name, res in results.items():
        if name == base_name:
            continue
        flat = _flatten_numeric(res)
        deltas[name] = {
            k: round(flat[k] - base[k], 6) for k in flat if k in base
        }
    _bump(ab_runs_total=1)
    return {
        "label": label,
        "baseline": base_name,
        "variants": results,
        "deltas_vs_baseline": deltas,
        "cpu_s": cpu_s,
    }


def default_policy_scorecard(seed: int = 0, n_faults: int = 12) -> dict:
    """A real policy question answered on one ingested fault timeline:
    checkpoint-interval 50/100/200 × compile-index on/off, each variant
    replayed through the full self-heal lane + goodput ledger + SLO
    alerter. The baseline is the shipped config (interval 100, index on)."""
    base = TrainTwinParams()
    events = chip_fault_timeline(seed, n_faults, base)
    variants: Dict[str, dict] = {
        "ckpt100_index_on": {"params": base, "compile_index": True},
        "ckpt50_index_on": {
            "params": dataclasses.replace(base, ckpt_interval_steps=50),
            "compile_index": True,
        },
        "ckpt200_index_on": {
            "params": dataclasses.replace(base, ckpt_interval_steps=200),
            "compile_index": True,
        },
        "ckpt100_index_off": {"params": base, "compile_index": False},
    }

    def runner(name: str, cfg: dict) -> dict:
        params: TrainTwinParams = cfg["params"]
        rec = FlightRecorder(
            max_spans=16384, max_events=16384, clock=lambda: 0.0,
            id_factory=deterministic_ids(name),
        )
        tid = rec.new_trace_id()
        index = None
        if cfg["compile_index"]:
            index = CompileCacheIndex(
                path=None, default_cold_s=params.cold_compile_s
            )
            seed_initial_compile(index, params)
        heal = replay_self_heal(
            events, params, recorder=rec, trace_id=tid, compile_index=index
        )
        gp = goodput_lane(rec, tid, heal["wall_s"], full_gang=params.n_chips)
        return {
            "ckpt_interval_steps": params.ckpt_interval_steps,
            "compile_index": cfg["compile_index"],
            "wall_s": heal["wall_s"],
            "goodput_fraction": gp["goodput_fraction"],
            "productive_pct": gp["breakdown_pct"]["productive"],
            "checkpoint_pct": gp["breakdown_pct"]["checkpoint_save"],
            "compile_pct": gp["breakdown_pct"]["compile"],
            "mttr_mean_s": heal["mttr_mean_s"],
            "warm_resumes": heal["warm_resumes"],
            "cold_resumes": heal["cold_resumes"],
            "slo_alerts": gp["slo"]["alert_count"],
        }

    card = ab_scorecard(
        variants, runner, label="chaos-ckpt-interval-x-compile-index"
    )
    card["seed"] = seed
    card["n_faults"] = n_faults
    return card


def admission_policy_scorecard(seed: int = 0, n_jobs: int = 16) -> dict:
    """Queue-wait A/B on one seeded job list: strict FIFO vs the
    cache-aware warm-preferring admission order."""
    rng = random.Random(seed)
    layouts = [f"sim|data{g}xfsdp2" for g in (1, 2, 4)]
    jobs = [
        (rng.choice(layouts), round(rng.uniform(4.0, 12.0), 2))
        for _ in range(n_jobs)
    ]
    return ab_scorecard(
        {"fifo": False, "warm_preferring": True},
        lambda name, prefer_warm: warm_admission_lane(jobs, prefer_warm),
        label="admission-fifo-vs-warm",
    )


# -- fidelity + bench wiring --------------------------------------------------


def replay_fidelity(seed: int = 0, n_faults: int = 12) -> dict:
    """The acceptance loop end to end: record a real self-heal run to
    JSONL, ingest it, replay it on the twin, and diff the replayed
    goodput decomposition against the source run's (per category, % of
    wall). Also measures replay throughput in simulated fleet-seconds
    per CPU-second."""
    params = TrainTwinParams()
    with tempfile.TemporaryDirectory(prefix="twin_fidelity_") as root:
        path = os.path.join(root, "trace.jsonl")
        rec = FlightRecorder(
            max_spans=16384, max_events=16384, clock=lambda: 0.0,
            persist_path=path, persist_max_bytes=64 * 1024 * 1024,
        )
        tid = rec.new_trace_id()
        index = CompileCacheIndex(path=None, default_cold_s=params.cold_compile_s)
        seed_initial_compile(index, params)
        events = chip_fault_timeline(seed, n_faults, params)
        heal = replay_self_heal(
            events, params, recorder=rec, trace_id=tid, compile_index=index
        )
        source = goodput_lane(rec, tid, heal["wall_s"], full_gang=params.n_chips)
        workload = ReplayWorkload.from_jsonl(path)
    engine = TwinEngine()
    out = engine.replay(workload)
    twin_side = out["traces"].get(tid) or {}
    diff = decomposition_diff(
        source["breakdown_s"], twin_side.get("categories") or {},
        source["wall_s"],
    )
    return {
        "seed": seed,
        "wall_s": source["wall_s"],
        "source_goodput_fraction": source["goodput_fraction"],
        "replay_goodput_fraction": round(
            float(twin_side.get("goodput_fraction") or 0.0), 4
        ),
        "per_category_error_pct": diff["per_category_pct"],
        "max_error_pct": diff["max_error_pct"],
        "spans_replayed": out["spans_replayed"],
        "events_replayed": out["events_replayed"],
        "ingest": out["ingest"],
        "fleet_seconds": out["fleet_seconds"],
        "cpu_seconds": out["cpu_seconds"],
        "fleet_seconds_per_cpu_second": out["fleet_seconds_per_cpu_second"],
    }


def twin_replay_gates(seed: int = 0) -> Dict[str, bool]:
    """The replay engine's exit gates on the seeded chaos trace: the replay
    reproduces the recorded run's goodput decomposition, runs far faster
    than the fleet it replays, and both policy A/Bs measure a difference.
    Gates only: the numbers behind them are :func:`replay_fidelity`'s and
    the two scorecards'."""
    fid = replay_fidelity(seed=seed)
    variants = default_policy_scorecard(seed=seed)["variants"]
    admission = admission_policy_scorecard(seed=seed)["variants"]
    return {
        "replay_within_1pct": fid["max_error_pct"] < 1.0,
        "replay_fast_enough": fid["fleet_seconds_per_cpu_second"] >= 1000.0,
        "policy_delta_measured": (
            variants["ckpt50_index_on"]["goodput_fraction"]
            != variants["ckpt200_index_on"]["goodput_fraction"]
        ),
        "warm_beats_fifo": (
            admission["warm_preferring"]["mean_wait_s"]
            < admission["fifo"]["mean_wait_s"]
        ),
    }


# -- historian lane ------------------------------------------------------------

_HISTORIAN_FIDELITY_AGGS = ("avg", "min", "max", "last", "sum")


def _fault_incidents(correlator: "historian_mod.IncidentCorrelator") -> List[dict]:
    return [
        i for i in correlator.incidents(limit=0) if i["trigger"] == "fault"
    ]


def _incident_chain_ok(inc: dict) -> bool:
    """detect → action → resolution, in timestamp order, resolved."""
    roles = [e["role"] for e in inc["timeline"]]
    if "detect" not in roles or "action" not in roles or "resolution" not in roles:
        return False
    t_detect = min(e["ts"] for e in inc["timeline"] if e["role"] == "detect")
    t_action = min(e["ts"] for e in inc["timeline"] if e["role"] == "action")
    t_resol = min(e["ts"] for e in inc["timeline"] if e["role"] == "resolution")
    return inc["state"] == "resolved" and t_detect <= t_action <= t_resol


def historian_lane(seed: int = 0, n_faults: int = 12) -> dict:
    """Record a chaos self-heal + goodput run to JSONL, build the live
    historian series and incident set from the in-memory recorder, then
    rebuild both from the persisted JSONL alone and diff — the
    acceptance loop for the historian: a replayed trace must yield the
    same metric history (per queried aggregate, within 1%) and the same
    causally-chained incidents the live run produced, and every injected
    fault must land in exactly one resolved detect→action→resolution
    incident."""
    params = TrainTwinParams()
    with tempfile.TemporaryDirectory(prefix="twin_historian_") as root:
        path = os.path.join(root, "trace.jsonl")
        rec = FlightRecorder(
            max_spans=16384, max_events=16384, clock=lambda: 0.0,
            persist_path=path, persist_max_bytes=64 * 1024 * 1024,
            id_factory=deterministic_ids("hist"),
        )
        tid = rec.new_trace_id()
        index = CompileCacheIndex(path=None, default_cold_s=params.cold_compile_s)
        seed_initial_compile(index, params)
        events = chip_fault_timeline(seed, n_faults, params)
        heal = replay_self_heal(
            events, params, recorder=rec, trace_id=tid, compile_index=index
        )
        gp = goodput_lane(rec, tid, heal["wall_s"], full_gang=params.n_chips)
        wall = heal["wall_s"]
        counter_events = rec.events(kind="counter", limit=0)
        live_hist = historian_mod.MetricHistorian(clock=lambda: 0.0)
        t_ingest = time.perf_counter()
        ingested = live_hist.ingest_counter_events(counter_events)
        ingest_s = max(time.perf_counter() - t_ingest, 1e-9)
        live_corr = historian_mod.IncidentCorrelator(
            clock=lambda: wall, stale_after_s=1e9,
        )
        live_corr.ingest(recorder=rec, now=wall)
        records, ingest_stats = read_recorder_jsonl(path)
    replay_hist = historian_mod.MetricHistorian(clock=lambda: 0.0)
    replay_hist.ingest_jsonl_records(records)
    replay_corr = historian_mod.IncidentCorrelator(
        clock=lambda: wall, stale_after_s=1e9,
    )
    replay_corr.ingest(records=records, now=wall)

    # Per-series, per-aggregate fidelity of the rebuilt store.
    max_err = 0.0
    n_queries = 0
    t_query = time.perf_counter()
    for info in live_hist.series_list():
        for agg in _HISTORIAN_FIDELITY_AGGS:
            live_q = live_hist.query(
                info["name"], t0=0.0, t1=wall + 120.0, agg=agg, tier="raw"
            )
            rep_q = replay_hist.query(
                info["name"], t0=0.0, t1=wall + 120.0, agg=agg, tier="raw"
            )
            n_queries += 2
            lv, rv = live_q["value"], rep_q["value"]
            if lv is None and rv is None:
                continue
            if lv is None or rv is None:
                max_err = float("inf")
                continue
            denom = max(abs(lv), 1e-9)
            max_err = max(max_err, abs(lv - rv) / denom * 100.0)
    query_s = max(time.perf_counter() - t_query, 1e-9)

    live_faults = _fault_incidents(live_corr)
    replay_faults = _fault_incidents(replay_corr)

    def _fault_keys(incs: List[dict]) -> set:
        keys = set()
        for inc in incs:
            detects = [e for e in inc["timeline"] if e["role"] == "detect"]
            step = detects[0]["attrs"].get("step") if detects else None
            keys.add((step, inc.get("device_index")))
        return keys

    # chip_fault_timeline dedups colliding steps, so the injected count
    # is len(events), not necessarily n_faults.
    injected = {(e["step"], e["device"]) for e in events}
    gates = {
        "series_within_1pct": max_err < 1.0,
        "every_fault_one_incident": (
            len(live_faults) == len(injected)
            and _fault_keys(live_faults) == injected
        ),
        "causal_chains": all(_incident_chain_ok(i) for i in live_faults),
        "replay_incidents_match": (
            replay_corr.stats()["opened_by_trigger"]
            == live_corr.stats()["opened_by_trigger"]
            and replay_corr.stats()["resolved_total"]
            == live_corr.stats()["resolved_total"]
            and _fault_keys(replay_faults) == _fault_keys(live_faults)
        ),
        "nothing_skipped": ingest_stats["skipped"] == 0,
    }
    return {
        "seed": seed,
        "wall_s": wall,
        "series": live_hist.stats()["series"],
        "samples": live_hist.stats()["samples_total"],
        "samples_ingested": ingested,
        "incidents": live_corr.stats()["opened_by_trigger"],
        "fault_incidents": len(live_faults),
        "resolved_incidents": live_corr.stats()["resolved_total"],
        "slo_progression": gp["slo"]["progression"][:3],
        "max_series_error_pct": round(max_err, 6),
        "ingest_samples_per_sec": round(ingested / ingest_s, 1),
        "query_avg_us": round(query_s / max(n_queries, 1) * 1e6, 1),
        "gates": gates,
        "ok": all(gates.values()),
    }


# -- autopilot lane ------------------------------------------------------------


def replay_autopilot(
    mode: str,
    plan: FaultPlan,
    params: HeteroTwinParams = HeteroTwinParams(),
) -> dict:
    """Replay the seeded slow-host chaos plan under one autopilot mode on
    the virtual clock: ``"off"`` (no control loop — the uniform gang
    gates on the slow host forever), ``"armed"`` (the autopilot's
    drain-host rule sheds the blamed host after its hysteresis clears),
    or ``"dry-run"`` (the full decision stream, zero actuations).

    The injector is both truth and signal, as in :func:`replay_hetero`:
    each consumed HOST_SLOW spec slows the simulated host and is
    mirrored as a ``kind="fault"`` blame event on the lane recorder; the
    lane also retains per-step time and per-host health into its own
    historian, so every autopilot decision consults real range queries
    over the exact series a live fleet would have."""
    hosts = params.hosts
    rows_u = params.global_micro // hosts
    vclock = VirtualClock(0.0)
    rec = FlightRecorder(
        max_spans=8192, max_events=8192, clock=vclock,
        id_factory=deterministic_ids(f"ap-{mode}"),
    )
    hist = historian_mod.MetricHistorian(clock=vclock)
    # Sustained degradation is ONE incident: successive blame events land
    # well inside the widened merge window instead of opening per-step
    # incidents.
    corr = historian_mod.IncidentCorrelator(
        clock=vclock, merge_window_s=4.0 * params.step_time_s,
        stale_after_s=1e9,
    )
    inj = FaultInjector(plan)
    inj.arm()
    rate = [1.0] * hosts
    drained = [False] * hosts

    def drain_actuator(record) -> None:
        drained[int(record.action["params"]["device_index"])] = True

    autopilot = FleetAutopilot(
        AutopilotConfig(
            trend_window_s=60.0,
            sustain_consults=3,
            cooldown_s=120.0,
            max_actions_per_window=2,
            action_window_s=600.0,
            fault_blame_threshold=3,
            host_health_floor=0.9,
        ),
        dry_run=(mode == "dry-run"),
        historian=hist,
        correlator=corr,
        recorder=rec,
        actuators={} if mode == "off" else {"drain_host": drain_actuator},
        gauges_fn=lambda: {
            f"host_health_{h}": (0.0 if drained[h] else rate[h])
            for h in range(hosts)
        },
        clock=vclock,
        id_factory=deterministic_ids("apd"),
        trace_id="fleet",
    )
    downtime_s = 0.0
    ideal_wall = 0.0
    tail_wall = tail_ideal = 0.0
    for step in range(1, params.steps + 1):
        spec = inj.take_host_slow(step)
        if spec is not None:
            idx = int(spec.device_index or 0)
            if not drained[idx]:
                rate[idx] = params.step_time_s / (
                    params.step_time_s + float(spec.slow_s)
                )
                rec.event(
                    "host_slow", kind="fault", trace_id="fleet", ts=vclock.t,
                    attrs={"step": step, "device_index": idx,
                           "slow_s": float(spec.slow_s)},
                )
        active = [h for h in range(hosts) if not drained[h]]
        rows_h = params.global_micro / len(active)
        step_s = max(
            rows_h * params.step_time_s / (rows_u * rate[h]) for h in active
        )
        ideal_s = params.global_micro * params.step_time_s / (
            rows_u * sum(rate)
        )
        now = vclock.advance(step_s)
        ideal_wall += ideal_s
        hist.record("step_time_s", step_s, ts=now)
        for h in range(hosts):
            hist.record(
                "hetero_host_health", 0.0 if drained[h] else rate[h],
                ts=now, labels={"host": str(h)},
            )
        if mode != "off" and step % params.check_every == 0:
            before = sum(drained)
            autopilot.tick(now=now)
            if sum(drained) > before:
                # Shedding a host is an emergency save + re-admit + cold
                # compile, exactly the shrink path's price.
                downtime_s += (
                    params.ckpt_save_s + params.resume_admit_s
                    + params.cold_compile_s
                )
                vclock.advance(
                    params.ckpt_save_s + params.resume_admit_s
                    + params.cold_compile_s
                )
        if step > params.steps - params.tail_steps:
            tail_wall += step_s
            tail_ideal += ideal_s
    stats = autopilot.stats()
    return {
        "mode": mode,
        "wall_s": round(vclock.t, 1),
        "ideal_wall_s": round(ideal_wall, 1),
        "downtime_s": round(downtime_s, 1),
        "goodput": round(ideal_wall / vclock.t, 4),
        "steady_goodput": round(tail_ideal / tail_wall, 4),
        "drained_hosts": [h for h in range(hosts) if drained[h]],
        "autopilot": stats,
        "decisions": autopilot.decisions(limit=0),
        "incidents": corr.incidents(limit=0),
        "incident_stats": corr.stats(),
    }


def _autopilot_action_legs(incidents: List[dict]) -> List[dict]:
    return [
        e
        for inc in incidents
        for e in inc["timeline"]
        if e["role"] == "action" and e["kind"] == "autopilot"
    ]


def autopilot_lane(
    seed: int = 0, params: HeteroTwinParams = HeteroTwinParams()
) -> dict:
    """Chaos A/B for the autopilot: armed vs off vs dry-run on one seeded
    slow-host fault plan. Gates: the armed loop's steady-state goodput
    beats (or matches) the uncontrolled fleet; dry-run emits the decision
    stream with zero actuations; every decision carries historian query
    inputs and its incident link; and the correlator shows the decision
    as the incident's action leg with the right ``action_source``."""
    plan = host_slow_plan(seed, params)
    slow_host = int(plan.specs[0].device_index or 0)
    off = replay_autopilot("off", plan, params)
    on = replay_autopilot("armed", plan, params)
    dry = replay_autopilot("dry-run", plan, params)
    explained = [
        d
        for run in (on, dry)
        for d in run["decisions"]
    ]
    gates = {
        "autopilot_on_ge_off": on["steady_goodput"] >= off["steady_goodput"],
        "armed_drained_slow_host": on["drained_hosts"] == [slow_host],
        "dry_run_zero_actuations": (
            dry["autopilot"]["actuations_total"] == 0
            and dry["drained_hosts"] == []
        ),
        "dry_run_emits_decisions": (
            dry["autopilot"]["decisions_total"] > 0
            and dry["autopilot"]["fired_total"] > 0
        ),
        "every_decision_explainable": bool(explained) and all(
            d["inputs"]["queries"]
            and d["inputs"]["incidents"]
            and d["hysteresis"]["required"] >= 1
            for d in explained
        ),
        "action_leg_sourced": (
            all(
                leg["action_source"] == "autopilot"
                for leg in _autopilot_action_legs(on["incidents"])
            )
            and all(
                leg["action_source"] == "autopilot-dryrun"
                for leg in _autopilot_action_legs(dry["incidents"])
            )
            and bool(_autopilot_action_legs(on["incidents"]))
            and bool(_autopilot_action_legs(dry["incidents"]))
        ),
    }
    return {
        "seed": seed,
        "slow_host": slow_host,
        "steady_goodput_on": on["steady_goodput"],
        "steady_goodput_off": off["steady_goodput"],
        "steady_goodput_dry": dry["steady_goodput"],
        "goodput_recovered": round(
            on["steady_goodput"] - off["steady_goodput"], 4
        ),
        "armed": {
            k: on["autopilot"][k]
            for k in ("decisions_total", "fired_total", "suppressed_total",
                      "actuations_total", "suppressed_by_reason")
        },
        "dry_run": {
            k: dry["autopilot"][k]
            for k in ("decisions_total", "fired_total", "suppressed_total",
                      "actuations_total", "suppressed_by_reason")
        },
        "incidents_armed": on["incident_stats"]["opened_by_trigger"],
        "gates": gates,
        "ok": all(gates.values()),
    }


# -- control-plane scale lane --------------------------------------------------
#
# 100k jobs / 1M serving requests as a *measured* regime: push the real
# FleetScheduler, FleetRouter, MetricHistorian and IncidentCorrelator
# through two phases under one VirtualClock, profile where the control
# seconds go, and gate that control overhead per simulated fleet-second
# stays flat as the fleet's job/request history grows 100x. Any control
# cost that scales with history (a ring scan, an unindexed _subs walk, a
# per-sample lock round-trip) shows up here as a rising ratio before it
# shows up as a stuck production scheduler.


@dataclasses.dataclass
class ScaleLaneParams:
    """One control-plane scale configuration.

    ``small()`` and ``big()`` differ ONLY in job/request counts: the
    per-simulated-second workload — submission chunking, job duration
    mix, serving arrival rate, control cadence, replica churn — is
    identical, so control overhead per simulated fleet-second is
    directly comparable between them. A flat ratio means no control-
    plane cost grows with how much history the fleet has accumulated."""

    n_jobs: int = 1_000
    n_requests: int = 10_000
    max_concurrent: int = 128
    submit_chunk: int = 1_000
    poll_dt_s: float = 5.0
    n_tenants: int = 8
    n_replicas: int = 8
    replica_slots: int = 16
    request_rate_hz: float = 1_000.0
    control_period_s: float = 1.0
    churn_period_s: float = 2.5
    scrape_every_polls: int = 16
    correlate_every_s: float = 10.0

    @staticmethod
    def small() -> "ScaleLaneParams":
        return ScaleLaneParams()

    @staticmethod
    def big() -> "ScaleLaneParams":
        return ScaleLaneParams(n_jobs=100_000, n_requests=1_000_000)


class _ScaleJob:
    """Virtual-clock stand-in for one training attempt: runs for a fixed
    number of simulated seconds, then completes. ``watcher = None`` marks
    it non-preemptible, so submit -> admit -> reap is the whole lifecycle
    — exactly the per-job control cost the lane measures — with zero
    threads."""

    __slots__ = (
        "_clock", "_sim_s", "_done_at", "_st", "status",
        "current_step", "watcher", "preemption_reason", "_stop",
    )

    def __init__(self, clock: Callable[[], float], sim_s: float, status_enum):
        self._clock = clock
        self._sim_s = float(sim_s)
        self._done_at = math.inf
        self._st = status_enum
        self.status = status_enum.PENDING
        self.current_step = 0
        self.watcher = None
        self.preemption_reason = None
        self._stop = threading.Event()

    def start(self) -> None:
        self._done_at = self._clock() + self._sim_s
        self.status = self._st.RUNNING

    @property
    def is_alive(self) -> bool:
        st = self._st
        if self.status == st.RUNNING and self._clock() >= self._done_at:
            self.status = st.STOPPED if self._stop.is_set() else st.COMPLETED
            self.current_step = int(self._sim_s)
        return self.status in (st.PENDING, st.RUNNING)

    def join(self, timeout: Optional[float] = None) -> None:
        return None

    def describe(self) -> Dict[str, Any]:
        return {
            "status": getattr(self.status, "value", str(self.status)),
            "step": self.current_step,
        }


def scale_lane(seed: int = 0, params: Optional[ScaleLaneParams] = None) -> dict:
    """Drive ONE scale configuration through the real control plane under
    the virtual clock and profile where the control seconds went.

    Two phases share one flight recorder / historian / goodput ledger
    (installed process-wide for the run via the singleton setters,
    restored after):

    - **training**: ``params.n_jobs`` submissions through the real
      :class:`~tpu_engine.scheduler.FleetScheduler`. Chunked submits
      keep a bounded standing queue; the background pump is disabled and
      ``poll()`` is driven manually, so the run is single-threaded and
      byte-deterministic. Every completion settles its goodput trace
      through the recorder's per-trace index (the O(trace) read this
      lane exists to keep honest — it used to copy the whole ring per
      reaped job).
    - **serving**: ``params.n_requests`` through the real
      :class:`~tpu_engine.serving_fleet.FleetRouter` over a slot-model
      replica fleet — periodic weight refreshes, replica kill/revive
      churn (fault + resume events the correlator must open and
      resolve), batched historian ingest of every latency sample, and
      bounded-window percentile reads each control tick.

    Returns per-phase timings, ``overhead_us_per_fleet_s`` (control CPU
    microseconds per simulated fleet-second — THE scale metric), ring
    bounds, and a ``deterministic`` dict of every count that must be
    byte-identical across two runs of the same config.

    All timings are ``time.process_time()`` — the lane is single-threaded,
    so CPU time IS the control cost, and it does not absorb the
    descheduling noise a wall clock picks up on a loaded host (on a
    1-core CI box wall-clock phase timings varied +-25% run to run; the
    flatness gate needs better than that). The cyclic GC is paused for
    the run (restored after): a gen-2 pass landing inside a sub-second
    phase window is a +-17% lump that has nothing to do with control-
    plane flatness — the lane instead proves the live set is bounded
    directly (``rings_bounded``, including the scheduler's finished-
    history bound), which is what keeps real GC pauses flat at depth."""
    import gc

    from tpu_engine import goodput as goodput_mod
    from tpu_engine import tracing as tracing_mod
    from tpu_engine.mesh_runtime import MeshConfig
    from tpu_engine.scheduler import FleetScheduler, JobPriority
    from tpu_engine.serving_fleet import FleetRouter, _PercentileWindow
    from tpu_engine.sharding import TPUTrainConfig
    from tpu_engine.supervisor import JobStatus

    p = params or ScaleLaneParams.small()
    vclock = VirtualClock(0.0)
    # Small rings on purpose: even the small config saturates them during
    # its training phase, so correlator ingest normalizes a FULL ring in
    # both configs and the overhead ratio compares steady states, not a
    # warm ring against a cold one.
    rec = FlightRecorder(
        max_spans=1024, max_events=1024, clock=vclock,
        id_factory=deterministic_ids("ctl"),
    )
    hist = historian_mod.MetricHistorian(clock=vclock)
    # max_tracked sized above the standing submission window so every
    # trace settles through the full finalize path, none via eviction.
    ledger = GoodputLedger(clock=vclock, max_tracked=2 * p.submit_chunk + 256)
    corr = historian_mod.IncidentCorrelator(clock=vclock, stale_after_s=1e9)

    old_rec = tracing_mod.get_recorder()
    old_hist = historian_mod.get_historian()
    old_ledger = goodput_mod.get_ledger()
    tracing_mod.set_recorder(rec)
    historian_mod.set_historian(hist)
    goodput_mod.set_ledger(ledger)
    gc_was_enabled = gc.isenabled()
    gc.collect()
    gc.disable()
    try:
        # ---- phase 1: n_jobs through the real scheduler ----------------------
        cfg = TPUTrainConfig(
            model_name="gpt-tiny", mesh=MeshConfig(data=1, fsdp=1),
            micro_batch_size=1, seq_len=32, precision="fp32",
            total_steps=5, activation_checkpointing=False,
        )
        jcount = iter(range(1 << 30))

        def make_job(sub) -> _ScaleJob:
            return _ScaleJob(vclock, 30.0 + 7.5 * (next(jcount) % 9), JobStatus)

        sched = FleetScheduler(
            max_concurrent_jobs=p.max_concurrent,
            # Backfill must see the whole admissible window, or admission
            # throttles to 4 jobs per poll regardless of free capacity.
            backfill_depth=p.max_concurrent,
            job_factory=make_job,
            poll_interval_s=3600.0,
            grow_back=False,
            hetero_rebalance=False,
            # Pin the finished-history bound to the same constant for every
            # config, low enough that BOTH configs evict at steady state:
            # the flatness claim is "bounded live state => flat control
            # cost", so both sides of the ratio must hold the same live
            # set AND pay the same per-job eviction/deallocation cost (a
            # bound the small config never fills shows up as a flat ~30us
            # per-job surcharge on the big side only).
            max_finished_history=256,
        )
        sched._ensure_thread = lambda: None  # the lane owns the poll cadence
        prios = (JobPriority.NORMAL, JobPriority.LOW, JobPriority.HIGH)

        submit_s = poll_s = scrape_s = 0.0
        polls = scrapes = submitted = 0
        max_polls = 1_000 + 40 * (p.n_jobs // max(p.max_concurrent, 1) + 1)
        t_train0 = time.process_time()
        sim_train0 = vclock.now()
        while sched.completed_total + sched.failed_total < p.n_jobs:
            if (
                submitted < p.n_jobs
                and submitted - sched.completed_total <= p.submit_chunk // 2
            ):
                k = min(p.submit_chunk, p.n_jobs - submitted)
                t0 = time.process_time()
                for i in range(submitted, submitted + k):
                    sched.submit(
                        cfg,
                        priority=prios[i % 3],
                        submitter=f"team-{i % p.n_tenants}",
                    )
                submit_s += time.process_time() - t0
                submitted += k
            t0 = time.process_time()
            sched.poll()
            poll_s += time.process_time() - t0
            polls += 1
            if polls % p.scrape_every_polls == 0:
                t0 = time.process_time()
                sched.stats()
                scrape_s += time.process_time() - t0
                scrapes += 1
            vclock.advance(p.poll_dt_s)
            if polls > max_polls:
                raise RuntimeError(
                    f"scale lane wedged: {sched.completed_total}/{p.n_jobs} "
                    f"completed after {polls} polls"
                )
        train_wall_s = time.process_time() - t_train0
        sim_train_s = vclock.now() - sim_train0
        sched_stats = sched.stats()
        sched.shutdown()

        # ---- phase 2: n_requests through the real router ---------------------
        router = FleetRouter()
        lat_win = _PercentileWindow(window=512)
        tps = {f"r{j}": 1500.0 + 137.0 * j for j in range(p.n_replicas)}
        busy = {rid: 0 for rid in tps}
        down: set = set()
        inflight: list = []  # (finish_ts, replica_id) min-heap
        # 64 distinct prompt prefixes: a deterministic affinity working set.
        prompts = [
            [(seed * 131 + g * 17 + k) % 5003 for k in range(40)]
            for g in range(64)
        ]

        def _snapshot() -> Dict[str, Dict[str, Any]]:
            return {
                rid: {
                    "tokens_per_sec": tps[rid],
                    "free_slots": max(p.replica_slots - busy[rid], 0),
                    "slots": p.replica_slots,
                }
                for rid in tps if rid not in down
            }

        dt = 1.0 / p.request_rate_hz
        serve_t0 = vclock.now()
        next_control = serve_t0
        next_churn = serve_t0 + p.churn_period_s
        next_corr = serve_t0 + p.correlate_every_s
        churn_events = routed = misrouted = control_ticks = 0
        ingest_s = correlate_s = pct_s = 0.0
        lat_batch: list = []
        p50 = p99 = None
        router.update(_snapshot())
        t_serve0 = time.process_time()
        for i in range(p.n_requests):
            now = serve_t0 + i * dt
            vclock.set(now)
            while inflight and inflight[0][0] <= now:
                busy[heapq.heappop(inflight)[1]] -= 1
            if now >= next_churn:
                j = (churn_events // 2) % p.n_replicas
                rid = f"r{j}"
                if churn_events % 2 == 0:
                    down.add(rid)
                    rec.event(
                        "replica_down", kind="fault",
                        trace_id=f"srv-{churn_events // 2}", ts=now,
                        attrs={"replica": rid},
                    )
                else:
                    down.discard(rid)
                    rec.event(
                        "replica_resume", kind="supervisor",
                        trace_id=f"srv-{churn_events // 2}", ts=now,
                        attrs={"replica": rid},
                    )
                churn_events += 1
                next_churn += p.churn_period_s
            if now >= next_control:
                control_ticks += 1
                router.update(_snapshot())
                t0 = time.process_time()
                p50, p99 = lat_win.percentiles((0.50, 0.99))
                pct_s += time.process_time() - t0
                lat_batch.append(("serving_inflight", float(len(inflight))))
                if p99 is not None:
                    lat_batch.append(("serving_p99_ms", p99))
                t0 = time.process_time()
                hist.observe_batch(lat_batch, ts=now)
                ingest_s += time.process_time() - t0
                lat_batch = []
                next_control += p.control_period_s
            if now >= next_corr:
                t0 = time.process_time()
                corr.ingest(recorder=rec, now=now)
                correlate_s += time.process_time() - t0
                next_corr += p.correlate_every_s
            rid = router.route(prompts[(i * 7) % 64])
            if rid is None or rid in down:
                misrouted += 1
                continue
            routed += 1
            service_s = (40 + (i % 160)) / tps[rid]
            over = busy[rid] - p.replica_slots
            if over >= 0:
                service_s *= 1.0 + 0.1 * (over + 1)
            busy[rid] += 1
            heapq.heappush(inflight, (now + service_s, rid))
            lat_win.add(service_s * 1000.0)
            lat_batch.append(("serving_latency_ms", service_s * 1000.0))
        # Drain the tail, then settle the final tick / ingest / read.
        while inflight:
            ts_f, rid = heapq.heappop(inflight)
            busy[rid] -= 1
            if ts_f > vclock.now():
                vclock.set(ts_f)
        router.update(_snapshot())
        if lat_batch:
            t0 = time.process_time()
            hist.observe_batch(lat_batch, ts=vclock.now())
            ingest_s += time.process_time() - t0
        t0 = time.process_time()
        p50, p99 = lat_win.percentiles((0.50, 0.99))
        pct_s += time.process_time() - t0
        t0 = time.process_time()
        corr.ingest(recorder=rec, now=vclock.now())
        correlate_s += time.process_time() - t0
        serve_wall_s = time.process_time() - t_serve0
        sim_serve_s = vclock.now() - serve_t0
        route_s = max(serve_wall_s - ingest_s - correlate_s - pct_s, 0.0)

        # ---- accounting ------------------------------------------------------
        rec_stats = rec.stats()
        hist_stats = hist.stats()
        corr_stats = corr.stats()
        rings = {
            "recorder_spans": len(rec.spans(limit=0)),
            "recorder_events": len(rec.events(limit=0)),
            "recorder_open_spans": rec_stats["open_spans"],
            "recorder_trace_index": rec_stats["trace_index"],
            "historian_raw_samples": hist_stats["raw_samples"],
            "incidents_retained": len(corr.incidents(limit=0)),
            "scheduler_history": len(sched._subs),
        }
        rings_bounded = (
            rings["recorder_spans"] <= rec.max_spans
            and rings["recorder_events"] <= rec.max_events
            and rings["recorder_open_spans"] == 0
            and rings["recorder_trace_index"] <= rec.max_spans
            and rings["historian_raw_samples"]
                <= hist_stats["series"] * hist.raw_capacity
            and rings["incidents_retained"] <= corr.max_incidents
            and rings["scheduler_history"] <= sched.max_finished_history
        )
        ctl_s = (
            submit_s + poll_s + scrape_s
            + route_s + ingest_s + correlate_s + pct_s
        )
        sim_s = sim_train_s + sim_serve_s
        # Overhead is normalized by *delivered* fleet-seconds (job-seconds
        # at peak concurrency plus request-seconds at the offered rate),
        # not the measured virtual wall: the 1k-job run spends a far
        # larger fraction of its wall in ramp/drain tails where the fleet
        # is part-empty, which dilutes the small denominator and fakes a
        # 100x-scale slowdown that per-job costs do not show.
        work_s = (
            sum(30.0 + 7.5 * (i % 9) for i in range(p.n_jobs))
            / max(p.max_concurrent, 1)
            + p.n_requests / p.request_rate_hz
        )
        det = {
            "jobs": {
                "submitted": sched.submitted_total,
                "admitted": sched.admitted_total,
                "completed": sched.completed_total,
                "failed": sched.failed_total,
                "requeues": sched.requeues_total,
                "preemptions": sched.preemptions_total,
                "queue_depth_end": sched_stats["queue_depth"],
                "history_evicted": sched.finished_evicted_total,
                "polls": polls,
            },
            "serving": {
                "routed": routed,
                "misrouted": misrouted,
                "router_routed_total": router.routed_total,
                "affinity_hits": router.affinity_hits,
                "control_ticks": control_ticks,
                "churn_events": churn_events,
                "p50_ms": None if p50 is None else round(p50, 6),
                "p99_ms": None if p99 is None else round(p99, 6),
            },
            "historian": {
                "samples_total": hist_stats["samples_total"],
                "batches": hist_stats["ingest_batch_total"],
                "batched_samples": hist_stats["ingest_batched_samples_total"],
            },
            "recorder": {
                "spans_total": rec_stats["spans_total"],
                "events_total": rec_stats["events_total"],
                "spans_dropped": rec_stats["spans_dropped"],
                "events_dropped": rec_stats["events_dropped"],
            },
            "incidents": {
                "opened": corr_stats["opened_total"],
                "resolved": corr_stats["resolved_total"],
                "correlated": corr_stats["correlated_total"],
                "ignored": corr_stats["ignored_total"],
            },
        }
        return {
            "params": dataclasses.asdict(p),
            "phases": {
                "submit_s": round(submit_s, 4),
                "sched_poll_s": round(poll_s, 4),
                "scrape_s": round(scrape_s, 4),
                "route_s": round(route_s, 4),
                "historian_ingest_s": round(ingest_s, 4),
                "correlate_s": round(correlate_s, 4),
                "percentile_s": round(pct_s, 4),
                "train_wall_s": round(train_wall_s, 4),
                "serve_wall_s": round(serve_wall_s, 4),
            },
            "scrapes": scrapes,
            "control_s": round(ctl_s, 4),
            "sim_fleet_s": round(sim_s, 3),
            "work_fleet_s": round(work_s, 3),
            "overhead_us_per_fleet_s": round(ctl_s / max(work_s, 1e-9) * 1e6, 3),
            # Marginal control cost per unit of work — the saturation-
            # independent flatness signal (the 1k config spends a large
            # share of its polls in half-empty ramp/drain tails, which
            # shifts any wall-clock-per-fleet-second ratio without any
            # per-job cost changing).
            "control_us_per_job": round(
                (submit_s + poll_s + scrape_s) / max(p.n_jobs, 1) * 1e6, 3
            ),
            "control_us_per_request": round(
                (route_s + ingest_s + correlate_s + pct_s)
                / max(p.n_requests, 1) * 1e6, 3
            ),
            "rings": rings,
            "rings_bounded": rings_bounded,
            "deterministic": det,
        }
    finally:
        if gc_was_enabled:
            gc.enable()
            gc.collect()
        tracing_mod.set_recorder(old_rec)
        historian_mod.set_historian(old_hist)
        goodput_mod.set_ledger(old_ledger)


def ctl_scale_profile(
    seed: int = 0,
    small: Optional[ScaleLaneParams] = None,
    big: Optional[ScaleLaneParams] = None,
) -> dict:
    """The scale lane's exit gate: run the small (1k-job / 10k-request)
    configuration five times — every run's ``deterministic`` dict must be
    byte-identical, and the median marginal cost is the denominator —
    then the big (100k-job / 1M-request) configuration twice, gating that
    the control cost per job and per request stays flat (<= 1.25x) as
    job/request volume grows 100x."""
    small = small or ScaleLaneParams.small()
    big = big or ScaleLaneParams.big()
    # Warmup (discarded): the first lane run in a process pays one-time
    # import/alloc/branch-warming costs that would land entirely on the
    # small side of the ratio.
    scale_lane(seed=seed, params=ScaleLaneParams(n_jobs=100, n_requests=1_000))
    # The small config is sub-second, so any single run is at the mercy
    # of allocator/cpufreq lumps: take the median of five, and require
    # every run's deterministic counts to be byte-identical.
    small_runs = [scale_lane(seed=seed, params=small) for _ in range(5)]
    digests = {
        json.dumps(r["deterministic"], sort_keys=True) for r in small_runs
    }
    overheads = sorted(r["overhead_us_per_fleet_s"] for r in small_runs)
    overhead_small = overheads[len(overheads) // 2]
    run_small = small_runs[0]
    # The big config runs twice: the deterministic counts must agree at
    # depth too, and the flatness numerator takes the cheaper run — a
    # shared-host tenant polluting the cache for one 20-second window
    # must not read as superlinear control cost, while a real
    # superlinearity (an unbounded index, an O(history) scan) inflates
    # even the best of two runs.
    big_runs = [scale_lane(seed=seed, params=big) for _ in range(2)]
    big_digests = {
        json.dumps(r["deterministic"], sort_keys=True) for r in big_runs
    }
    run_big = big_runs[0]
    overhead_big = min(r["overhead_us_per_fleet_s"] for r in big_runs)

    def _median(key: str) -> float:
        vals = sorted(r[key] for r in small_runs)
        return vals[len(vals) // 2]

    # Flatness is gated on marginal control cost per job and per request:
    # that is the statement "100x more jobs costs 100x more control work,
    # not more" with the small config's ramp-tail share factored out. The
    # per-fleet-second overheads are reported alongside for the capacity
    # framing (what fraction of a core one fleet-second of control takes).
    big_per_job = min(r["control_us_per_job"] for r in big_runs)
    big_per_req = min(r["control_us_per_request"] for r in big_runs)
    ratio_job = big_per_job / max(_median("control_us_per_job"), 1e-9)
    ratio_req = big_per_req / max(_median("control_us_per_request"), 1e-9)
    ratio = max(ratio_job, ratio_req)
    served_frac = (
        run_big["deterministic"]["serving"]["routed"] / max(big.n_requests, 1)
    )
    gates = {
        "deterministic": len(digests) == 1 and len(big_digests) == 1,
        "overhead_flat_1k_to_100k": ratio <= 1.25,
        "all_jobs_completed": (
            run_small["deterministic"]["jobs"]["completed"] == small.n_jobs
            and run_big["deterministic"]["jobs"]["completed"] == big.n_jobs
        ),
        "requests_routed_98pct": served_frac >= 0.98,
        "rings_bounded": run_small["rings_bounded"] and run_big["rings_bounded"],
    }
    return {
        "small": run_small,
        "big": run_big,
        "overhead_small_us_per_fleet_s": overhead_small,
        "overhead_small_spread_us": [overheads[0], overheads[-1]],
        "overhead_big_us_per_fleet_s": overhead_big,
        "per_job_us": {
            "small": _median("control_us_per_job"),
            "big": big_per_job,
            "ratio": round(ratio_job, 4),
        },
        "per_request_us": {
            "small": _median("control_us_per_request"),
            "big": big_per_req,
            "ratio": round(ratio_req, 4),
        },
        "overhead_ratio": round(ratio, 4),
        "gates": gates,
        "ok": all(gates.values()),
    }


# -- fleet prefix plane lane ---------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PrefixPlaneLaneParams:
    """Many-tenant shared-prefix serving scenario: more hot system
    prompts than any one replica's prefix cache can retain, so the
    fleet's TTFT is set by how prefix residency is managed — per-replica
    LRU (baseline) vs the fleet prefix plane (radix index routing +
    host-RAM tier)."""

    duration_s: float = 480.0
    dt_s: float = 0.05
    control_period_s: float = 1.0
    n_replicas: int = 4
    slots: int = REPLICA_SLOTS
    tokens_per_slot_s: float = REPLICA_TOKENS_PER_SLOT_S
    chips_per_replica: int = 1
    # Prefill legs: full prompt (cold), resident-prefix tail, and
    # host-tier rehydration (host->HBM copy + tail) — between the two.
    prefill_s: float = REPLICA_PREFILL_S
    prefill_hit_s: float = REPLICA_PREFILL_HIT_S
    prefill_host_s: float = 0.35
    # 32 hot tenants vs 4 replicas x 4 resident prefixes: half the
    # working set cannot be device-resident anywhere.
    n_prefixes: int = 32
    prefix_len: int = 32
    replica_cache_prefixes: int = 4
    # Host tier capacity model: one int8 KVHandoff wire payload per
    # prefix (a 32-token llama-1b prefix is ~0.2 MiB; 1 MiB is a round
    # conservative stand-in), budget big enough to absorb the overflow.
    host_entry_bytes: int = 1 << 20
    host_budget_entries: int = 64
    base_rps: float = 4.0
    burst_rps: float = 10.0
    burst_every_s: float = 120.0
    burst_len_s: float = 30.0
    mean_new_tokens: float = 48.0
    min_new_tokens: int = 8
    warmup_s: float = 60.0


def prefix_plane_lane(
    seed: int,
    plane: bool,
    params: PrefixPlaneLaneParams = PrefixPlaneLaneParams(),
) -> dict:
    """One seeded many-tenant shared-prefix run at fixed chips, through
    the REAL :class:`~tpu_engine.serving_fleet.FleetRouter` — baseline
    (``plane=False``: affinity pinning + per-replica LRU residency) or
    with a real :class:`~tpu_engine.prefix_plane.PrefixPlane` attached
    (radix-index routing, host-tier absorption of replica-cache
    overflow, rehydration on host hits). Fully virtual-clock: same seed
    and mode give a byte-identical report."""
    from tpu_engine.prefix_plane import HostKVTier, PrefixPlane
    from tpu_engine.serving_fleet import FleetRouter

    clock = VirtualClock(0.0)
    pplane = None
    if plane:
        hist = historian_mod.MetricHistorian()
        host = HostKVTier(
            budget_bytes=params.host_budget_entries * params.host_entry_bytes,
            historian=hist, clock=clock, reuse_window_s=params.duration_s,
        )
        pplane = PrefixPlane(
            prefix_tokens=params.prefix_len,
            replica_prefix_budget=params.replica_cache_prefixes,
            host=host, historian=hist, clock=clock,
            # Capacity-model spill: the evicted entry's modeled wire bytes.
            spill=lambda prefix, rid: params.host_entry_bytes,
        )
    router = FleetRouter(affinity_tokens=params.prefix_len,
                         prefix_plane=pplane)
    replicas = {
        f"r{i}": SlotReplica(f"r{i}", params.slots, params.tokens_per_slot_s)
        for i in range(params.n_replicas)
    }
    # Baseline per-replica residency: LRU over prefix ids, capped at what
    # the replica's device cache could actually hold.
    lru: Dict[str, "collections.OrderedDict[int, None]"] = {
        rid: collections.OrderedDict() for rid in replicas
    }

    def touch(rid: str, pid: int) -> bool:
        """True on a hit; a miss inserts and LRU-evicts past the replica's
        budget (the eviction is silent — per-replica LRU has nowhere to
        put the overflow, which is the point)."""
        cache = lru[rid]
        if pid in cache:
            cache.move_to_end(pid)
            return True
        cache[pid] = None
        while len(cache) > params.replica_cache_prefixes:
            cache.popitem(last=False)
        return False

    trace = bursty_arrivals(
        seed,
        duration_s=params.duration_s,
        base_rps=params.base_rps,
        burst_rps=params.burst_rps,
        burst_every_s=params.burst_every_s,
        burst_len_s=params.burst_len_s,
        n_prefixes=params.n_prefixes,
        prefix_len=params.prefix_len,
        mean_new_tokens=params.mean_new_tokens,
        min_new_tokens=params.min_new_tokens,
    )
    queue: List[dict] = []
    done: List[dict] = []
    kinds = {"replica": 0, "host": 0, "cold": 0}

    def control(t: float) -> None:
        router.update({r.rid: r.router_stats(t) for r in replicas.values()})

    def tick(t: float) -> None:
        clock.set(t)
        free_total = sum(r.free_slots(t) for r in replicas.values())
        while queue and free_total > 0:
            req = queue[0]
            rid = router.route(req["prompt"])
            rep = replicas.get(rid) if rid else None
            if rep is None or rep.free_slots(t) <= 0:
                break  # full pick: weights refresh next control period
            queue.pop(0)
            free_total -= 1
            if pplane is not None:
                obs = pplane.observe_admit(req["prompt"], rid, now=t)
                kinds[obs["kind"]] += 1
                prefill = {
                    "replica": params.prefill_hit_s,
                    "host": params.prefill_host_s,
                    "cold": params.prefill_s,
                }[obs["kind"]]
            else:
                hit = touch(rid, req["prefix_id"])
                kinds["replica" if hit else "cold"] += 1
                prefill = params.prefill_hit_s if hit else params.prefill_s
            rep.admit(req, prefill)
        for r in replicas.values():
            r.step(t, params.dt_s, done)

    run_open_loop(
        trace,
        dt=params.dt_s,
        duration_s=params.duration_s,
        pending=lambda: queue or any(r.active for r in replicas.values()),
        arrive=queue.append,
        tick=tick,
        control=control,
        control_period_s=params.control_period_s,
        safety_factor=3.0,
    )

    total_chips = params.n_replicas * params.chips_per_replica
    metrics = serving_metrics(done, [], warmup_s=params.warmup_s,
                              total_chips=total_chips, dt_s=params.dt_s)
    out = {
        "mode": "plane" if plane else "baseline",
        "metrics": metrics,
        "admission_kinds": dict(kinds),
        "router": {
            k: v for k, v in router.stats().items() if k != "prefix_plane"
        },
    }
    if pplane is not None:
        st = pplane.stats()
        out["plane"] = st
        out["host_occupancy"] = st["host"]["occupancy"]
    return out


def prefix_plane_ab(
    seed: int = 0,
    params: PrefixPlaneLaneParams = PrefixPlaneLaneParams(),
) -> dict:
    """The prefix-plane exit gate: baseline vs plane at EQUAL chips on
    the same seeded trace, a byte-identical plane repeat (determinism),
    and the estimator's structured host-budget rejection."""
    from tpu_engine.hbm_estimate import HostBudgetExceeded, estimate_serving_hbm

    base = prefix_plane_lane(seed, plane=False, params=params)
    plane = prefix_plane_lane(seed, plane=True, params=params)
    repeat = prefix_plane_lane(seed, plane=True, params=params)

    b, p = base["metrics"], plane["metrics"]
    improvement = round(b["ttft_p99_ms"] / max(p["ttft_p99_ms"], 1e-9), 2)
    tps_ratio = round(p["tokens_per_sec"] / max(b["tokens_per_sec"], 1e-9), 4)

    # Admission honesty: a sane host tier budgets through the estimator;
    # an oversubscribed one is refused with a structured reason.
    est = estimate_serving_hbm(
        "llama-1b", params.slots, 2048,
        host_prefix_tokens=params.host_budget_entries * params.prefix_len,
        host_budget_gib=4.0,
    )
    rejection = None
    try:
        estimate_serving_hbm(
            "llama-1b", params.slots, 2048,
            host_prefix_tokens=1 << 30, host_budget_gib=1.0,
        )
    except HostBudgetExceeded as e:
        rejection = e.reason

    gates = {
        "plane_beats_baseline_p99_ttft_2x": improvement >= 2.0,
        "tokens_per_sec_no_worse": tps_ratio >= 0.99,
        "deterministic_repeat": plane == repeat,
        "host_tier_absorbs_overflow": (
            plane.get("plane", {}).get("host", {}).get("stores", 0) > 0
            and plane.get("plane", {}).get("host_rehydrations", 0) > 0
        ),
        "host_budget_rejected": (
            rejection is not None
            and rejection.get("kind") == "host_budget_exceeded"
            and est is not None and est.host_gib > 0
        ),
    }
    return {
        "baseline": base,
        "plane": plane,
        "ttft_p99_improvement": improvement,
        "tokens_per_sec_ratio": tps_ratio,
        "host_tier_gib": None if est is None else est.host_gib,
        "host_budget_rejection": rejection,
        "gates": gates,
        "ok": all(gates.values()),
    }


# -- reshard lane: topology-changing resume vs topology-locked restart --------


@dataclasses.dataclass(frozen=True)
class ReshardLaneParams:
    """The reshard exit-gate scenario knobs. ``state_bytes`` prices the
    remap leg through :func:`tpu_engine.reshard.reshard_cost_s` — the
    default is a ~1B-param job (fp32 master + two Adam moments); the
    MTTR budget is the ratio against the same-trace same-topology warm
    self-heal mean (PR 10's number re-derived in-process)."""

    train: TrainTwinParams = TrainTwinParams(layout_prefix="reshard")
    n_faults: int = 12
    state_bytes: int = 12_000_000_000
    mttr_budget_ratio: float = 1.5


def _reshard_layout_key(use: int, flipped: bool, params: TrainTwinParams) -> str:
    """Layout key for ``use`` chips under one of its two factorizations:
    canonical ``data(use/model_axis)×fsdp(model_axis)`` or the flipped
    alternate — the topology change every reshard resume bridges."""
    d, m = use // params.model_axis, params.model_axis
    if flipped:
        d, m = m, d
    return f"{params.layout_prefix}|data{d}xfsdp{m}"


def _keyed_compile(
    index: Optional[CompileCacheIndex],
    key: str,
    params: TrainTwinParams,
    precompile: bool,
) -> Tuple[float, bool]:
    """Compile leg for an explicit layout key. With ``precompile`` the
    scheduler compiled the target layout in the background before the
    cutover (the grow-back discipline), so only the warm relink lands on
    the critical path."""
    if index is None:
        return params.cold_compile_s, False
    if precompile and not index.is_warm(key):
        index.record(key, params.cold_compile_s, cache_hit=False,
                     label=key.split("|", 1)[1], model=params.layout_prefix,
                     via="precompile")
    if index.is_warm(key):
        index.record(key, params.warm_compile_s, cache_hit=True,
                     via=params.layout_prefix)
        return params.warm_compile_s, True
    index.record(key, params.cold_compile_s, cache_hit=False,
                 label=key.split("|", 1)[1], model=params.layout_prefix,
                 via=params.layout_prefix)
    return params.cold_compile_s, False


def replay_reshard_resume(
    events: List[dict],
    params: TrainTwinParams = TrainTwinParams(layout_prefix="reshard"),
    state_bytes: int = 12_000_000_000,
    compile_index: Optional[CompileCacheIndex] = None,
) -> dict:
    """Self-heal where every resume lands on a *different factorization*
    of the surviving chips (data4×fsdp2 → data2×fsdp4 and back), so each
    recovery pays the reshard plane's remap leg
    (:func:`tpu_engine.reshard.reshard_cost_s` over ``state_bytes``) on
    top of save + admit + compile. Zero lost steps, like
    :func:`replay_self_heal`; the A/B against that lane isolates what
    topology freedom costs."""
    from tpu_engine import reshard as reshard_mod

    reshard_s_per = reshard_mod.reshard_cost_s(state_bytes)
    clock = 0.0
    healthy = params.n_chips
    flipped = False  # which factorization the job currently runs under
    pending: List[float] = []
    mttrs: List[float] = []
    grow_backs = 0
    degraded_s = 0.0
    warm_resumes = 0
    cold_resumes = 0
    compile_s_total = 0.0
    reshard_s_total = 0.0
    topology_changes = 0
    i = 0
    for step in range(1, params.total_steps + 1):
        # Grow back onto the canonical factorization of the larger mesh —
        # a topology change too, so the remap leg rides the cutover.
        while pending and pending[0] <= clock and healthy < params.n_chips:
            pending.pop(0)
            healthy += 1
            if _usable(healthy, params) > _usable(healthy - 1, params):
                key = _reshard_layout_key(_usable(healthy, params), False, params)
                g_compile_s, g_warm = _keyed_compile(
                    compile_index, key, params, precompile=True
                )
                clock += (params.ckpt_save_s + params.resume_admit_s
                          + g_compile_s + reshard_s_per)
                compile_s_total += g_compile_s
                reshard_s_total += reshard_s_per
                topology_changes += 1
                flipped = False
                warm_resumes += 1 if g_warm else 0
                cold_resumes += 0 if g_warm else 1
                grow_backs += 1
        use = _usable(healthy, params)
        step_t = params.step_time_s * params.n_chips / use
        clock += step_t
        if use < params.n_chips:
            degraded_s += step_t
        if step % params.ckpt_interval_steps == 0:
            clock += params.ckpt_save_s
        if i < len(events) and step >= events[i]["step"]:
            i += 1
            healthy -= 1
            # Shrink-resume onto the ALTERNATE factorization of what
            # survives: emergency save, re-admit, compile (warm iff the
            # index has seen that layout), then the state remap.
            flipped = not flipped
            key = _reshard_layout_key(_usable(healthy, params), flipped, params)
            compile_s, warm = _keyed_compile(
                compile_index, key, params, precompile=False
            )
            down = (params.ckpt_save_s + params.resume_admit_s
                    + compile_s + reshard_s_per)
            clock += down
            compile_s_total += compile_s
            reshard_s_total += reshard_s_per
            topology_changes += 1
            warm_resumes += 1 if warm else 0
            cold_resumes += 0 if warm else 1
            mttrs.append(step_t + down)
            pending.append(clock + events[i - 1]["recovery_s"])
            pending.sort()
    wall = clock
    return {
        "policy": "reshard-resume",
        "compile_index": compile_index is not None,
        "wall_s": round(wall, 1),
        "steps_run": params.total_steps,
        "lost_steps": 0,
        "faults": len(mttrs),
        "grow_backs": grow_backs,
        "topology_changes": topology_changes,
        "reshard_s_per_resume": round(reshard_s_per, 2),
        "reshard_s_total": round(reshard_s_total, 1),
        "degraded_step_s": round(degraded_s, 1),
        "warm_resumes": warm_resumes,
        "cold_resumes": cold_resumes,
        "compile_s_total": round(compile_s_total, 1),
        "mttr_mean_s": round(sum(mttrs) / len(mttrs), 2) if mttrs else 0.0,
        "mttr_max_s": round(max(mttrs), 2) if mttrs else 0.0,
        "goodput": round(params.total_steps * params.step_time_s / wall, 4),
    }


def reshard_roundtrip_report(seed: int = 0) -> dict:
    """REAL-executor reshard round trip on the host-platform device grid:
    a train-style sharded pytree saved under ``data4×fsdp2`` through the
    real Orbax manager restores — via
    :func:`tpu_engine.reshard.restore_resharded` — onto ``data2×fsdp4``
    and a *shrunk* 6-chip ``data3×fsdp2`` mesh, byte-parity-gated leaf
    by leaf against the source bytes."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from tpu_engine import reshard as reshard_mod
    from tpu_engine.checkpoint import TrainCheckpointManager

    devs = jax.devices()
    if len(devs) < 8:
        return {"skipped": f"needs 8 devices, have {len(devs)}", "ok": False}
    rng = np.random.default_rng(seed)
    host = {
        "params": {
            "w": rng.standard_normal((16, 8)).astype(np.float32),
            "b": rng.standard_normal((8,)).astype(np.float32),
        },
        "opt": {
            "mu": rng.standard_normal((16, 8)).astype(np.float32),
            "nu": rng.standard_normal((16, 8)).astype(np.float32),
        },
    }
    specs = {
        "params": {"w": PartitionSpec("fsdp"), "b": PartitionSpec("fsdp")},
        "opt": {"mu": PartitionSpec("fsdp"), "nu": PartitionSpec("fsdp")},
    }
    want = reshard_mod.leaf_checksums(host)

    def mesh_for(data: int, fsdp: int) -> Mesh:
        grid = np.array(devs[: data * fsdp]).reshape(data, fsdp)
        return Mesh(grid, ("data", "fsdp"))

    src_mesh = mesh_for(4, 2)
    placed = jax.tree.map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(src_mesh, spec)),
        host, specs,
    )
    out: dict = {"targets": []}
    with tempfile.TemporaryDirectory() as tmp:
        mgr = TrainCheckpointManager(tmp, async_save=False)
        saved = mgr.save(100, placed, wait=True)
        reshard_mod.write_topology(tmp, reshard_mod.mesh_topology(src_mesh))
        out["saved"] = bool(saved)
        out["saved_topology"] = reshard_mod.read_topology(tmp)
        for d, f in ((2, 4), (3, 2)):
            tgt_mesh = mesh_for(d, f)
            abstract = jax.tree.map(
                lambda leaf, spec: jax.ShapeDtypeStruct(
                    leaf.shape, leaf.dtype,
                    sharding=NamedSharding(tgt_mesh, spec),
                ),
                host, specs,
            )
            _s, state, report = reshard_mod.restore_resharded(
                mgr, abstract, saved_topology=out["saved_topology"]
            )
            got = reshard_mod.leaf_checksums(state) if state is not None else {}
            out["targets"].append({
                "topology": reshard_mod.mesh_topology(tgt_mesh),
                "step": report.get("step"),
                "parity_ok": bool(report.get("parity_ok")),
                "leaves": report.get("leaves"),
                "bytes_remapped": report.get("bytes_remapped"),
                "byte_parity_vs_source": got == want,
            })
    out["ok"] = bool(out["targets"]) and all(
        t["parity_ok"] and t["byte_parity_vs_source"] and t["step"] == 100
        for t in out["targets"]
    )
    return out


def _pump_until_done(engine: Any, rids: List[int], steps: int = 600) -> List[list]:
    for _ in range(steps):
        if all(engine.result(r)["status"] == "done" for r in rids):
            break
        engine.step()
    return [engine.result(r)["tokens"] for r in rids]


def reshard_migration_report(seed: int = 0) -> dict:
    """REAL gpt-tiny pool migration: a source replica holding live
    ``hold_kv`` requests and a resident shared prefix drains onto a
    destination pool of *different* chunk/lane geometry and int8 storage
    via :func:`tpu_engine.reshard.migrate_held_requests`. Every held
    request must complete on the destination (stitched streams within
    the documented one-token int8 bound of the unified baseline), and
    the prefix payload must cross both replica→replica and host-tier
    legs. Engines are caller-stepped; same seed → same weights → a
    deterministic report (the virtual migration MTTR is the cost model
    over the actual wire bytes, not wall clock)."""
    import numpy as np

    from tpu_engine import reshard as reshard_mod
    from tpu_engine.prefix_plane import HostKVTier
    from tpu_engine.serving_fleet import ServingReplicaSpec, build_replica_engine

    prompts = [[11, 7, 23, 42, 5], [3, 1, 4, 15, 9, 2]]
    max_new = 8
    src = build_replica_engine(ServingReplicaSpec(
        model_name="gpt-tiny", max_slots=4, max_len=96, prefill_chunk=16,
        prefix_cache_tokens=256,
    ))
    dst = build_replica_engine(ServingReplicaSpec(
        model_name="gpt-tiny", max_slots=4, max_len=128, prefill_chunk=32,
        kv_quant=True, prefix_cache_tokens=256,
    ))
    ref = build_replica_engine(ServingReplicaSpec(
        model_name="gpt-tiny", max_slots=2, max_len=96, prefill_chunk=16,
    ))

    # Unified baseline: the whole request on one replica.
    refs = [
        _pump_until_done(ref, [ref.submit(p, max_new_tokens=max_new)])[0]
        for p in prompts
    ]

    # Live requests: first token on the source, KV held for migration.
    first: List[int] = []
    for p in prompts:
        rid = src.submit(p, max_new_tokens=1, hold_kv=True)
        first.append(_pump_until_done(src, [rid])[0][0])

    # A shared prefix resident in the source cache (and spilled to the
    # host tier) — the prefix-plane payloads a drain must carry along.
    sys_tokens = np.random.default_rng(seed + 1).integers(1, 250, 64).tolist()
    _pump_until_done(src, [
        src.submit(sys_tokens + [9, 9], max_new_tokens=2),
        src.submit(sys_tokens + [8, 8], max_new_tokens=2),
    ])
    key = max(src._prefix_cache._entries, key=len)
    tier = HostKVTier(budget_bytes=64 << 20,
                      historian=historian_mod.MetricHistorian(),
                      clock=VirtualClock(0.0))
    tier.put(key, handoff=src.export_prefix(list(key)), now=0.0)

    migration = reshard_mod.migrate_held_requests(
        src, dst, max_new_tokens=max_new - 1
    )
    prefix_replica = reshard_mod.migrate_prefix(src, dst, list(key))
    prefix_host = reshard_mod.rehydrate_from_host(tier, list(key), dst, now=1.0)

    dst_tokens = _pump_until_done(dst, list(migration["mapping"].values()))
    completed = sum(1 for t in dst_tokens if len(t) == max_new - 1)
    reshard_mod.note_migrated_completions(completed)
    mismatches = sum(
        a != b
        for f0, tail, want in zip(first, dst_tokens, refs)
        for a, b in zip([f0, *tail], want)
    )
    return {
        "migrated": int(migration["migrated"]),
        "completed": int(completed),
        "held_left_on_src": len(src.held_requests()),
        "wire_bytes": int(migration["wire_bytes"]),
        "migration_mttr_s": round(
            reshard_mod.reshard_cost_s(migration["wire_bytes"]), 3
        ),
        "parity_mismatches": int(mismatches),
        "parity_tokens": sum(len(r) for r in refs),
        "prefix_replica_migrated": bool(prefix_replica),
        "prefix_host_rehydrated": bool(prefix_host),
        "prefix_tokens": len(key),
        "dst_kv_quant": True,
    }


def reshard_ab(
    seed: int = 0, params: ReshardLaneParams = ReshardLaneParams()
) -> dict:
    """The reshard exit gate: same seeded chip-fault trace through (a)
    same-topology warm self-heal (PR 10's MTTR reference, re-derived
    in-process), (b) topology-changing reshard resume, (c) the
    topology-locked die-and-restart baseline that loses steps waiting
    for the exact mesh — plus the real-executor restore round trip and
    the real-engine KV/prefix migration, and a byte-identical repeat."""
    events = chip_fault_timeline(seed, n_faults=params.n_faults,
                                 params=params.train)

    idx_same = CompileCacheIndex()
    seed_initial_compile(idx_same, params.train)
    same = replay_self_heal(events, params.train, compile_index=idx_same)

    idx_rs = CompileCacheIndex()
    seed_initial_compile(idx_rs, params.train)
    rs = replay_reshard_resume(events, params.train,
                               state_bytes=params.state_bytes,
                               compile_index=idx_rs)
    idx_rep = CompileCacheIndex()
    seed_initial_compile(idx_rep, params.train)
    repeat = replay_reshard_resume(events, params.train,
                                   state_bytes=params.state_bytes,
                                   compile_index=idx_rep)

    locked = replay_die_and_restart(events, params.train)
    roundtrip = reshard_roundtrip_report(seed)
    migration = reshard_migration_report(seed)

    budget = round(params.mttr_budget_ratio * same["mttr_mean_s"], 2)
    ratio = round(rs["mttr_mean_s"] / max(same["mttr_mean_s"], 1e-9), 3)
    gates = {
        "zero_lost_steps": rs["lost_steps"] == 0,
        "mttr_within_budget": rs["mttr_mean_s"] <= budget,
        "beats_topology_locked": (
            rs["wall_s"] < locked["wall_s"] and locked["lost_steps"] > 0
        ),
        "roundtrip_byte_parity": bool(roundtrip.get("ok")),
        "held_requests_complete": (
            migration["completed"] == migration["migrated"] > 0
            and migration["held_left_on_src"] == 0
        ),
        "int8_parity_within_bound": (
            migration["parity_mismatches"] <= migration["migrated"]
        ),
        "prefix_migrates_both_paths": (
            migration["prefix_replica_migrated"]
            and migration["prefix_host_rehydrated"]
        ),
        "deterministic_repeat": rs == repeat,
    }
    return {
        "same_topology": same,
        "reshard": rs,
        "topology_locked": locked,
        "roundtrip": roundtrip,
        "migration": migration,
        "mttr_ratio": ratio,
        "mttr_budget_s": budget,
        "gates": gates,
        "ok": all(gates.values()),
    }


# -- fleet speculative decoding pool lane --------------------------------------


@dataclasses.dataclass(frozen=True)
class SpecPoolLaneParams:
    """Multi-tenant speculative serving scenario at EQUAL chips: the same
    verify pool serves every request, drafts colocate in the fragmented
    HBM headroom (validated by the estimator in the A/B, costing zero
    extra chips), and each tenant's draft quality — its true acceptance
    rate α — sets how much faster its slots decode. One tenant's draft is
    junk (α far below the floor): without the spill rule it makes serving
    SLOWER than plain decode; with it, the sustained-α consult spills the
    tenant back to plain chunked decode and the fleet keeps the win."""

    duration_s: float = 480.0
    dt_s: float = 0.05
    control_period_s: float = 1.0
    n_replicas: int = 4
    slots: int = REPLICA_SLOTS
    tokens_per_slot_s: float = REPLICA_TOKENS_PER_SLOT_S
    chips_per_replica: int = 1
    prefill_s: float = 0.5
    # Propose leg: gamma sequential draft steps through the draft pool
    # (plan_serving_pool's predicted_propose_s axis) — a TTFT adder.
    draft_leg_s: float = 0.1
    spec_gamma: int = 4
    # Draft step cost as a fraction of a target step: the standard
    # speculative speedup model α(γ+1)/(1+γd) tokens per target-step.
    draft_cost_frac: float = 0.15
    # Four tenants, one with a junk draft (α = 0.06 → 0.19× plain speed
    # until spilled — strictly worse than not speculating).
    tenant_alphas: Tuple[float, ...] = (0.72, 0.65, 0.58, 0.06)
    alpha_jitter: float = 0.06
    # Offered load sits ~1.35x the plain pool's effective capacity (the
    # speculative pools' remains comfortably above it): plain decode
    # saturates and its makespan stretches, which IS the fleet-level
    # tokens/sec/chip gap the A/B gates on at equal chips.
    base_rps: float = 9.0
    burst_rps: float = 20.0
    burst_every_s: float = 120.0
    burst_len_s: float = 30.0
    mean_new_tokens: float = 96.0
    min_new_tokens: int = 8
    warmup_s: float = 120.0
    ema_beta: float = 0.25
    # Spill rule (SpecSpillConfig): floors/hysteresis tuned so the junk
    # tenant spills well inside warmup and a hovering tenant cannot flap.
    accept_floor: float = 0.35
    recover_margin: float = 0.15
    spill_window_s: float = 20.0
    sustain_consults: int = 3
    cooldown_s: float = 60.0
    canary_every: int = 8


def spec_pool_lane(
    seed: int,
    spec: bool,
    params: SpecPoolLaneParams = SpecPoolLaneParams(),
) -> dict:
    """One seeded multi-tenant run at fixed chips through the REAL
    :class:`~tpu_engine.serving_fleet.FleetRouter` — plain chunked decode
    (``spec=False``) or speculative pools (``spec=True``) with a real
    :class:`~tpu_engine.historian.MetricHistorian` carrying the
    ``serving.spec.accept_rate`` series and a real
    :class:`~tpu_engine.spec_pool.SpecSpillController` consulting it on
    the control cadence. Fully virtual-clock: same seed and mode give a
    byte-identical report."""
    from tpu_engine.serving_fleet import FleetRouter
    from tpu_engine.spec_pool import SpecSpillConfig, SpecSpillController

    clock = VirtualClock(0.0)
    rng = random.Random(seed + 7)
    n_tenants = len(params.tenant_alphas)
    spill = None
    hist = historian_mod.MetricHistorian(clock=clock)
    if spec:
        spill = SpecSpillController(
            hist,
            SpecSpillConfig(
                accept_floor=params.accept_floor,
                recover_margin=params.recover_margin,
                window_s=params.spill_window_s,
                sustain_consults=params.sustain_consults,
                cooldown_s=params.cooldown_s,
                canary_every=params.canary_every,
            ),
            clock=clock,
        )
    router = FleetRouter()
    replicas = {
        f"r{i}": SlotReplica(f"r{i}", params.slots, params.tokens_per_slot_s)
        for i in range(params.n_replicas)
    }
    trace = bursty_arrivals(
        seed,
        duration_s=params.duration_s,
        base_rps=params.base_rps,
        burst_rps=params.burst_rps,
        burst_every_s=params.burst_every_s,
        burst_len_s=params.burst_len_s,
        n_prefixes=n_tenants,  # prefix id IS the tenant id
        prefix_len=32,
        mean_new_tokens=params.mean_new_tokens,
        min_new_tokens=params.min_new_tokens,
    )
    speedup = {
        f"t{i}": a * (params.spec_gamma + 1)
        / (1.0 + params.spec_gamma * params.draft_cost_frac)
        for i, a in enumerate(params.tenant_alphas)
    }
    true_alpha = {f"t{i}": a for i, a in enumerate(params.tenant_alphas)}
    emas: Dict[str, float] = {}
    canary_seq: Dict[str, int] = {}
    legs = {"draft": 0, "plain": 0, "canary": 0}
    queue: List[dict] = []
    done: List[dict] = []
    scored = 0

    def control(t: float) -> None:
        clock.set(t)
        router.update({r.rid: r.router_stats(t) for r in replicas.values()})
        if spill is not None:
            spill.consult(sorted(emas), now=t)

    def tick(t: float) -> None:
        nonlocal scored
        clock.set(t)
        free_total = sum(r.free_slots(t) for r in replicas.values())
        while queue and free_total > 0:
            req = queue[0]
            rid = router.route(req["prompt"])
            rep = replicas.get(rid) if rid else None
            if rep is None or rep.free_slots(t) <= 0:
                break  # full pick: weights refresh next control period
            queue.pop(0)
            free_total -= 1
            tenant = f"t{req['prefix_id']}"
            req["tenant"] = tenant
            if not spec:
                rep.admit(req, params.prefill_s, 1.0)
                continue
            spilled = spill.is_spilled(tenant)
            canary = False
            if spilled:
                canary_seq[tenant] = canary_seq.get(tenant, 0) + 1
                canary = canary_seq[tenant] % params.canary_every == 0
            if not spilled:
                # Full speculative request: draft-propose leg then the
                # verify stream at the tenant's α-speedup.
                legs["draft"] += 1
                req["speculated"] = True
                rep.admit(req, params.prefill_s + params.draft_leg_s,
                          speedup[tenant])
            elif canary:
                # Canary probe: a few speculative rounds re-measure α
                # (the sample below), the bulk decodes plain.
                legs["canary"] += 1
                req["speculated"] = True
                rep.admit(req, params.prefill_s + params.draft_leg_s, 1.0)
            else:
                legs["plain"] += 1
                req["speculated"] = False
                rep.admit(req, params.prefill_s, 1.0)
        for r in replicas.values():
            r.step(t, params.dt_s, done)
        # Score newly-completed speculative legs: a jittered draw around
        # the tenant's true α, folded into its EMA and recorded as the
        # historian series the spill controller consults.
        while scored < len(done):
            req = done[scored]
            scored += 1
            if not spec or not req.get("speculated"):
                continue
            tenant = req["tenant"]
            a = true_alpha[tenant] + params.alpha_jitter * (rng.random() - 0.5)
            a = min(max(a, 0.0), 1.0)
            prev = emas.get(tenant)
            emas[tenant] = a if prev is None else (
                params.ema_beta * a + (1.0 - params.ema_beta) * prev)
            hist.record("serving.spec.accept_rate", round(emas[tenant], 6),
                        ts=t, labels={"tenant": tenant})

    run_open_loop(
        trace,
        dt=params.dt_s,
        duration_s=params.duration_s,
        pending=lambda: queue or any(r.active for r in replicas.values()),
        arrive=queue.append,
        tick=tick,
        control=control,
        control_period_s=params.control_period_s,
        safety_factor=3.0,
    )

    total_chips = params.n_replicas * params.chips_per_replica
    metrics = serving_metrics(done, [], warmup_s=params.warmup_s,
                              total_chips=total_chips, dt_s=params.dt_s)
    per_tenant: Dict[str, dict] = {}
    for tenant in sorted(true_alpha):
        lat = [(r["done_at"] - r["t"]) * 1000.0 for r in done
               if r["tenant"] == tenant and r["t"] >= params.warmup_s]
        per_tenant[tenant] = {
            "completed": len(lat),
            "p99_ms": round(percentile(lat, 0.99), 1),
            "accept_ema": (None if tenant not in emas
                           else round(emas[tenant], 4)),
        }
    out = {
        "mode": "spec" if spec else "plain",
        "total_chips": total_chips,
        "metrics": metrics,
        "legs": dict(legs),
        "tenants": per_tenant,
        "router": router.stats(),
    }
    if spill is not None:
        out["spill"] = spill.status()
        out["spill_decisions_fired"] = [
            {"rule": d.rule, "target": d.target,
             "ts": d.ts, "action": d.action}
            for d in spill.decisions if d.outcome == "fired"
        ]
        out["accept_series_samples"] = hist.samples_total
    return out


def spec_pool_ab(
    seed: int = 0,
    params: SpecPoolLaneParams = SpecPoolLaneParams(),
) -> dict:
    """The spec-pool exit gate: plain chunked decode vs speculative pools
    at EQUAL chips on the same seeded bursty trace, a byte-identical spec
    repeat (determinism), the sustained-α spill of the junk-draft tenant
    (audited DecisionRecord, fleet never below the plain baseline), and
    the estimator's structured draft-HBM rejection + the draft-role
    placement plan that backfills fragmented headroom."""
    from tpu_engine.hbm_estimate import (
        SpecHBMOversubscribed,
        estimate_serving_hbm,
    )
    from tpu_engine.placement import plan_serving_pool

    plain = spec_pool_lane(seed, spec=False, params=params)
    pool = spec_pool_lane(seed, spec=True, params=params)
    repeat = spec_pool_lane(seed, spec=True, params=params)

    p, s = plain["metrics"], pool["metrics"]
    tpsc_ratio = round(
        s["tokens_per_sec_per_chip"] / max(p["tokens_per_sec_per_chip"], 1e-9),
        4)
    p99_ratio = round(s["p99_ms"] / max(p["p99_ms"], 1e-9), 4)
    low_tenant = f"t{len(params.tenant_alphas) - 1}"
    t_low_ratio = round(
        pool["tenants"][low_tenant]["p99_ms"]
        / max(plain["tenants"][low_tenant]["p99_ms"], 1e-9), 4)
    spill_fired = [
        d for d in pool.get("spill_decisions_fired", [])
        if d["rule"] == "spill_low_acceptance" and d["target"] == low_tenant
    ]

    # Admission honesty: a draft that fits the verify pool's fragmented
    # headroom estimates cleanly (with the colocated-draft terms); one
    # that oversubscribes is refused with a structured reason.
    est = estimate_serving_hbm(
        "llama-1b", params.slots, 2048,
        draft_model_name="gpt-tiny", device_budget_gib=16.0,
    )
    rejection = None
    try:
        estimate_serving_hbm(
            "llama-1b", params.slots, 2048,
            draft_model_name="gpt-tiny", device_budget_gib=0.5,
        )
    except SpecHBMOversubscribed as e:
        rejection = e.reason
    # Placement: the draft role ranks by propose latency and deliberately
    # fits inside small fragmented headroom (2 GiB here).
    draft_plans = plan_serving_pool(
        "gpt-tiny", "draft", params.n_replicas, hbm_free_gib=2.0,
        max_len=2048, spec_gamma=params.spec_gamma,
    )

    gates = {
        "spec_beats_plain_tokens_per_chip": tpsc_ratio >= 1.2,
        "p99_no_worse": p99_ratio <= 1.02,
        "low_alpha_tenant_spilled": (
            low_tenant in pool.get("spill", {}).get("spilled", [])
            and len(spill_fired) > 0
        ),
        "spilled_tenant_not_below_plain_baseline": t_low_ratio <= 1.10,
        "deterministic_repeat": pool == repeat,
        "draft_hbm_rejected": (
            rejection is not None
            and rejection.get("kind") == "spec_hbm_oversubscribed"
            and est is not None and est.device_total_gib > 0
        ),
        "draft_plan_feasible": (
            len(draft_plans) > 0 and draft_plans[0].feasible
            and draft_plans[0].predicted_propose_s > 0
        ),
    }
    return {
        "plain": plain,
        "spec": pool,
        "tokens_per_sec_per_chip_ratio": tpsc_ratio,
        "p99_ratio": p99_ratio,
        "low_alpha_tenant": low_tenant,
        "low_alpha_tenant_p99_ratio": t_low_ratio,
        "spill_decisions_fired": pool.get("spill_decisions_fired", []),
        "draft_hbm_rejection": rejection,
        "spec_replica_gib": None if est is None else est.device_total_gib,
        "draft_plan_label": (
            draft_plans[0].label if draft_plans else None),
        "gates": gates,
        "ok": all(gates.values()),
    }


# -- durable control plane: crash / restore / re-adoption lane -----------------


@dataclasses.dataclass(frozen=True)
class CtlCrashLaneParams:
    """One control-plane crash scenario: a storm of training submissions,
    chaos preemptions and serving traffic, with the scheduler/fleet host
    killed mid-storm (``crash_at_poll``) and rebuilt from its write-ahead
    journal. The no-crash run of the SAME workload, measured from the
    same poll, is the MTTR reference the 1.5× budget gates against."""

    n_train_jobs: int = 24
    n_requests: int = 36
    n_replicas: int = 2
    max_concurrent: int = 8
    submit_chunk: int = 6
    requests_per_poll: int = 3
    poll_dt_s: float = 2.0
    snapshot_every_polls: int = 8
    n_chaos_faults: int = 8
    crash_at_poll: int = 10
    job_base_s: float = 20.0
    job_spread_s: float = 6.0
    job_spread_mod: int = 7
    # Offered decode load (requests_per_poll × tokens_per_request) runs
    # ~2× the fleet's per-poll token capacity, so a standing backlog of
    # held/pending requests exists at the kill point — the crash must
    # catch requests in every phase: done, in-flight, and still queued.
    tokens_per_request: int = 40
    engine_tokens_per_poll: int = 32
    replica_slots: int = 8
    mttr_budget_ratio: float = 1.5


class _CtlTrainJob(_ScaleJob):
    """:class:`_ScaleJob` plus the chaos seam the storm needs: a running
    attempt can be preempted (the scheduler then requeues it at its
    original seq) or simply vanish with the crashed control-plane host."""

    __slots__ = ()

    def preempt(self, reason: str = "chaos-storm") -> None:
        if self.status == self._st.RUNNING:
            self.status = self._st.PREEMPTED
            self.preemption_reason = reason
            self.current_step = max(
                0, int(self._sim_s - max(self._done_at - self._clock(), 0.0))
            )


class _CtlLaneEngine:
    """Slot-model decode engine for the crash lane: each control poll
    grants it a token budget, spread round-robin over active requests —
    deterministic, thread-free, and it survives its control plane (the
    whole point: the data plane keeps decoding while the brain is dead)."""

    def __init__(self, slots: int):
        self.slots = int(slots)
        self._reqs: Dict[int, dict] = {}
        self._seq = 0

    def submit(self, prompt: Any, max_new_tokens: int = 64,
               temperature: float = 0.0) -> int:
        self._seq += 1
        self._reqs[self._seq] = {"need": int(max_new_tokens), "tokens": []}
        return self._seq

    def step(self, budget: int) -> None:
        active = [r for r in self._reqs.values()
                  if len(r["tokens"]) < r["need"]]
        while budget > 0 and active:
            for r in list(active):
                if budget <= 0:
                    break
                r["tokens"].append(1)
                budget -= 1
                if len(r["tokens"]) >= r["need"]:
                    active.remove(r)

    def result(self, rid: int) -> dict:
        r = self._reqs[rid]  # KeyError IS the fleet's redispatch signal
        done = len(r["tokens"]) >= r["need"]
        return {"status": "done" if done else "running",
                "tokens": list(r["tokens"])}

    def stats(self) -> dict:
        active = sum(1 for r in self._reqs.values()
                     if len(r["tokens"]) < r["need"])
        return {"slots": self.slots, "active_slots": active, "prefilling": 0,
                "queued": 0, "queued_handoffs": 0,
                "tokens_per_sec_recent": 100.0}


class _CtlReplicaJob:
    """Thread-free serving replica job: the engine is built synchronously
    and ready the moment the scheduler admits the replica."""

    __slots__ = ("_st", "status", "engine", "engine_ready", "current_step",
                 "watcher", "preemption_reason", "_stop")

    def __init__(self, slots: int, status_enum):
        self._st = status_enum
        self.status = status_enum.PENDING
        self.engine = _CtlLaneEngine(slots)
        self.engine_ready = threading.Event()
        self.current_step = 0
        self.watcher = None
        self.preemption_reason = None
        self._stop = threading.Event()

    def start(self) -> None:
        self.status = self._st.RUNNING
        self.engine_ready.set()

    @property
    def is_alive(self) -> bool:
        if self.status == self._st.RUNNING and self._stop.is_set():
            self.status = self._st.STOPPED
        return self.status in (self._st.PENDING, self._st.RUNNING)

    def join(self, timeout: Optional[float] = None) -> None:
        return None

    def describe(self) -> dict:
        return {"status": getattr(self.status, "value", str(self.status)),
                "step": self.current_step}


def ctl_crash_lane(
    seed: int,
    crash: bool,
    params: CtlCrashLaneParams = CtlCrashLaneParams(),
) -> dict:
    """One seeded storm through the REAL control plane — FleetScheduler +
    ServingFleet journaling every state change to a
    :class:`~tpu_engine.journal.ControlPlaneJournal` — with chaos
    preemptions drawn from ``FaultPlan.random(seed)`` and, when ``crash``
    is set, a ``FaultKind.CONTROLPLANE_CRASH`` consumed mid-storm via the
    injector seam.

    The crash drops the scheduler and fleet objects on the floor (no
    shutdown — the host died), leaves a torn half-written line on the
    live journal file, and lets live reality diverge from the journal:
    every third running training job and the first serving replica die
    with the host, the rest keep running orphaned. Recovery builds fresh
    objects and runs ``restore`` + ``re_adopt`` against a fresh journal
    handle — twice, from the same bytes, to prove the rebuild is
    byte-identical (``snapshot_state`` digests) — then drives the storm
    to completion. MTTR is virtual-clock time from the kill to the last
    journaled obligation (every training job completed, every accepted
    request answered)."""
    import gc

    from tpu_engine import goodput as goodput_mod
    from tpu_engine import journal as journal_mod
    from tpu_engine import tracing as tracing_mod
    from tpu_engine.mesh_runtime import MeshConfig
    from tpu_engine.scheduler import FleetScheduler, JobPriority, SubmissionState
    from tpu_engine.serving_fleet import (
        AutoscalerConfig,
        ReplicaAutoscaler,
        ServingFleet,
        ServingReplicaSpec,
    )
    from tpu_engine.sharding import TPUTrainConfig
    from tpu_engine.supervisor import JobStatus

    p = params
    vclock = VirtualClock(0.0)
    rec = FlightRecorder(
        max_spans=4096, max_events=8192, clock=vclock,
        id_factory=deterministic_ids("ctlcrash"),
    )
    hist = historian_mod.MetricHistorian(clock=vclock)
    ledger = GoodputLedger(clock=vclock, max_tracked=4096)

    old_rec = tracing_mod.get_recorder()
    old_hist = historian_mod.get_historian()
    old_ledger = goodput_mod.get_ledger()
    tracing_mod.set_recorder(rec)
    historian_mod.set_historian(hist)
    goodput_mod.set_ledger(ledger)
    gc_was_enabled = gc.isenabled()
    gc.disable()
    tmp = tempfile.TemporaryDirectory(prefix="ctl_crash_")
    try:
        journal = journal_mod.ControlPlaneJournal(
            os.path.join(tmp.name, "ctl_journal.jsonl"), clock=vclock
        )

        cfg = TPUTrainConfig(
            model_name="gpt-tiny", mesh=MeshConfig(data=1, fsdp=1),
            micro_batch_size=1, seq_len=32, precision="fp32",
            total_steps=5, activation_checkpointing=False,
        )
        jcount = iter(range(1 << 30))

        def make_train_job(sub) -> _CtlTrainJob:
            n = next(jcount)
            return _CtlTrainJob(
                vclock, p.job_base_s + p.job_spread_s * (n % p.job_spread_mod),
                JobStatus,
            )

        def new_sched() -> FleetScheduler:
            s = FleetScheduler(
                max_concurrent_jobs=p.max_concurrent,
                backfill_depth=p.max_concurrent,
                job_factory=make_train_job,
                poll_interval_s=3600.0,
                grow_back=False,
                hetero_rebalance=False,
                max_finished_history=4096,
            )
            s._ensure_thread = lambda: None  # the lane owns the poll cadence
            return s

        spec = ServingReplicaSpec(
            model_name="gpt-tiny", max_slots=p.replica_slots, max_len=128
        )

        def replica_job_factory(sub, spec_) -> _CtlReplicaJob:
            return _CtlReplicaJob(spec_.max_slots, JobStatus)

        def new_fleet(s, j) -> ServingFleet:
            return ServingFleet(
                s, spec,
                autoscaler=ReplicaAutoscaler(AutoscalerConfig(
                    min_replicas=1, max_replicas=max(4, p.n_replicas),
                )),
                replica_job_factory=replica_job_factory,
                journal=j,
            )

        sched = new_sched()
        sched.attach_journal(journal)
        fleet = new_fleet(sched, journal)
        fleet.scale_to(p.n_replicas)

        # Chaos storm: the SEEDED random plan picks the preemption polls;
        # the crash itself is an explicit spec consumed through the
        # injector seam (never part of random draws — see faults.py).
        storm = FaultPlan.random(
            seed, n_faults=p.n_chaos_faults, max_step=4 * p.crash_at_poll
        )
        storm_polls = sorted(
            s.at_step for s in storm.specs if s.at_step is not None
        )
        injector = FaultInjector(FaultPlan(seed=seed, specs=(
            [FaultSpec(kind=FaultKind.CONTROLPLANE_CRASH,
                       at_step=p.crash_at_poll)] if crash else []
        )))

        train_sids: List[str] = []
        fids: List[str] = []
        done_fids: set = set()
        submitted = 0
        polls = storms = 0
        crashed = False
        t_crash: Optional[float] = None
        recovery: Optional[dict] = None
        readopt: Optional[dict] = None
        double_identical = False
        held_recovered: List[str] = []
        t_done: Optional[float] = None
        max_polls = 400 + 40 * p.n_train_jobs

        def _train_done() -> int:
            return sum(
                1 for sid in train_sids
                if (s := sched.get(sid)) is not None
                and s.state == SubmissionState.COMPLETED
            )

        while True:
            # -- offered load ------------------------------------------------
            if submitted < p.n_train_jobs:
                k = min(p.submit_chunk, p.n_train_jobs - submitted)
                for _ in range(k):
                    sub = sched.submit(
                        cfg, priority=JobPriority.NORMAL,
                        submitter=f"team-{submitted % 4}",
                    )
                    train_sids.append(sub.submission_id)
                    submitted += 1
            if len(fids) < p.n_requests:
                for _ in range(min(p.requests_per_poll,
                                   p.n_requests - len(fids))):
                    prompt = [(seed * 131 + len(fids) * 17 + k) % 5003
                              for k in range(16)]
                    fids.append(fleet.submit_request(
                        prompt, max_new_tokens=p.tokens_per_request,
                    ))
            # -- chaos preemptions (the storm) -------------------------------
            while storm_polls and storm_polls[0] <= polls:
                storm_polls.pop(0)
                storms += 1
                for sid in train_sids:
                    s = sched.get(sid)
                    if (
                        s is not None
                        and s.state == SubmissionState.RUNNING
                        and isinstance(s.job, _CtlTrainJob)
                    ):
                        s.job.preempt()
                        break
            # -- one control pass --------------------------------------------
            sched.poll()
            for eng in fleet.running_replicas().values():
                eng.step(p.engine_tokens_per_poll)
            for fid in fids:
                if fid in done_fids:
                    continue
                if fleet.result(fid).get("status") == "done":
                    done_fids.add(fid)
            polls += 1
            if polls % p.snapshot_every_polls == 0:
                journal.snapshot(
                    journal_mod.collect_sections(scheduler=sched,
                                                 serving=fleet),
                    ts=vclock.now(),
                )
            # -- the kill point ----------------------------------------------
            if crash and not crashed and injector.take_controlplane_crash(polls):
                crashed = True
                t_crash = vclock.now()
                # Live reality at the moment of death: every third running
                # training job and the first replica die WITH the host;
                # everything else keeps running orphaned.
                live_jobs: Dict[str, Any] = {}
                nth_train = 0
                replica_vanished = False
                for s in sorted(sched._subs.values(), key=lambda x: x.seq):
                    if s.state not in (
                        SubmissionState.RUNNING, SubmissionState.CANCELLING
                    ) or s.job is None:
                        continue
                    if s.workload == "training":
                        nth_train += 1
                        if nth_train % 3 == 0:
                            continue  # died with the host
                    elif not replica_vanished:
                        replica_vanished = True
                        continue  # this replica's host died too
                    live_jobs[s.submission_id] = s.job
                # The crash lands mid-append: a torn half-line on the live
                # file that ingestion must skip, not raise on.
                with open(journal.path, "a", encoding="utf-8") as f:
                    f.write('{"record":"event","kind":"sched.su')
                # The old process is gone — no shutdown, no cleanup.
                journal2 = journal_mod.ControlPlaneJournal(
                    journal.path, clock=vclock
                )
                journal_mod.set_active_journal(journal2)
                sched2 = new_sched()
                recovery = sched2.restore(
                    journal2, live_jobs=live_jobs, now=vclock.now()
                )
                digest1 = json.dumps(sched2.snapshot_state(), sort_keys=True)
                # Double recovery from the same bytes must be byte-identical.
                sched3 = new_sched()
                sched3.restore(journal2, live_jobs=live_jobs,
                               now=vclock.now())
                digest2 = json.dumps(sched3.snapshot_state(), sort_keys=True)
                fleet3 = new_fleet(sched3, None)
                r3 = fleet3.re_adopt(journal2, redispatch=False)
                # Now the real recovery: re-adopt + re-dispatch the
                # vanished replica, then a fresh settling snapshot.
                fleet2 = new_fleet(sched2, None)
                readopt = fleet2.re_adopt(journal2)
                double_identical = (
                    digest1 == digest2
                    and readopt["held_fids"] == r3["held_fids"]
                    and readopt["replicas_readopted"]
                    == r3["replicas_readopted"]
                )
                held_recovered = list(readopt["held_fids"])
                sched, fleet, journal = sched2, fleet2, journal2
                journal.snapshot(
                    journal_mod.collect_sections(scheduler=sched,
                                                 serving=fleet),
                    ts=vclock.now(),
                )
            if not crash and t_crash is None and polls >= p.crash_at_poll:
                # The no-crash reference clocks its "MTTR" from the same
                # poll the crash run dies at.
                t_crash = vclock.now()
            # -- done? -------------------------------------------------------
            if (
                submitted >= p.n_train_jobs
                and len(fids) >= p.n_requests
                and _train_done() >= p.n_train_jobs
                and len(done_fids) >= p.n_requests
            ):
                t_done = vclock.now()
                break
            vclock.advance(p.poll_dt_s)
            if polls > max_polls:
                raise RuntimeError(
                    f"ctl_crash lane wedged: {_train_done()}/{p.n_train_jobs} "
                    f"jobs, {len(done_fids)}/{p.n_requests} requests "
                    f"after {polls} polls"
                )

        mttr_s = round(t_done - (t_crash if t_crash is not None else 0.0), 3)
        if crash:
            journal_mod.note_mttr(mttr_s)
        out = {
            "crash": crash,
            "polls": polls,
            "storm_preemptions": storms,
            "sim_s": round(vclock.now(), 3),
            "t_crash": t_crash,
            "mttr_s": mttr_s,
            "train_submitted": submitted,
            "train_completed": _train_done(),
            "train_subs_final": sum(
                1 for sid in train_sids if sched.get(sid) is not None
            ),
            "requests_total": len(fids),
            "requests_completed": len(done_fids),
            "journal": journal.stats(),
        }
        if crash:
            held_done = sum(1 for fid in held_recovered if fid in done_fids)
            out.update({
                "recovery": recovery,
                "re_adopt": {
                    k: v for k, v in (readopt or {}).items() if k != "ingest"
                },
                "double_recovery_identical": double_identical,
                "held_recovered": len(held_recovered),
                "held_done": held_done,
                "ingest": (recovery or {}).get("ingest", {}),
            })
        return out
    finally:
        journal_mod.clear_active_journal()
        tmp.cleanup()
        if gc_was_enabled:
            gc.enable()
        tracing_mod.set_recorder(old_rec)
        historian_mod.set_historian(old_hist)
        goodput_mod.set_ledger(old_ledger)


def ctl_crash_ab(
    seed: int = 0,
    params: CtlCrashLaneParams = CtlCrashLaneParams(),
) -> dict:
    """The durable-control-plane exit gate: the same seeded storm with and
    without a mid-storm control-plane kill. Gates: nothing the dead
    process had accepted is lost or duplicated, every held serving
    request completes, orphans are re-adopted (never re-launched), the
    vanished replica is re-dispatched, double recovery from the same
    journal bytes is byte-identical, the torn tail is skipped not raised,
    and crash-recovery MTTR stays within ``mttr_budget_ratio`` of the
    no-crash reference."""
    base = ctl_crash_lane(seed, crash=False, params=params)
    cr = ctl_crash_lane(seed, crash=True, params=params)

    budget = round(params.mttr_budget_ratio * base["mttr_s"], 3)
    ratio = round(cr["mttr_s"] / max(base["mttr_s"], 1e-9), 3)
    ingest = cr.get("ingest", {})
    gates = {
        "zero_lost_submissions": (
            cr["train_completed"] == params.n_train_jobs
        ),
        "zero_duplicated_submissions": (
            cr["train_subs_final"] == params.n_train_jobs
            and cr["train_submitted"] == params.n_train_jobs
        ),
        "held_requests_complete": (
            cr["held_recovered"] > 0
            and cr["held_done"] == cr["held_recovered"]
            and cr["requests_completed"] == params.n_requests
        ),
        "orphans_readopted": (
            (cr.get("recovery") or {}).get("readopted", 0) > 0
        ),
        "vanished_training_requeued": (
            (cr.get("recovery") or {}).get("requeued_vanished", 0) >= 1
        ),
        "vanished_replica_redispatched": (
            (cr.get("re_adopt") or {}).get("replicas_redispatched", 0) >= 1
        ),
        "no_phantom_double_grants": (
            (cr.get("recovery") or {}).get("double_grants", 0) == 0
        ),
        "double_recovery_identical": bool(cr.get("double_recovery_identical")),
        "torn_tail_skipped_not_raised": (
            (ingest.get("skipped_by_reason") or {}).get("torn_tail", 0) == 1
        ),
        "mttr_within_budget": cr["mttr_s"] <= budget,
    }
    return {
        "baseline": base,
        "crashed": cr,
        "mttr_ratio": ratio,
        "mttr_budget_s": budget,
        "gates": gates,
        "ok": all(gates.values()),
    }
