"""Fleet autopilot: the unified control loop's decision audit trail.

Covers the PR's acceptance surface: hysteresis (sustained-trend consult
counts), the blast-radius guards (per-target cooldown,
max-actions-per-window), dry-run producing byte-identical
DecisionRecords to an armed run on the same seeded fault plan (with
zero actuations), the headless historian tick (no ``/metrics`` scrape
anywhere), the IncidentCorrelator action leg with ``action_source``,
the subsumed scheduler/serving/precompile ticks, the scheduler's
autopilot quarantine lifecycle, the HTTP surface, and the twin chaos
A/B lane's gates."""

import asyncio
import threading

import httpx
import pytest
from aiohttp import web

from tpu_engine import autopilot as autopilot_mod
from tpu_engine.autopilot import (
    RULES,
    SUPPRESSION_REASONS,
    AutopilotConfig,
    FleetAutopilot,
)
from tpu_engine.compile_index import CompileCacheIndex, PrecompileWorker
from tpu_engine.historian import IncidentCorrelator, MetricHistorian
from tpu_engine.tracing import FlightRecorder
from tpu_engine.twin import VirtualClock, deterministic_ids, host_slow_plan
from tpu_engine.faults import FaultInjector

# ---------------------------------------------------------------------------
# rig: scripted planes on a virtual clock
# ---------------------------------------------------------------------------


def make_rig(
    dry_run: bool = False,
    *,
    sustain: int = 3,
    cooldown_s: float = 100.0,
    max_actions: int = 2,
    blame_threshold: int = 2,
    max_decisions: int = 512,
    actuator=None,
):
    clock = VirtualClock(1000.0)
    rec = FlightRecorder(
        max_spans=4096, max_events=4096, clock=clock,
        id_factory=deterministic_ids("t"),
    )
    hist = MetricHistorian(clock=clock)
    corr = IncidentCorrelator(
        clock=clock, merge_window_s=10.0, stale_after_s=1e9
    )
    drained = []
    ap = FleetAutopilot(
        AutopilotConfig(
            trend_window_s=60.0,
            sustain_consults=sustain,
            cooldown_s=cooldown_s,
            max_actions_per_window=max_actions,
            action_window_s=10_000.0,
            fault_blame_threshold=blame_threshold,
            host_health_floor=0.9,
            max_decisions=max_decisions,
        ),
        dry_run=dry_run,
        historian=hist,
        correlator=corr,
        recorder=rec,
        actuators={
            "drain_host": actuator
            or (lambda r: drained.append(r.action["params"]["device_index"]))
        },
        clock=clock,
        id_factory=deterministic_ids("apd"),
        trace_id="fleet",
    )
    return clock, rec, hist, corr, ap, drained


def blame(rec, hist, t: float, idx: int = 3, n: int = 2, health: float = 0.5):
    """Script the drain-rule trigger: n recorder blame events + an
    unhealthy retained health sample for host idx at time t."""
    for i in range(n):
        rec.event(
            "host_slow", kind="fault", trace_id="fleet", ts=t,
            attrs={"device_index": idx, "step": i},
        )
    hist.record("hetero_host_health", health, ts=t, labels={"host": str(idx)})


# ---------------------------------------------------------------------------
# hysteresis + guards
# ---------------------------------------------------------------------------


def test_sustained_trend_consult_counts():
    """The rule fires only on the Nth *consecutive* breaching consult;
    each earlier consult is a recorded trend-not-sustained suppression."""
    clock, rec, hist, corr, ap, drained = make_rig(sustain=3)
    outcomes = []
    for _ in range(3):
        blame(rec, hist, clock.t)
        (d,) = ap.tick(now=clock.t)
        outcomes.append((d.outcome, d.suppressed_reason,
                         d.hysteresis["streak"]))
        clock.advance(5.0)
    assert outcomes == [
        ("suppressed", "trend-not-sustained", 1),
        ("suppressed", "trend-not-sustained", 2),
        ("fired", None, 3),
    ]
    assert drained == [3]


def test_streak_resets_when_signal_goes_quiet():
    clock, rec, hist, corr, ap, _ = make_rig(sustain=3)
    for _ in range(2):
        blame(rec, hist, clock.t)
        ap.tick(now=clock.t)
        clock.advance(5.0)
    # Signal absent for longer than the trend window: no consult at all,
    # and the streak starts over on the next breach.
    clock.advance(120.0)
    assert ap.tick(now=clock.t) == []
    blame(rec, hist, clock.t)
    (d,) = ap.tick(now=clock.t)
    assert d.hysteresis["streak"] == 1
    assert d.suppressed_reason == "trend-not-sustained"


def test_per_target_cooldown():
    clock, rec, hist, corr, ap, drained = make_rig(sustain=1, cooldown_s=100.0)
    blame(rec, hist, clock.t)
    (d1,) = ap.tick(now=clock.t)
    assert d1.outcome == "fired"
    clock.advance(10.0)
    blame(rec, hist, clock.t)
    (d2,) = ap.tick(now=clock.t)
    assert d2.outcome == "suppressed"
    assert d2.suppressed_reason == "cooldown-active"
    assert d2.hysteresis["cooldown_remaining_s"] == pytest.approx(90.0)
    # Past the cooldown the same target may fire again.
    clock.advance(95.0)
    blame(rec, hist, clock.t)
    (d3,) = ap.tick(now=clock.t)
    assert d3.outcome == "fired"
    assert drained == [3, 3]


def test_max_actions_per_window_blast_radius():
    """The budget is loop-wide: a third target's decision is suppressed
    even though its own streak and cooldown would allow it."""
    clock, rec, hist, corr, ap, drained = make_rig(
        sustain=1, max_actions=2, cooldown_s=1.0
    )
    for idx in (1, 2, 5):
        blame(rec, hist, clock.t, idx=idx)
    decisions = ap.tick(now=clock.t)
    assert [d.outcome for d in decisions] == ["fired", "fired", "suppressed"]
    assert decisions[2].suppressed_reason == "blast-radius"
    assert decisions[2].hysteresis["actions_in_window"] == 2
    assert drained == [1, 2]


def test_no_actuator_is_a_structured_suppression():
    clock, rec, hist, corr, ap, _ = make_rig(sustain=1)
    ap.actuators = {}  # nothing wired: the loop must say so, not crash
    blame(rec, hist, clock.t)
    (d,) = ap.tick(now=clock.t)
    assert (d.outcome, d.suppressed_reason) == ("suppressed", "no-actuator")
    assert ap.stats()["actuations_total"] == 0


def test_decision_ring_is_bounded():
    clock, rec, hist, corr, ap, _ = make_rig(sustain=1, max_decisions=4,
                                             cooldown_s=1e9)
    for _ in range(6):
        blame(rec, hist, clock.t)
        ap.tick(now=clock.t)
        clock.advance(5.0)
    s = ap.stats()
    assert s["decisions_retained"] == 4
    assert s["decisions_dropped_total"] == 2
    assert len(ap.decisions(limit=0)) == 4


# ---------------------------------------------------------------------------
# every consult -> exactly one explainable record
# ---------------------------------------------------------------------------


def test_exactly_one_record_per_consult_with_inputs_and_incident_link():
    clock, rec, hist, corr, ap, _ = make_rig(sustain=2)
    # Quiet loop: no signal, no records at all.
    assert ap.tick(now=clock.t) == []
    assert ap.stats()["decisions_total"] == 0
    blame(rec, hist, clock.t)
    (d,) = ap.tick(now=clock.t)
    # Historian range-query inputs: the consulted series, aggregate and
    # window — never an instant sample.
    (q,) = d.inputs["queries"]
    assert q["series"] == "hetero_host_health"
    assert q["labels"] == {"host": "3"}
    assert q["agg"] == "avg"
    assert q["window_s"] == 60.0
    assert q["value"] == pytest.approx(0.5)
    assert q["count"] == 1
    assert d.inputs["evidence"]["blame_events"] == 2
    # The blame events opened an incident before the rules ran; its id
    # is the decision's incident link.
    assert d.inputs["incidents"], "decision carries no incident link"
    inc_id = d.inputs["incidents"][0]
    assert corr.get(inc_id) is not None
    # Mirrored as a kind="autopilot" span on the flight recorder.
    spans = rec.spans(kind="autopilot", limit=0)
    assert len(spans) == 1
    assert spans[0]["attrs"]["decision_id"] == d.decision_id
    assert spans[0]["attrs"]["incident_ids"] == [inc_id]


def test_correlator_attaches_action_leg_with_action_source():
    clock, rec, hist, corr, ap, _ = make_rig(sustain=1)
    blame(rec, hist, clock.t)
    (d,) = ap.tick(now=clock.t)
    assert d.outcome == "fired"
    (inc,) = corr.incidents(limit=0)
    legs = [e for e in inc["timeline"]
            if e["role"] == "action" and e["kind"] == "autopilot"]
    assert len(legs) == 1
    assert legs[0]["action_source"] == "autopilot"
    assert legs[0]["attrs"]["decision_id"] == d.decision_id
    assert inc["state"] == "mitigating"


def test_dry_run_action_leg_is_sourced_dryrun_and_human_stays_human():
    clock, rec, hist, corr, ap, _ = make_rig(sustain=1, dry_run=True)
    blame(rec, hist, clock.t)
    ap.tick(now=clock.t)
    # A human-operated mitigation on the same incident keeps its source.
    rec.event(
        "hetero_quarantine", kind="scheduler", trace_id="fleet", ts=clock.t,
        attrs={"devices": [3]},
    )
    corr.ingest(recorder=rec, now=clock.t)
    (inc,) = corr.incidents(limit=0)
    sources = sorted(
        e["action_source"] for e in inc["timeline"] if e["role"] == "action"
    )
    assert sources == ["autopilot-dryrun", "human"]


# ---------------------------------------------------------------------------
# dry-run: byte-identical stream, zero actuations
# ---------------------------------------------------------------------------


def _replay_plan_through(ap_dry_run: bool, seed: int):
    """Feed the same seeded HOST_SLOW fault plan through a rig. The spy
    actuator records but does not feed back into the observed series, so
    armed and shadow runs see identical inputs end to end."""
    plan = host_slow_plan(seed)
    inj = FaultInjector(plan)
    inj.arm()
    actuations = []
    clock, rec, hist, corr, ap, _ = make_rig(
        ap_dry_run, sustain=3, cooldown_s=30.0,
        actuator=lambda r: actuations.append(r.action["params"]),
    )
    for step in range(1, 61):
        spec = inj.take_host_slow(step)
        if spec is not None:
            idx = int(spec.device_index or 0)
            rec.event(
                "host_slow", kind="fault", trace_id="fleet", ts=clock.t,
                attrs={"step": step, "device_index": idx},
            )
            hist.record(
                "hetero_host_health", 0.75, ts=clock.t,
                labels={"host": str(idx)},
            )
        clock.advance(0.5)
        if step % 5 == 0:
            ap.tick(now=clock.t)
    return ap, actuations


def test_dry_run_byte_identical_to_armed_on_same_seeded_plan():
    armed, armed_actuations = _replay_plan_through(False, seed=0)
    shadow, shadow_actuations = _replay_plan_through(True, seed=0)
    armed_stream = [r.to_json() for r in armed._records]
    shadow_stream = [r.to_json() for r in shadow._records]
    assert armed_stream, "seeded plan produced no decisions"
    # Byte-for-byte: same ids, same inputs, same hysteresis, same
    # outcomes — mode is not part of the serialized record.
    assert armed_stream == shadow_stream
    assert any(r.outcome == "fired" for r in armed._records)
    # ...but only the armed run touched the fleet.
    assert len(armed_actuations) == armed.stats()["fired_total"] > 0
    assert shadow_actuations == []
    assert shadow.stats()["actuations_total"] == 0
    assert shadow.stats()["fired_total"] == armed.stats()["fired_total"]


# ---------------------------------------------------------------------------
# satellite: headless historian tick (no scrape anywhere)
# ---------------------------------------------------------------------------


def test_autopilot_tick_drives_historian_rollup_without_scrape():
    clock, rec, hist, corr, ap, _ = make_rig()
    seen = []
    hist.add_collector(lambda now: seen.append(now) or {"fleet_gauge": 1.0})
    assert hist.stats()["ticks_total"] == 0
    for _ in range(3):
        ap.tick(now=clock.t)
        clock.advance(11.0)
    # The collector ran and the rollup/retention tick advanced — with no
    # /metrics scrape in sight.
    assert hist.stats()["ticks_total"] == 3
    assert len(seen) == 3
    assert hist.query(
        "fleet_gauge", t0=0.0, t1=clock.t, agg="count"
    )["count"] == 3


# ---------------------------------------------------------------------------
# subsumed ticks: scheduler poll, serving tick, precompile pump
# ---------------------------------------------------------------------------


class _SpyScheduler:
    def __init__(self):
        self.polls = 0

    def poll(self):
        self.polls += 1


class _SpyServing:
    def __init__(self):
        self.ticks = []
        self.desired_replicas = 1

    def tick(self, now):
        self.ticks.append(now)


def test_tick_subsumes_the_three_control_loops():
    clock, rec, hist, corr, ap, _ = make_rig()
    sched, serving = _SpyScheduler(), _SpyServing()
    index = CompileCacheIndex(path=None)
    worker = PrecompileWorker(
        index, compile_fn=lambda task: None, clock=clock, background=False
    )
    ap.scheduler, ap.serving_fleet, ap.precompiler = sched, serving, worker
    ap.actuators = {}
    assert worker.request("layout-a", label="grow-back") == "queued"
    assert worker._thread is None, "background=False must not spawn a thread"
    (d,) = ap.tick(now=clock.t)
    # One pass drove all three planes deterministically on the caller's
    # thread: the scheduler polled, the fleet ticked, and the queued
    # precompile ran through the kick_precompile decision's actuator.
    assert sched.polls == 1
    assert serving.ticks == [clock.t]
    assert d.rule == "kick_precompile"
    assert d.outcome == "fired"
    assert worker.stats()["completed_total"] == 1
    assert worker._thread is None
    # The rule consulted the depth *series* the tick itself retains.
    assert {q["series"] for q in d.inputs["queries"]} == {
        "precompile_queue_depth"
    }
    # Queue drained: the next tick has no consult.
    clock.advance(5.0)
    assert ap.tick(now=clock.t) == []


# ---------------------------------------------------------------------------
# scheduler: autopilot quarantine lifecycle
# ---------------------------------------------------------------------------


def test_scheduler_autopilot_quarantine_survives_heal_pass():
    from tpu_engine.scheduler import FleetScheduler

    sched = FleetScheduler(poll_interval_s=3600.0, hetero_quarantine_ttl_s=50.0)
    try:
        assert sched.quarantine_device(2, owner="autopilot", now=0.0)
        assert not sched.quarantine_device(2, now=0.0), "idempotent"
        # The owner-vouch heal pass must NOT release it as owner-gone
        # ("autopilot" is no submission) — only the TTL or an explicit
        # release does.
        sched._heal_quarantine(now=10.0)
        assert 2 in sched._hetero_quarantined
        assert sched.release_quarantine(2)
        assert 2 not in sched._hetero_quarantined
        # TTL expiry path.
        sched.quarantine_device(5, now=0.0)
        sched._heal_quarantine(now=60.0)
        assert 5 not in sched._hetero_quarantined
    finally:
        sched.shutdown()


# ---------------------------------------------------------------------------
# HTTP surface
# ---------------------------------------------------------------------------


@pytest.fixture()
def client():
    from backend.main import create_app

    loop = asyncio.new_event_loop()
    started = threading.Event()
    state = {}

    def run():
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(create_app())
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, "127.0.0.1", 0)
        loop.run_until_complete(site.start())
        state["port"] = runner.addresses[0][1]
        started.set()
        loop.run_forever()
        loop.run_until_complete(runner.cleanup())

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert started.wait(timeout=30)
    prev = autopilot_mod._autopilot
    with httpx.Client(
        base_url=f"http://127.0.0.1:{state['port']}", timeout=60
    ) as c:
        yield c
    autopilot_mod.set_autopilot(prev)
    loop.call_soon_threadsafe(loop.stop)
    t.join(timeout=10)


def test_autopilot_http_surface(client):
    clock, rec, hist, corr, ap, drained = make_rig(sustain=1)
    autopilot_mod.set_autopilot(ap)
    blame(rec, hist, clock.t)
    ap.tick(now=clock.t)

    r = client.get("/api/v1/autopilot")
    assert r.status_code == 200
    body = r.json()
    assert body["mode"] == "armed"
    assert body["rules"] == list(RULES)
    assert body["suppression_reasons"] == list(SUPPRESSION_REASONS)
    assert body["stats"]["decisions_total"] == 1

    r = client.get("/api/v1/autopilot/decisions")
    assert r.status_code == 200
    (dec,) = r.json()["decisions"]
    assert dec["rule"] == "drain_host"
    assert dec["outcome"] == "fired"
    assert dec["inputs"]["queries"] and dec["inputs"]["incidents"]

    # Filters validate and apply.
    assert client.get(
        "/api/v1/autopilot/decisions", params={"rule": "nope"}
    ).status_code == 400
    assert client.get(
        "/api/v1/autopilot/decisions", params={"outcome": "nope"}
    ).status_code == 400
    assert client.get(
        "/api/v1/autopilot/decisions", params={"outcome": "suppressed"}
    ).json()["decisions"] == []

    # POST /tick runs one control pass (quiet: signal aged out of the
    # trend window, so no consult and no new record).
    clock.advance(120.0)
    r = client.post("/api/v1/autopilot/tick")
    assert r.status_code == 200
    assert r.json()["decisions"] == []
    assert r.json()["stats"]["ticks_total"] == 2

    # Mode flip is explicit and validated.
    assert client.post(
        "/api/v1/autopilot/mode", json={"dry_run": "yes"}
    ).status_code == 400
    r = client.post("/api/v1/autopilot/mode", json={"dry_run": True})
    assert r.json()["mode"] == "dry-run"
    assert ap.dry_run is True


# ---------------------------------------------------------------------------
# twin chaos A/B lane
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_autopilot_chaos_lane_gates():
    from tpu_engine.twin import autopilot_lane

    lane = autopilot_lane(seed=0)
    assert lane["ok"], lane["gates"]
    assert lane["steady_goodput_on"] >= lane["steady_goodput_off"]
    assert lane["dry_run"]["actuations_total"] == 0
