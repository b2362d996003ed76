"""Fleet speculative decoding pools: spill rule, paired fleet, twin lane.

Four tiers in one file:

- **Spill controller units** — sustained-α spill/restore over a real
  :class:`MetricHistorian` with explicit timestamps: streak hysteresis,
  the recover-margin band, per-tenant cooldown, no-data freeze, and the
  PR-15 audit contract (every consult that could fire leaves a
  byte-stable :class:`DecisionRecord`).
- **Paired fleet on stubs** — :class:`SpecServingFleet` through the real
  :class:`FleetScheduler`: draft-propose + target-verify legs, the
  authoritative-target correctness contract, acceptance EMAs feeding the
  historian, spill → plain chunked decode with canary probes, and
  draft-replica prefix-cache invalidation.
- **Admission/placement** — ``estimate_serving_hbm(draft_model_name=...)``
  draft terms + structured :class:`SpecHBMOversubscribed`, and
  ``plan_serving_pool(role="draft")`` propose-latency ranking.
- **Distill smoke** — the only end-to-end draft-production recipe
  (``benchmarks/spec_decode_distill.py``) at tiny dims on CPU, so the
  path that makes real drafts cannot silently rot.
"""

import time

import pytest

from tests.test_serving_fleet import (
    StubEngine,
    StubTrainJob,
    mock_fleet_fn,
    wait_until,
)
from tpu_engine.hbm_estimate import (
    SpecHBMOversubscribed,
    estimate_serving_hbm,
)
from tpu_engine.historian import MetricHistorian
from tpu_engine.placement import plan_serving_pool
from tpu_engine.scheduler import FleetScheduler
from tpu_engine.serving_fleet import (
    AutoscalerConfig,
    ReplicaAutoscaler,
    ServingReplicaSpec,
)
from tpu_engine.spec_pool import (
    SpecServingFleet,
    SpecSpillConfig,
    SpecSpillController,
    _reset_stats_for_tests,
    spec_pool_stats,
)

SERIES = "serving.spec.accept_rate"


@pytest.fixture
def sched_factory():
    created = []

    def make(**kw):
        jobs = []

        def factory(sub):
            job = StubTrainJob(sub)
            jobs.append(job)
            return job

        kw.setdefault("job_factory", factory)
        kw.setdefault("poll_interval_s", 0.01)
        kw.setdefault("grow_back_cooldown_s", 0.0)
        s = FleetScheduler(**kw)
        s._stub_jobs = jobs
        created.append(s)
        return s

    yield make
    for s in created:
        for j in getattr(s, "_stub_jobs", []):
            j.finish()
        s.shutdown()


def _one():
    return ReplicaAutoscaler(
        AutoscalerConfig(min_replicas=1, max_replicas=1))


# ---------------------------------------------------------------------------
# SpecSpillController: the audited sustained-α rule
# ---------------------------------------------------------------------------


def _feed(hist, tenant, alpha, t0, n=5, dt=1.0):
    for i in range(n):
        hist.record(SERIES, alpha, ts=t0 + i * dt,
                    labels={"tenant": tenant})


def _ctl(hist, **kw):
    base = dict(accept_floor=0.35, recover_margin=0.15, window_s=60.0,
                sustain_consults=3, cooldown_s=0.0, canary_every=8)
    base.update(kw)
    return SpecSpillController(hist, SpecSpillConfig(**base))


def test_spill_fires_only_when_sustained():
    hist = MetricHistorian()
    ctl = _ctl(hist)
    _feed(hist, "junk", 0.05, t0=100.0)
    # Two consults build the streak (each audited as suppressed); the
    # third fires.
    assert ctl.consult(["junk"], now=110.0) == []
    assert ctl.consult(["junk"], now=111.0) == []
    assert ctl.consult(["junk"], now=112.0) == ["junk"]
    assert ctl.is_spilled("junk")
    outs = [d.outcome for d in ctl.decisions]
    assert outs == ["suppressed", "suppressed", "fired"]
    assert all(d.rule == "spill_low_acceptance" for d in ctl.decisions)
    assert ctl.decisions[0].suppressed_reason == "trend-not-sustained"
    fired = ctl.decisions[-1]
    assert fired.action == {"verb": "spill", "tenant": "junk",
                            "alpha": 0.05}
    assert fired.inputs["queries"][0]["series"] == SERIES
    assert fired.hysteresis["required"] == 3
    # Audit records are byte-stable dicts.
    assert fired.to_dict()["decision_id"].startswith("spd-")


def test_spill_streak_resets_on_healthy_alpha():
    hist = MetricHistorian()
    ctl = _ctl(hist)
    _feed(hist, "t", 0.1, t0=100.0)
    ctl.consult(["t"], now=110.0)
    ctl.consult(["t"], now=111.0)
    # A healthy window wipes the streak — two breaches then recovery is
    # not "sustained".
    _feed(hist, "t", 0.9, t0=112.0)
    assert ctl.consult(["t"], now=115.0) == []
    assert ctl.status()["streaks"]["t"] == 0
    assert not ctl.is_spilled("t")


def test_restore_needs_margin_and_cooldown():
    hist = MetricHistorian()
    ctl = _ctl(hist, sustain_consults=2, cooldown_s=50.0, window_s=10.0)
    _feed(hist, "t", 0.05, t0=100.0)
    ctl.consult(["t"], now=110.0)
    assert ctl.consult(["t"], now=111.0) == ["t"]  # spilled at t=111
    # α inside the hysteresis band (floor < α < floor+margin) must NOT
    # restore — the band is what stops flapping.
    _feed(hist, "t", 0.45, t0=115.0)
    ctl.consult(["t"], now=122.0)
    ctl.consult(["t"], now=123.0)
    assert ctl.is_spilled("t")
    # Recovered α above the band: sustained, but inside cooldown →
    # suppressed with the audited reason; after cooldown it fires.
    _feed(hist, "t", 0.9, t0=130.0)
    ctl.consult(["t"], now=136.0)
    ctl.consult(["t"], now=137.0)
    assert ctl.is_spilled("t")
    assert ctl.decisions[-1].suppressed_reason == "cooldown-active"
    assert ctl.decisions[-1].rule == "restore_speculation"
    _feed(hist, "t", 0.9, t0=155.0)
    assert ctl.consult(["t"], now=162.0) == []
    assert not ctl.is_spilled("t")
    assert ctl.decisions[-1].action["verb"] == "restore"


def test_no_data_freezes_the_streak():
    hist = MetricHistorian()
    ctl = _ctl(hist)
    _feed(hist, "t", 0.1, t0=100.0, n=2)
    ctl.consult(["t"], now=103.0)
    assert ctl.status()["streaks"]["t"] == 1
    # Window slides past every sample: no evidence either way — the
    # streak must neither advance nor reset, and the consult is audited.
    ctl.consult(["t"], now=500.0)
    assert ctl.status()["streaks"]["t"] == 1
    assert ctl.decisions[-1].suppressed_reason == "no-data"
    assert not ctl.is_spilled("t")


# ---------------------------------------------------------------------------
# SpecServingFleet on stubs through the real scheduler
# ---------------------------------------------------------------------------


class MisdraftEngine(StubEngine):
    """Draft stand-in whose proposals never match the target stream
    (StubEngine emits 1s; this emits 2s) → measured α = 0."""

    def step(self):
        out = 0
        with self._lock:
            for r in self._reqs.values():
                if len(r["tokens"]) < r["need"]:
                    r["tokens"].append(2)
                    out += 1
        return out


def _spec(**kw):
    base = dict(model_name="gpt-tiny", max_slots=4, max_len=128)
    base.update(kw)
    return ServingReplicaSpec(**base)


def make_spec_fleet(sched, engine_factory=StubEngine, **kw):
    kw.setdefault("verify_autoscaler", _one())
    kw.setdefault("draft_autoscaler", _one())
    return SpecServingFleet(
        sched, _spec(), _spec(max_slots=2), engine_factory=engine_factory,
        **kw)


def _pools_up(fleet):
    return (len(fleet.draft.running_replicas()) == 1
            and len(fleet.verify.running_replicas()) == 1)


def test_spec_fleet_pairs_draft_and_verify_pools(sched_factory):
    _reset_stats_for_tests()
    s = sched_factory(max_concurrent_jobs=4, fleet_fn=mock_fleet_fn)
    hist = MetricHistorian()
    fleet = make_spec_fleet(s, historian=hist)
    # The pairing forces the roles: drafts are first-class draft-pool
    # tenants, verify is an ordinary decode pool.
    assert fleet.draft.spec.pool_role == "draft"
    assert fleet.verify.spec.pool_role == "decode"
    fleet.start()
    assert wait_until(lambda: _pools_up(fleet))
    fid = fleet.submit_request([3, 1, 4], max_new_tokens=5, tenant="good")
    out = fleet.wait(fid, timeout=10.0)
    # The emitted stream is the TARGET's own tokens (StubEngine 1s), and
    # both legs ran on distinct pools.
    assert out["status"] == "done" and out["tokens"] == [1] * 5
    assert out["speculated"] and not out["canary"]
    assert out["draft_replica"] is not None
    assert out["verify_replica"] is not None
    st = fleet.status()
    assert st["draft_legs_total"] == 1 and st["plain_legs_total"] == 0
    # Stub draft emits the same 1s → perfect acceptance, recorded to the
    # historian under the tenant label.
    assert fleet.tenant_accept_rates()["good"] == 1.0
    q = hist.query(SERIES, 0.0, time.time() + 1.0, agg="last",
                   labels={"tenant": "good"})
    assert q["value"] == 1.0 and q["count"] >= 1
    mod = spec_pool_stats()
    assert mod["requests_total"] == 1 and mod["draft_legs_total"] == 1
    assert mod["accepted_tokens_total"] == mod["proposed_tokens_total"] > 0
    fleet.stop()


def test_spec_fleet_spills_low_alpha_tenant_with_canary(sched_factory):
    _reset_stats_for_tests()
    s = sched_factory(max_concurrent_jobs=4, fleet_fn=mock_fleet_fn)
    hist = MetricHistorian()

    def mixed(spec):
        # Factory sees the spec it builds for: junk proposals on the
        # draft pool only.
        return (MisdraftEngine(spec) if spec.pool_role == "draft"
                else StubEngine(spec))

    fleet = make_spec_fleet(
        s, engine_factory=mixed, historian=hist,
        spill_config=SpecSpillConfig(
            accept_floor=0.35, recover_margin=0.15, window_s=60.0,
            sustain_consults=2, cooldown_s=0.0, canary_every=2),
    )
    fleet.start()
    assert wait_until(lambda: _pools_up(fleet))
    out = fleet.wait(
        fleet.submit_request([7, 7], max_new_tokens=4, tenant="junk"),
        timeout=10.0)
    # Mismatched proposal can never corrupt output — the verify stream
    # is authoritative.
    assert out["tokens"] == [1] * 4
    assert fleet.tenant_accept_rates()["junk"] == 0.0
    fleet.tick()
    fleet.tick()
    assert fleet.spill.is_spilled("junk")
    fired = [d for d in fleet.spill.decisions if d.outcome == "fired"]
    assert fired and fired[-1].rule == "spill_low_acceptance"
    # Spilled tenant: next request rides plain chunked decode, the one
    # after is the canary probe back down the draft leg.
    plain = fleet.wait(
        fleet.submit_request([7, 8], max_new_tokens=4, tenant="junk"),
        timeout=10.0)
    assert not plain["speculated"] and not plain["canary"]
    assert plain["draft_replica"] is None and plain["tokens"] == [1] * 4
    canary = fleet.wait(
        fleet.submit_request([7, 9], max_new_tokens=4, tenant="junk"),
        timeout=10.0)
    assert canary["speculated"] and canary["canary"]
    assert canary["draft_replica"] is not None
    st = fleet.status()
    assert st["plain_legs_total"] == 1
    assert st["tenants"]["junk"]["spilled"]
    mod = spec_pool_stats()
    assert mod["spills_total"] == 1 and mod["canary_probes_total"] == 1
    assert mod["plain_legs_total"] == 1 and mod["tenants_spilled"] == 1
    fleet.stop()


class FakePrefixPlane:
    def __init__(self):
        self.dropped = []

    def drop_replica(self, sid):
        self.dropped.append(sid)


def test_draft_replica_loss_drops_prefix_cache(sched_factory):
    _reset_stats_for_tests()
    s = sched_factory(max_concurrent_jobs=4, fleet_fn=mock_fleet_fn)
    fleet = make_spec_fleet(s)
    plane = FakePrefixPlane()
    fleet.prefix_plane = plane
    fleet.start()
    assert wait_until(lambda: _pools_up(fleet))
    fleet.tick()  # seeds the seen-set with the live draft replica
    # A draft replica that vanished since the last pump (preempt /
    # migrate / scale-down) must have its cache entries dropped.
    with fleet._lock:
        fleet._draft_sids_seen = set(fleet._draft_sids_seen) | {"ghost"}
    fleet.tick()
    assert plane.dropped == ["ghost"]
    assert spec_pool_stats()["draft_cache_invalidations_total"] == 1
    fleet.stop()


# ---------------------------------------------------------------------------
# Admission + placement: draft HBM terms and draft-pool plans
# ---------------------------------------------------------------------------


def test_estimate_serving_hbm_draft_terms():
    plain = estimate_serving_hbm("llama-1b", max_slots=8, max_len=2048)
    spec = estimate_serving_hbm("llama-1b", max_slots=8, max_len=2048,
                                draft_model_name="gpt-tiny")
    assert plain is not None and spec is not None
    # Colocated draft = weights + a second KV pool: strictly more HBM.
    assert spec.device_total_gib > plain.device_total_gib
    assert any("draft" in n for n in spec.notes)
    # Unknown draft model → no estimate, same contract as the target.
    assert estimate_serving_hbm("llama-1b", max_slots=8, max_len=2048,
                                draft_model_name="nope") is None


def test_estimate_serving_hbm_rejects_oversubscribed_draft():
    with pytest.raises(SpecHBMOversubscribed) as ei:
        estimate_serving_hbm("llama-1b", max_slots=8, max_len=2048,
                             draft_model_name="gpt-tiny",
                             device_budget_gib=0.5)
    err = ei.value
    assert isinstance(err, ValueError)
    assert err.reason["kind"] == "spec_hbm_oversubscribed"
    assert err.draft_model_name == "gpt-tiny"
    assert err.required_gib > err.budget_gib == 0.5
    assert err.draft_gib > 0
    # A sane budget admits the same geometry.
    est = estimate_serving_hbm("llama-1b", max_slots=8, max_len=2048,
                               draft_model_name="gpt-tiny",
                               device_budget_gib=64.0)
    assert est is not None


def test_plan_serving_pool_draft_role():
    plans = plan_serving_pool("gpt-tiny", "draft", 4,
                              hbm_free_gib=2.0, max_len=2048)
    feasible = [p for p in plans if p.feasible]
    assert feasible
    assert all(p.role == "draft" for p in plans)
    assert all(p.predicted_propose_s > 0 for p in feasible)
    # Ranked by draft-propose latency (γ sequential memory-bound steps),
    # ties toward fewer chips — drafts backfill fragmented headroom.
    keys = [(p.predicted_propose_s, p.tensor_parallel, -p.max_slots)
            for p in feasible]
    assert keys == sorted(keys)
    assert "draft" in feasible[0].label
    with pytest.raises(ValueError, match="role"):
        plan_serving_pool("gpt-tiny", "oracle", 4)


# ---------------------------------------------------------------------------
# Twin lane: deterministic A/B machinery (full gates ride the slow tier)
# ---------------------------------------------------------------------------

_FAST_LANE = dict(duration_s=90.0, warmup_s=30.0, spill_window_s=10.0,
                  cooldown_s=20.0)


def test_spec_pool_lane_deterministic_and_spills():
    from tpu_engine.twin import SpecPoolLaneParams, spec_pool_lane

    p = SpecPoolLaneParams(**_FAST_LANE)
    a = spec_pool_lane(0, spec=True, params=p)
    b = spec_pool_lane(0, spec=True, params=p)
    assert a == b  # byte-identical repeat, same seed
    # The junk-draft tenant (α ≈ 0.06) is spilled by the real controller
    # consulting the real historian even on the short trace.
    assert a["spill"]["spilled"] == ["t3"]
    assert len(a["spill_decisions_fired"]) >= 1
    assert a["metrics"]["completed"] > 0
    plain = spec_pool_lane(0, spec=False, params=p)
    assert plain["mode"] == "plain" and "spill" not in plain
    assert plain["total_chips"] == a["total_chips"]


@pytest.mark.slow
def test_spec_pool_ab_gates():
    from tpu_engine.twin import spec_pool_ab

    res = spec_pool_ab(seed=0)
    assert res["ok"], res["gates"]
    assert res["tokens_per_sec_per_chip_ratio"] >= 1.2


# ---------------------------------------------------------------------------
# Distill smoke: the draft-production recipe at tiny scale on CPU
# ---------------------------------------------------------------------------


def test_spec_decode_distill_smoke():
    from benchmarks.spec_decode_distill import run

    rep = run(
        vocab=64, seq=64, gamma=2, train_steps=6, distill_steps=6,
        target_kw=dict(name="smoke-target", vocab_size=64, d_model=32,
                       n_layers=2, n_heads=2, n_kv_heads=2, d_ff=64,
                       max_seq_len=64),
        draft_kw=dict(name="smoke-draft", vocab_size=64, d_model=16,
                      n_layers=1, n_heads=2, n_kv_heads=2, d_ff=32,
                      max_seq_len=64),
        micro_batch=8, prompt_len=8, n_kd_prompts=4, n_eval_prompts=2,
        max_new=8,
    )
    assert rep["metric"] == "spec_decode_distilled_draft"
    assert rep["spec_rounds"] > 0
    assert rep["spec_tokens_proposed"] >= rep["spec_tokens_accepted"] >= 0
    assert 0.0 <= rep["alpha_accept_rate"] <= 1.0
    # Speculation must not change the stream: greedy target output is
    # authoritative in both modes.
    assert rep["stream_agreement"] >= 0.99
    assert rep["gamma"] == 2 and rep["draft"]["layers"] == 1
