"""Serving fleet: KV-gated admission, preempt round trip, router, autoscaler.

Fast tier: replicas run stub engines (no JAX compute) through the real
:class:`~tpu_engine.scheduler.FleetScheduler` +
:class:`~tpu_engine.serving_fleet.ServingFleet` machinery; one test builds
a real tiny :class:`ContinuousBatcher` through the default engine factory.
"""

import threading
import time

import pytest

from tpu_engine.hbm_estimate import estimate_serving_hbm
from tpu_engine.scheduler import FleetScheduler, JobPriority, SubmissionState
from tpu_engine.serving_fleet import (
    AutoscalerConfig,
    FleetRouter,
    ReplicaAutoscaler,
    ServingFleet,
    ServingReplicaSpec,
)
from tpu_engine.sharding import Precision
from tpu_engine.supervisor import JobStatus
from tpu_engine.tpu_manager import TPUManager


def wait_until(pred, timeout=5.0, interval=0.01):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


class StubEngine:
    """ContinuousBatcher stand-in: instant decode, real surface."""

    def __init__(self, spec):
        self.slots = int(spec.max_slots)
        self._reqs = {}
        self._seq = 0
        self._lock = threading.Lock()

    def submit(self, prompt, max_new_tokens=64, temperature=0.0):
        with self._lock:
            self._seq += 1
            self._reqs[self._seq] = {"need": int(max_new_tokens), "tokens": []}
            return self._seq

    def step(self):
        out = 0
        with self._lock:
            for r in self._reqs.values():
                if len(r["tokens"]) < r["need"]:
                    r["tokens"].append(1)
                    out += 1
        return out

    def result(self, rid):
        with self._lock:
            r = self._reqs[rid]
            done = len(r["tokens"]) >= r["need"]
            return {
                "status": "done" if done else "running",
                "tokens": list(r["tokens"]),
            }

    def stats(self):
        with self._lock:
            active = sum(
                1 for r in self._reqs.values() if len(r["tokens"]) < r["need"]
            )
        return {
            "slots": self.slots, "active_slots": active, "prefilling": 0,
            "queued": 0, "tokens_per_sec_recent": 100.0,
        }


class StubWatcher:
    def __init__(self):
        self.fired = threading.Event()

    def simulate_interruption(self):
        self.fired.set()


class StubTrainJob:
    """Thread-backed TrainingJob stand-in (test_scheduler.py idiom)."""

    def __init__(self, sub):
        self.job_id = sub.job_id
        self.config = sub.config
        self.status = JobStatus.PENDING
        self.error = None
        self.current_step = 0
        self.watcher = StubWatcher()
        self._stop = threading.Event()
        self._done = threading.Event()
        self._final = JobStatus.COMPLETED
        self._thread = threading.Thread(target=self._run, daemon=True)

    @property
    def is_alive(self):
        return self._thread.is_alive()

    def start(self):
        self._thread.start()

    def join(self, timeout=None):
        self._thread.join(timeout)

    def describe(self):
        return {"job_id": self.job_id, "status": self.status.value}

    def finish(self, status=JobStatus.COMPLETED):
        self._final = status
        self._done.set()

    def _run(self):
        self.status = JobStatus.RUNNING
        while not self._done.is_set():
            if self._stop.is_set():
                self.status = JobStatus.STOPPED
                return
            if self.watcher.fired.is_set():
                self.status = JobStatus.PREEMPTED
                return
            self._done.wait(0.005)
        self.status = self._final


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


def small_spec(**kw):
    base = dict(model_name="gpt-tiny", max_slots=4, max_len=128)
    base.update(kw)
    return ServingReplicaSpec(**base)


def make_fleet(sched, spec=None, **kw):
    kw.setdefault("engine_factory", StubEngine)
    kw.setdefault(
        "autoscaler",
        ReplicaAutoscaler(AutoscalerConfig(min_replicas=1, max_replicas=4)),
    )
    return ServingFleet(sched, spec or small_spec(), **kw)


def mock_fleet_fn():
    return TPUManager().get_mock_fleet()


# ---------------------------------------------------------------------------
# estimate_serving_hbm: the KV-pool admission plane
# ---------------------------------------------------------------------------


def test_estimate_serving_kv_pool_plane():
    est = estimate_serving_hbm("gpt-tiny", max_slots=8, max_len=256)
    assert est is not None and est.gang_devices == 1
    # Serving has no training planes; the KV pool is first-class.
    assert est.grads_gib == 0 and est.opt_gib == 0 and est.activations_gib == 0
    assert est.kv_pool_gib > 0
    assert est.device_total_gib >= est.params_gib + est.kv_pool_gib
    # KV pool scales with the slot pool.
    est2 = estimate_serving_hbm("gpt-tiny", max_slots=16, max_len=256)
    assert est2.kv_pool_gib == pytest.approx(2 * est.kv_pool_gib, rel=1e-6)


def test_estimate_serving_int8_kv_halves_pool():
    bf16 = estimate_serving_hbm("gpt-125m", max_slots=8, max_len=1024)
    int8 = estimate_serving_hbm(
        "gpt-125m", max_slots=8, max_len=1024, kv_quant=True
    )
    # int8 codes + per-(lane, head) fp32 scales: just over half of bf16.
    assert int8.kv_pool_gib < 0.6 * bf16.kv_pool_gib
    assert int8.kv_pool_gib > 0.5 * bf16.kv_pool_gib
    assert "int8 codes" in " / ".join(int8.notes)


def test_estimate_serving_weight_quant_and_tp():
    bf16 = estimate_serving_hbm("gpt-125m", max_slots=4, max_len=512)
    int8 = estimate_serving_hbm(
        "gpt-125m", max_slots=4, max_len=512, weight_quant="int8"
    )
    assert int8.params_gib < 0.6 * bf16.params_gib
    tp2 = estimate_serving_hbm(
        "gpt-125m", max_slots=4, max_len=512, tensor_parallel=2
    )
    assert tp2.gang_devices == 2
    assert tp2.params_gib == pytest.approx(bf16.params_gib / 2, rel=1e-2)
    # gpt-125m has 12 KV heads: divisible by tp=2 → KV pool shards too.
    assert tp2.kv_pool_gib == pytest.approx(bf16.kv_pool_gib / 2, rel=1e-2)


def test_estimate_serving_unknown_model_is_none():
    assert estimate_serving_hbm("no-such-model", 4, 128) is None


def test_spec_estimate_matches_module_fn():
    spec = small_spec(kv_quant=True, compute_dtype=Precision.BF16)
    est = spec.estimate()
    direct = estimate_serving_hbm(
        "gpt-tiny", max_slots=4, max_len=128, kv_quant=True
    )
    assert est.device_total_gib == direct.device_total_gib


# ---------------------------------------------------------------------------
# Scheduler integration: shared queue, HBM ledger, preempt round trip
# ---------------------------------------------------------------------------


def test_serving_submission_shares_queue_and_ledger(sched_factory):
    s = sched_factory(max_concurrent_jobs=2, fleet_fn=mock_fleet_fn)
    fleet = make_fleet(s)
    fleet.start()
    assert wait_until(lambda: len(fleet.running_replicas()) == 1)
    (sub,) = fleet._replicas.values()
    # First-class submission: same state machine, workload tagged, and the
    # replica's KV pool holds a real per-device HBM reservation.
    assert sub.state == SubmissionState.RUNNING
    assert sub.describe()["workload"] == "serving"
    assert sub.estimate is not None and sub.estimate.kv_pool_gib > 0
    st = s.stats()
    assert st["running_serving"] == 1
    assert st["reserved_hbm_gib"] > 0
    fleet.stop()
    assert wait_until(lambda: sub.state == SubmissionState.CANCELLED)
    assert s.stats()["reserved_hbm_gib"] == 0.0


def test_kv_pool_rejects_oversubscribed_fleet(sched_factory):
    # 64 slots × 8192 lanes of bf16 KV on gpt-125m ≈ 18 GiB/device — more
    # than the mock fleet's 9.6 GiB free per chip. The shared HBM gate must
    # hold the replica in the queue, not admit-and-OOM.
    big = ServingReplicaSpec(model_name="gpt-125m", max_slots=64, max_len=8192)
    assert big.estimate().device_total_gib > 9.6
    s = sched_factory(max_concurrent_jobs=2, fleet_fn=mock_fleet_fn)
    fleet = make_fleet(s, spec=big)
    fleet.start()
    time.sleep(0.15)
    (sub,) = fleet._replicas.values()
    assert sub.state == SubmissionState.QUEUED
    assert "have that headroom" in sub.last_skip_reason
    assert s.stats()["reserved_hbm_gib"] == 0.0
    fleet.stop()


def test_critical_training_preempts_replica_round_trip(sched_factory):
    """Teardown → training admitted → replica re-admitted on drain."""
    from tests.test_scheduler import cfg as train_cfg

    s = sched_factory(max_concurrent_jobs=1, fleet_fn=mock_fleet_fn)
    fleet = make_fleet(s)
    fleet.start()
    assert wait_until(lambda: len(fleet.running_replicas()) == 1)
    (replica,) = fleet._replicas.values()

    # A CRITICAL training job arrives: the replica is preemptible without
    # a checkpoint (stateless above its snapshot) — checkpoint-free
    # teardown, training takes the slot.
    training = s.submit(train_cfg(), priority=JobPriority.CRITICAL)
    assert wait_until(lambda: training.state == SubmissionState.RUNNING)
    assert replica.state == SubmissionState.QUEUED  # requeued, not dead
    assert replica.preemptions == 1
    assert replica.job is None
    assert len(fleet.running_replicas()) == 0
    assert s.stats()["preemptions_total"] == 1

    # A request submitted while evicted holds fleet-side.
    rid = fleet.submit_request([1, 2, 3], max_new_tokens=4)
    assert fleet.result(rid)["status"] == "pending"

    # Training drains → the SAME submission re-admits a fresh engine and
    # the held request completes on it.
    s._stub_jobs[-1].finish()
    assert wait_until(lambda: training.state == SubmissionState.COMPLETED)
    assert wait_until(lambda: replica.state == SubmissionState.RUNNING)
    assert replica.attempts == 2
    assert wait_until(lambda: fleet.result(rid)["status"] == "done")
    fleet.stop()


def test_fleet_scale_to_submits_and_cancels(sched_factory):
    s = sched_factory(max_concurrent_jobs=4, fleet_fn=mock_fleet_fn)
    fleet = make_fleet(s)
    fleet.scale_to(3)
    assert wait_until(lambda: len(fleet.running_replicas()) == 3)
    assert s.stats()["running_serving"] == 3
    fleet.scale_to(1)
    assert wait_until(lambda: len(fleet.running_replicas()) == 1)
    assert wait_until(lambda: s.stats()["running_serving"] == 1)
    fleet.stop()


def test_fleet_routes_requests_across_replicas(sched_factory):
    s = sched_factory(max_concurrent_jobs=4, fleet_fn=mock_fleet_fn)
    fleet = make_fleet(s)
    fleet.scale_to(2)
    assert wait_until(lambda: len(fleet.running_replicas()) == 2)
    rids = [
        fleet.submit_request([i, i + 1], max_new_tokens=3) for i in range(6)
    ]
    assert all(
        wait_until(lambda r=r: fleet.result(r)["status"] == "done")
        for r in rids
    )
    st = fleet.status()
    assert st["completed_total"] == 6
    assert st["tokens_total"] == 18
    assert st["p99_latency_ms"] is not None
    fleet.stop()


# ---------------------------------------------------------------------------
# FleetRouter
# ---------------------------------------------------------------------------


def _stats(tps, free, slots=8):
    return {"tokens_per_sec": tps, "free_slots": free, "slots": slots}


def test_router_weights_follow_throughput():
    r = FleetRouter(affinity_tokens=0)
    r.update({"fast": _stats(90.0, 8), "slow": _stats(10.0, 8)})
    picks = [r.route() for _ in range(100)]
    # Smooth WRR: traffic split tracks the ~9:1 throughput ratio.
    assert picks.count("fast") > 75
    assert picks.count("slow") >= 5  # degraded still serves, gated not binary


def test_router_starves_full_replica():
    r = FleetRouter(affinity_tokens=0)
    r.update({"full": _stats(90.0, 0), "free": _stats(30.0, 8)})
    picks = [r.route() for _ in range(20)]
    # free-slot fraction ≈ 0 crushes the busy replica's weight.
    assert picks.count("free") >= 18


def test_router_prefix_affinity_sticks_and_survives_teardown():
    r = FleetRouter(affinity_tokens=4)
    r.update({"a": _stats(50.0, 8), "b": _stats(50.0, 8)})
    prompt = [7, 7, 7, 7, 99]
    first = r.route(prompt)
    # Same prefix keeps landing on the same replica while it has slots.
    for i in range(5):
        assert r.route([7, 7, 7, 7, 100 + i]) == first
    assert r.affinity_hits == 5
    # The sticky replica disappears (preempted): affinity is dropped and
    # the prefix re-pins to a live replica instead of routing into a void.
    other = "b" if first == "a" else "a"
    r.update({other: _stats(50.0, 8)})
    assert r.route([7, 7, 7, 7, 200]) == other


def test_router_busy_fallthrough_keeps_live_pin():
    """A momentarily-full pinned replica must not lose its pin: the
    fall-through dispatch goes elsewhere, but the NEXT route with a free
    slot returns to the replica that still holds the prefix KV."""
    r = FleetRouter(affinity_tokens=4)
    r.update({"a": _stats(50.0, 8), "b": _stats(50.0, 8)})
    prompt = [3, 3, 3, 3, 1]
    pinned = r.route(prompt)
    other = "b" if pinned == "a" else "a"
    # Alternate: pinned replica full (fall-through) / free again. Before
    # the fix each fall-through re-pinned to the OTHER replica, so the
    # prefix ping-ponged and never re-used its cache.
    for i in range(6):
        r.update({pinned: _stats(50.0, 0), other: _stats(50.0, 8)})
        assert r.route([3, 3, 3, 3, 10 + i]) == other
        r.update({pinned: _stats(50.0, 8), other: _stats(50.0, 8)})
        assert r.route([3, 3, 3, 3, 20 + i]) == pinned
    # The pin is only released when its target actually dies.
    r.update({other: _stats(50.0, 8)})
    assert r.route([3, 3, 3, 3, 99]) == other


def test_router_affinity_hits_pay_wrr_share():
    """Affinity picks run the same smooth-WRR ledger as fair rotation:
    under an interleaved affinity/cold stream on equal-weight replicas,
    long-run total traffic still splits by weight (the old hit path
    skipped the ledger, skewing totals ~75/25)."""
    r = FleetRouter(affinity_tokens=4)
    r.update({"a": _stats(50.0, 8), "b": _stats(50.0, 8)})
    hot = [5, 5, 5, 5, 0]
    pinned = r.route(hot)
    counts = {"a": 1 if pinned == "a" else 0, "b": 1 if pinned == "b" else 0}
    for i in range(200):
        r.update({"a": _stats(50.0, 8), "b": _stats(50.0, 8)})
        counts[r.route([5, 5, 5, 5, i])] += 1   # affinity hit -> pinned
        counts[r.route([i, 1000 + i])] += 1     # cold -> WRR
    total = sum(counts.values())
    assert counts[pinned] == 201  # every hot prompt stuck to its pin
    # Equal weights -> both replicas within 45-55% of total traffic.
    for rid in ("a", "b"):
        assert 0.45 <= counts[rid] / total <= 0.55, counts


# ---------------------------------------------------------------------------
# ReplicaAutoscaler
# ---------------------------------------------------------------------------


def _scaler(**kw):
    base = dict(
        min_replicas=1, max_replicas=4, target_queue_per_replica=4.0,
        low_water_queue_per_replica=0.5, p99_slo_ms=1000.0, window_s=10.0,
        scale_up_cooldown_s=2.0, scale_down_cooldown_s=30.0,
    )
    base.update(kw)
    return ReplicaAutoscaler(AutoscalerConfig(**base))


def test_autoscaler_scales_up_on_queue_and_respects_max():
    a = _scaler()
    n = 1
    for t in range(0, 40):
        n = a.observe(float(t), queue_depth=40.0, p99_ms=None, n_replicas=n)
    assert n == 4  # max, not beyond
    assert a.scale_ups >= 3


def test_autoscaler_scales_up_on_p99_breach():
    a = _scaler()
    assert a.observe(0.0, queue_depth=0.0, p99_ms=5000.0, n_replicas=2) == 3
    assert "SLO" in a.last_reason


def test_autoscaler_scale_down_needs_calm_window_and_cooldown():
    a = _scaler()
    # A p99 breach at t=0 scales up (queue stays 0 so the sliding window
    # holds nothing that could re-trigger an up during the calm phase).
    assert a.observe(0.0, 0.0, 5000.0, 2) == 3
    n = 3
    for t in range(1, 30):
        n = a.observe(float(t), queue_depth=0.0, p99_ms=100.0, n_replicas=n)
        # Calm + full window, but inside the 30 s cooldown: hysteresis
        # holds the replica a traffic dip would otherwise shed.
        assert n == 3
    # Past the cooldown (last event t=0 + 30 s) the scale-down proceeds.
    assert a.observe(31.0, 0.0, 100.0, 3) == 2
    assert a.scale_downs == 1


def test_autoscaler_never_drops_below_min():
    a = _scaler(min_replicas=2, max_replicas=4)
    n = 2
    for t in range(0, 100):
        n = a.observe(float(t), queue_depth=0.0, p99_ms=50.0, n_replicas=n)
    assert n == 2
    assert a.observe(101.0, 0.0, None, 1) == 2  # below min → raise


# ---------------------------------------------------------------------------
# Default engine factory (real ContinuousBatcher) + bench smoke
# ---------------------------------------------------------------------------


def test_default_engine_factory_builds_real_batcher(sched_factory):
    import jax.numpy as jnp

    from tpu_engine.serving_fleet import build_replica_engine

    spec = small_spec(max_slots=2, max_len=64, prefill_chunk=16)
    engine = build_replica_engine(spec)
    rid = engine.submit([1, 2, 3], max_new_tokens=4)
    for _ in range(200):
        if engine.result(rid)["status"] == "done":
            break
        engine.step()
    out = engine.result(rid)
    assert out["status"] == "done" and len(out["tokens"]) >= 1
    assert jnp.asarray(out["tokens"]).dtype.kind == "i"


def test_autoscaled_lane_beats_static_replica():
    """The autoscaled-fleet lane on the seeded bursty trace (real router +
    autoscaler over the twin's capacity model at its assumed rates)."""
    from benchmarks.serving_fleet_sim import run_trace

    trace = run_trace(seed=0)
    auto = trace["autoscaled"]
    # The acceptance bar: ≥2x the model's aggregate tokens/sec over the
    # static single replica, with steady-state p99 inside the SLO.
    assert trace["throughput_improvement"] >= 2.0
    assert auto["p99_within_slo"]
    assert auto["p99_ms"] <= trace["p99_slo_ms"]
    assert auto["replica_trace"][0][1] == 1
    assert auto["max_replicas_used"] > 1
    # Weights are the END-of-trace routing plane; scale-downs may have
    # shed replicas since the peak.
    assert 1 <= len(auto["router"]["weights"]) <= auto["max_replicas_used"]
    assert auto["prefix_hit_rate"] > 0.5
