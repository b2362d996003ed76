"""Fleet scheduler: queue order, HBM-aware admission, preempt-requeue.

Fast tier: jobs are thread-backed stubs (no JAX compute) driven through the
real :class:`~tpu_engine.scheduler.FleetScheduler` state machine; the real
end-to-end checkpoint round trip lives in ``test_checkpoint_supervisor.py``
(slow tier) and ``benchmarks/scheduler_sim.py``.
"""

import threading
import time

import pytest

from tpu_engine.hbm_estimate import (
    HBMEstimate,
    estimate_job_hbm,
    gang_size,
)
from tpu_engine.mesh_runtime import MeshConfig
from tpu_engine.scheduler import (
    FleetScheduler,
    JobPriority,
    QuotaExceeded,
    SubmissionState,
)
from tpu_engine.sharding import OffloadDevice, ShardingStage, TPUTrainConfig
from tpu_engine.supervisor import JobStatus
from tpu_engine.tpu_manager import TPUManager


def cfg(**kw):
    base = dict(
        model_name="gpt-tiny",
        mesh=MeshConfig(data=1, fsdp=2),
        micro_batch_size=1,
        seq_len=32,
        precision="fp32",
        total_steps=5,
        activation_checkpointing=False,
        checkpoint_dir="/tmp/sched_test",  # preemptibility flag only
    )
    base.update(kw)
    return TPUTrainConfig(**base)


def wait_until(pred, timeout=5.0, interval=0.01):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


class StubWatcher:
    def __init__(self):
        self.fired = threading.Event()

    def simulate_interruption(self):
        self.fired.set()


class StubJob:
    """Thread-backed TrainingJob stand-in: runs until the test calls
    ``finish()`` (or the scheduler stops/preempts it)."""

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
                self.status = JobStatus.PREEMPTED  # the "emergency save"
                return
            self._done.wait(0.005)
        self.status = self._final


@pytest.fixture
def sched_factory():
    created = []

    def make(**kw):
        jobs = []

        def factory(sub):
            job = StubJob(sub)
            jobs.append(job)
            return job

        kw.setdefault("job_factory", factory)
        kw.setdefault("poll_interval_s", 0.01)
        # Hysteresis off by default so resize tests run at test speed; the
        # flap-plan regression test opts in with a real cooldown.
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


# ---------------------------------------------------------------------------
# hbm_estimate
# ---------------------------------------------------------------------------


def test_gang_size_explicit_and_elastic():
    assert gang_size(cfg(mesh=MeshConfig(data=2, fsdp=4))) == 8
    elastic = cfg(mesh=MeshConfig(data=-1, fsdp=2))
    assert gang_size(elastic) == 2  # no hint → smallest legal gang
    assert gang_size(elastic, available=7) == 6  # largest multiple of fsdp
    assert gang_size(elastic, available=1) == 2  # below one block → one block


def test_estimate_known_model_breakdown():
    est = estimate_job_hbm(cfg(mesh=MeshConfig(data=2, fsdp=4)))
    assert est is not None and est.gang_devices == 8
    parts = (
        est.params_gib + est.grads_gib + est.opt_gib + est.working_gib
        + est.activations_gib + est.logits_gib
    )
    assert est.device_total_gib == pytest.approx(parts, abs=1e-3)
    assert est.device_total_gib > 0 and est.host_gib == 0


def test_estimate_unknown_model_is_none():
    assert estimate_job_hbm(cfg(model_name="nope-9b")) is None


def test_estimate_sharding_shrinks_params():
    full = estimate_job_hbm(
        cfg(mesh=MeshConfig(data=1, fsdp=4),
            sharding_stage=ShardingStage.FULL_PARTITIONING)
    )
    rep = estimate_job_hbm(
        cfg(mesh=MeshConfig(data=4, fsdp=1),
            sharding_stage=ShardingStage.DISABLED)
    )
    assert full.params_gib < rep.params_gib
    assert full.grads_gib < rep.grads_gib


def test_estimate_offload_moves_state_to_host():
    on_dev = estimate_job_hbm(cfg())
    off = estimate_job_hbm(cfg(optimizer_offload=OffloadDevice.HOST))
    assert off.opt_gib == 0 and off.host_gib > 0
    assert off.device_total_gib < on_dev.device_total_gib
    assert any("offloaded" in n for n in off.notes)


def test_estimate_is_pipeline_schedule_aware():
    """Regression: activation residency must follow the SCHEDULE — O(M+P)
    stage boundary buffers for gpipe vs O(P) for 1f1b/zb — or the
    admission gate over-rejects 1F1B/ZB gangs that actually fit (and
    under-charges GPipe at large M)."""

    def est(sched, accum):
        return estimate_job_hbm(cfg(
            mesh=MeshConfig(data=1, fsdp=2, pipe=2),
            gradient_accumulation_steps=accum,
            pipeline_schedule=sched,
        ))

    # GPipe's boundary-buffer term grows with the microbatch count; the
    # manual-vjp schedules' does not (O(P) ring, M-independent).
    assert est("gpipe", 32).activations_gib > est("gpipe", 4).activations_gib
    assert est("1f1b", 32).activations_gib == est("1f1b", 4).activations_gib
    assert est("zb", 32).activations_gib == est("zb", 4).activations_gib
    # At large M the O(P) schedules project strictly below GPipe; zb pays
    # only its bounded deferred-W stash on top of the 1f1b ring.
    assert est("zb", 32).activations_gib < est("gpipe", 32).activations_gib
    assert est("1f1b", 32).activations_gib <= est("zb", 32).activations_gib
    # "auto" resolves (M > P → zb) before projecting, same answer.
    assert est("auto", 32).activations_gib == est("zb", 32).activations_gib
    assert any("pipeline schedule" in n for n in est("auto", 32).notes)
    # Non-pipelined configs carry no schedule term or note.
    flat = estimate_job_hbm(cfg(mesh=MeshConfig(data=1, fsdp=2)))
    assert not any("pipeline schedule" in n for n in flat.notes)


# ---------------------------------------------------------------------------
# queue order / capacity
# ---------------------------------------------------------------------------


def test_priority_then_fifo_order(sched_factory):
    s = sched_factory(max_concurrent_jobs=0)  # nothing admits: pure queue
    low = s.submit(cfg(), priority=JobPriority.LOW)
    norm1 = s.submit(cfg(), priority=JobPriority.NORMAL)
    high = s.submit(cfg(), priority=JobPriority.HIGH)
    norm2 = s.submit(cfg(), priority=JobPriority.NORMAL)
    crit = s.submit(cfg(), priority=JobPriority.CRITICAL)
    order = [q["submission_id"] for q in s.queue_state()["queued"]]
    assert order == [
        crit.submission_id, high.submission_id,
        norm1.submission_id, norm2.submission_id, low.submission_id,
    ]
    assert s.queue_position(crit.submission_id) == 1
    assert s.queue_position(low.submission_id) == 5


def test_capacity_admission_and_stats(sched_factory):
    s = sched_factory(max_concurrent_jobs=2)
    subs = [s.submit(cfg()) for _ in range(3)]
    assert wait_until(lambda: len(s._stub_jobs) == 2)
    s.poll()
    assert subs[2].state == SubmissionState.QUEUED
    assert s.queue_position(subs[2].submission_id) == 1
    assert subs[2].last_skip_reason == "at max_concurrent_jobs capacity"

    s._stub_jobs[0].finish()
    assert wait_until(lambda: subs[2].state == SubmissionState.RUNNING)
    for j in s._stub_jobs:
        j.finish()
    assert wait_until(
        lambda: all(sub.state == SubmissionState.COMPLETED for sub in subs)
    )
    st = s.stats()
    assert st["submitted_total"] == 3 and st["admitted_total"] == 3
    assert st["completed_total"] == 3 and st["queue_depth"] == 0
    assert all(sub.wait_s is not None for sub in subs)


# ---------------------------------------------------------------------------
# HBM-aware gang admission against the (mock) fleet
# ---------------------------------------------------------------------------


def test_gang_larger_than_healthy_fleet_never_admits(sched_factory):
    # Mock fleet: 8 chips, chip 5 hot (88% HBM, 97% duty) → 7 healthy.
    s = sched_factory(max_concurrent_jobs=4, fleet_fn=TPUManager.get_mock_fleet)
    big = s.submit(cfg(mesh=MeshConfig(data=2, fsdp=4)), priority=JobPriority.HIGH)
    small = s.submit(cfg(mesh=MeshConfig(data=1, fsdp=2)))
    assert wait_until(lambda: small.state == SubmissionState.RUNNING)
    # Backfill admitted the small job past the unplaceable head...
    assert big.state == SubmissionState.QUEUED
    assert "gang of 8 device(s) > 7 healthy chip(s)" in big.last_skip_reason
    # ...and an unplaceable head never evicts anyone.
    assert s.preemptions_total == 0


def test_hbm_reservation_serialises_big_jobs(sched_factory):
    # Healthy mock chips have 9.6 GiB free; two 6 GiB/device gangs of 4
    # cannot coexist (7 chips, each fits ONE such job's reservation).
    def est(config, n_avail):
        return HBMEstimate(
            model_name=config.model_name,
            gang_devices=gang_size(config, n_avail),
            params_gib=6.0, grads_gib=0, opt_gib=0, working_gib=0,
            activations_gib=0, logits_gib=0, device_total_gib=6.0, host_gib=0,
        )

    s = sched_factory(
        max_concurrent_jobs=4, fleet_fn=TPUManager.get_mock_fleet,
        estimate_fn=est,
    )
    first = s.submit(cfg(mesh=MeshConfig(data=1, fsdp=4)))
    assert wait_until(lambda: first.state == SubmissionState.RUNNING)
    assert len(first.placement) == 4
    second = s.submit(cfg(mesh=MeshConfig(data=1, fsdp=4)))
    s.poll()
    assert second.state == SubmissionState.QUEUED
    assert "only 3 have that headroom" in second.last_skip_reason
    assert s.stats()["reserved_hbm_gib"] == pytest.approx(24.0)

    s._stub_jobs[0].finish()
    assert wait_until(lambda: second.state == SubmissionState.RUNNING)
    # The finished job's reservation was released before re-placement.
    assert s.stats()["reserved_hbm_gib"] == pytest.approx(24.0)


def test_estimate_none_degrades_to_capacity_only(sched_factory):
    s = sched_factory(
        max_concurrent_jobs=1, fleet_fn=TPUManager.get_mock_fleet,
        estimate_fn=lambda config, n_avail: None,
    )
    sub = s.submit(cfg(model_name="gpt-tiny"))
    assert wait_until(lambda: sub.state == SubmissionState.RUNNING)
    assert sub.estimate is None and len(sub.placement) == 2


# ---------------------------------------------------------------------------
# preempt-requeue
# ---------------------------------------------------------------------------


def test_preempt_requeue_and_priority_resume(sched_factory):
    s = sched_factory(max_concurrent_jobs=1)
    low = s.submit(cfg(), priority=JobPriority.LOW)
    assert wait_until(lambda: low.state == SubmissionState.RUNNING)
    low_job_1 = low.job

    high = s.submit(cfg(), priority=JobPriority.HIGH)
    # The head cannot be admitted at capacity → the LOW victim is told to
    # emergency-save (watcher seam), then requeued at its original seq.
    assert wait_until(lambda: low_job_1.watcher.fired.is_set())
    assert wait_until(lambda: high.state == SubmissionState.RUNNING)
    assert low.state == SubmissionState.QUEUED
    assert low.preemptions == 1 and low.attempts == 1
    assert s.requeues_total == 1 and s.preemptions_total == 1

    s._stub_jobs[-1].finish()  # high completes
    assert wait_until(lambda: low.state == SubmissionState.RUNNING)
    assert low.attempts == 2
    assert low.job is not low_job_1  # fresh attempt
    assert low.job_id == low.job.job_id  # same durable job identity
    s._stub_jobs[-1].finish()
    assert wait_until(lambda: low.state == SubmissionState.COMPLETED)


def test_requeued_victim_goes_to_front_of_its_class(sched_factory):
    s = sched_factory(max_concurrent_jobs=1)
    victim = s.submit(cfg(), priority=JobPriority.LOW)
    assert wait_until(lambda: victim.state == SubmissionState.RUNNING)
    later_low = s.submit(cfg(), priority=JobPriority.LOW)
    high = s.submit(cfg(), priority=JobPriority.HIGH)
    assert wait_until(lambda: high.state == SubmissionState.RUNNING)
    # Requeued victim keeps its ORIGINAL seq → ahead of the later LOW.
    order = [q["submission_id"] for q in s.queue_state()["queued"]]
    assert order == [victim.submission_id, later_low.submission_id]


def test_equal_priority_never_preempts(sched_factory):
    s = sched_factory(max_concurrent_jobs=1)
    first = s.submit(cfg(), priority=JobPriority.NORMAL)
    assert wait_until(lambda: first.state == SubmissionState.RUNNING)
    second = s.submit(cfg(), priority=JobPriority.NORMAL)
    time.sleep(0.1)
    s.poll()
    assert second.state == SubmissionState.QUEUED
    assert s.preemptions_total == 0
    assert first.state == SubmissionState.RUNNING


def test_non_preemptible_job_is_never_evicted(sched_factory):
    s = sched_factory(max_concurrent_jobs=1)
    # No checkpoint_dir → no emergency-save path → not preemptible.
    low = s.submit(cfg(checkpoint_dir=None), priority=JobPriority.LOW)
    assert wait_until(lambda: low.state == SubmissionState.RUNNING)
    s.submit(cfg(), priority=JobPriority.CRITICAL)
    time.sleep(0.1)
    s.poll()
    assert low.state == SubmissionState.RUNNING
    assert s.preemptions_total == 0


def test_one_eviction_frees_exactly_one_slot(sched_factory):
    s = sched_factory(max_concurrent_jobs=2)
    lows = [s.submit(cfg(), priority=JobPriority.LOW) for _ in range(2)]
    assert wait_until(
        lambda: all(x.state == SubmissionState.RUNNING for x in lows)
    )
    crit = s.submit(cfg(), priority=JobPriority.CRITICAL)
    assert wait_until(lambda: crit.state == SubmissionState.RUNNING)
    # One LOW was evicted for the one missing slot; the other kept running.
    assert s.preemptions_total == 1
    assert sum(1 for x in lows if x.state == SubmissionState.RUNNING) == 1


# ---------------------------------------------------------------------------
# quotas / cancel / drain
# ---------------------------------------------------------------------------


def test_per_submitter_quota(sched_factory):
    s = sched_factory(max_concurrent_jobs=0, default_quota=2,
                      quotas={"vip": 3})
    s.submit(cfg(), submitter="alice")
    s.submit(cfg(), submitter="alice")
    with pytest.raises(QuotaExceeded, match="alice"):
        s.submit(cfg(), submitter="alice")
    s.submit(cfg(), submitter="bob")  # separate budget
    for _ in range(3):
        s.submit(cfg(), submitter="vip")  # per-submitter override
    with pytest.raises(QuotaExceeded):
        s.submit(cfg(), submitter="vip")


def test_quota_frees_on_terminal_state(sched_factory):
    s = sched_factory(max_concurrent_jobs=0, default_quota=1)
    first = s.submit(cfg(), submitter="alice")
    with pytest.raises(QuotaExceeded):
        s.submit(cfg(), submitter="alice")
    assert s.cancel(first.submission_id)
    s.submit(cfg(), submitter="alice")  # slot freed


def test_cancel_queued_and_running(sched_factory):
    s = sched_factory(max_concurrent_jobs=1)
    running = s.submit(cfg())
    queued = s.submit(cfg())
    assert wait_until(lambda: running.state == SubmissionState.RUNNING)
    assert s.cancel(queued.submission_id)
    assert queued.state == SubmissionState.CANCELLED

    assert s.cancel(running.submission_id)
    assert wait_until(lambda: running.state == SubmissionState.CANCELLED)
    assert not s.cancel(running.submission_id)  # already terminal
    assert not s.cancel("sub_nope")
    assert s.stats()["cancelled_total"] == 2


def test_drain_pauses_admission(sched_factory):
    s = sched_factory(max_concurrent_jobs=2)
    s.drain()
    sub = s.submit(cfg())
    time.sleep(0.1)
    s.poll()
    assert sub.state == SubmissionState.QUEUED and s.draining
    s.resume_admission()
    assert wait_until(lambda: sub.state == SubmissionState.RUNNING)


def test_fleet_exception_degrades_to_capacity_only(sched_factory):
    def broken_fleet():
        raise RuntimeError("telemetry source down")

    s = sched_factory(max_concurrent_jobs=1, fleet_fn=broken_fleet)
    sub = s.submit(cfg())
    assert wait_until(lambda: sub.state == SubmissionState.RUNNING)


def test_failed_job_is_terminal_not_requeued(sched_factory):
    s = sched_factory(max_concurrent_jobs=1)
    sub = s.submit(cfg())
    assert wait_until(lambda: sub.state == SubmissionState.RUNNING)
    s._stub_jobs[0].finish(JobStatus.FAILED)
    assert wait_until(lambda: sub.state == SubmissionState.FAILED)
    assert sub.attempts == 1 and s.stats()["failed_total"] == 1


def test_job_factory_exception_fails_submission(sched_factory):
    def exploding(sub):
        raise RuntimeError("bad mesh")

    s = sched_factory(max_concurrent_jobs=1, job_factory=exploding)
    sub = s.submit(cfg())
    assert wait_until(lambda: sub.state == SubmissionState.FAILED)
    assert "bad mesh" in sub.last_skip_reason


def test_fleet_hbm_utilization_view(sched_factory):
    s = sched_factory(fleet_fn=TPUManager.get_mock_fleet)
    view = s.fleet_hbm_utilization()
    assert view is not None
    assert view["total_gib"] == pytest.approx(128.0)
    assert 0 < view["utilization_pct"] <= 100
    # No fleet source → no honest utilization number.
    assert sched_factory().fleet_hbm_utilization() is None


# ---------------------------------------------------------------------------
# elastic-shrink admission / grow-back / ledger release
# ---------------------------------------------------------------------------


def _chip(i, **kw):
    base = dict(
        index=i, device_kind="TPU v5e", hbm_total_gb=16.0, hbm_used_gb=4.0,
        duty_cycle_pct=50.0, temperature_c=50.0,
    )
    base.update(kw)
    return base


def _degraded_fleet():
    """8 chips, chip 0 thermally CRITICAL → 7 healthy."""
    mgr = TPUManager()
    return mgr.get_fleet_status(
        metrics=[_chip(0, temperature_c=91.0)] + [_chip(i) for i in range(1, 8)]
    )


def _healthy_fleet():
    mgr = TPUManager()
    return mgr.get_fleet_status(metrics=[_chip(i) for i in range(8)])


def elastic_cfg(**kw):
    base = dict(mesh=MeshConfig(data=4, fsdp=2), elastic_min_devices=2)
    base.update(kw)
    return cfg(**base)


def test_elastic_shrink_admission_on_degraded_fleet(sched_factory):
    s = sched_factory(max_concurrent_jobs=1, fleet_fn=_degraded_fleet)
    sub = s.submit(elastic_cfg())
    assert wait_until(lambda: sub.state == SubmissionState.RUNNING)
    # Gang 8 > 7 healthy, but elastic bounds admit data=3 × fsdp=2 on 6.
    assert sub.admitted_gang == 6
    assert sub.shrunk_mesh["data"] == 3 and sub.shrunk_mesh["fsdp"] == 2
    # The CRITICAL chip is never in the placement.
    assert 0 not in sub.placement and len(sub.placement) == 6
    st = s.stats()
    assert st["elastic_shrinks_total"] == 1
    assert st["running_shrunk"] == 1
    assert st["reserved_hbm_gib"] > 0


def test_non_elastic_job_still_skips_on_degraded_fleet(sched_factory):
    s = sched_factory(max_concurrent_jobs=1, fleet_fn=_degraded_fleet)
    sub = s.submit(cfg(mesh=MeshConfig(data=4, fsdp=2)))  # no elastic bounds
    time.sleep(0.1)
    assert sub.state == SubmissionState.QUEUED
    assert "gang of 8 device(s) > 7 healthy chip(s)" in sub.last_skip_reason
    assert s.stats()["elastic_shrinks_total"] == 0


def test_ledger_release_on_cancel_of_elastic_job(sched_factory):
    s = sched_factory(max_concurrent_jobs=1, fleet_fn=_degraded_fleet)
    sub = s.submit(elastic_cfg())
    assert wait_until(lambda: sub.state == SubmissionState.RUNNING)
    assert s.stats()["reserved_hbm_gib"] > 0
    assert s.cancel(sub.submission_id)
    assert wait_until(lambda: sub.state == SubmissionState.CANCELLED)
    # Every per-device reservation of the shrunk placement is returned.
    assert s.stats()["reserved_hbm_gib"] == 0.0
    assert s.stats()["running_shrunk"] == 0


def test_grow_back_when_fleet_heals(sched_factory):
    fleet_holder = {"fleet": _degraded_fleet()}
    s = sched_factory(
        max_concurrent_jobs=1, fleet_fn=lambda: fleet_holder["fleet"],
    )
    sub = s.submit(elastic_cfg())
    assert wait_until(lambda: sub.state == SubmissionState.RUNNING)
    assert sub.admitted_gang == 6
    # Chip 0 cools down → the full gang fits again → preempt-requeue-regrow.
    fleet_holder["fleet"] = _healthy_fleet()
    assert wait_until(
        lambda: sub.state == SubmissionState.RUNNING and sub.admitted_gang == 8,
        timeout=10.0,
    )
    assert sub.shrunk_mesh is None
    assert sub.attempts == 2
    st = s.stats()
    assert st["grow_backs_total"] == 1
    assert st["requeues_total"] == 1
    assert st["running_shrunk"] == 0
    # The ledger re-reserved for the full gang exactly once: all 8 chips,
    # and everything is returned when the job finishes.
    s._stub_jobs[-1].finish()
    assert wait_until(lambda: sub.state == SubmissionState.COMPLETED)
    assert s.stats()["reserved_hbm_gib"] == 0.0


def test_grow_back_waits_for_queued_work(sched_factory):
    """Queued submissions have first claim on freed chips — a shrunk job is
    not grown while anything is waiting in the queue."""
    fleet_holder = {"fleet": _degraded_fleet()}
    s = sched_factory(
        max_concurrent_jobs=1, fleet_fn=lambda: fleet_holder["fleet"],
    )
    sub = s.submit(elastic_cfg())
    assert wait_until(lambda: sub.state == SubmissionState.RUNNING)
    blocked = s.submit(cfg())  # queued: max_concurrent_jobs=1
    fleet_holder["fleet"] = _healthy_fleet()
    time.sleep(0.2)
    assert sub.admitted_gang == 6  # no grow-back while the queue is non-empty
    assert s.stats()["grow_backs_total"] == 0
    s._stub_jobs[0].finish()
    assert wait_until(lambda: blocked.state == SubmissionState.RUNNING)


def test_grow_back_hysteresis_rides_out_chip_flap(sched_factory):
    """A chip flapping healthy/unhealthy faster than the cooldown costs the
    job ONE elastic shrink — not a preempt-requeue storm. Regression for
    the pre-cooldown behavior where every heal window fired a grow-back
    that the next fault immediately re-shrank."""
    from tpu_engine import faults as faults_mod
    from tpu_engine.faults import FaultInjector, FaultKind, FaultPlan, FaultSpec

    # Chip 0 flaps: unhealthy for one injector step at steps 0, 2, 4, ...
    plan = FaultPlan(specs=[
        FaultSpec(
            kind=FaultKind.CHIP_UNHEALTHY, at_step=at, device_index=0,
            duration_steps=1,
        )
        for at in (0, 2, 4, 6, 8)
    ])
    inj = FaultInjector(plan)
    faults_mod.set_active(inj)
    try:
        inj.observe_step(0)  # chip 0 down at admission time
        mgr = TPUManager()
        s = sched_factory(
            max_concurrent_jobs=1,
            fleet_fn=lambda: mgr.get_fleet_status(
                metrics=[_chip(i) for i in range(8)]
            ),
            grow_back_cooldown_s=3600.0,  # cooldown >> the whole flap train
        )
        sub = s.submit(elastic_cfg())
        assert wait_until(lambda: sub.state == SubmissionState.RUNNING)
        assert sub.admitted_gang == 6 and 0 not in sub.placement
        # Drive the flap train: each odd step heals chip 0, each even step
        # re-faults it, with several scheduler passes inside every phase.
        for step in range(1, 10):
            inj.observe_step(step)
            time.sleep(0.06)
        st = s.stats()
        assert st["grow_backs_total"] == 0
        assert st["requeues_total"] == 0
        assert sub.attempts == 1 and sub.admitted_gang == 6
        assert sub.state == SubmissionState.RUNNING
        # Flap train exhausted (chip stays healthy). Once the operator's
        # cooldown has elapsed the ONE grow-back proceeds as usual.
        s.grow_back_cooldown_s = 0.0
        assert wait_until(
            lambda: sub.state == SubmissionState.RUNNING
            and sub.admitted_gang == 8,
            timeout=10.0,
        )
        assert s.stats()["grow_backs_total"] == 1
    finally:
        faults_mod.set_active(None)


def test_per_submitter_wait_and_goodput_stats(sched_factory):
    """Multi-tenant observability: queue wait and device-holding goodput
    are attributed per submitter, so a noisy neighbour shows up as THEIR
    numbers, not an anonymous fleet average."""
    s = sched_factory(max_concurrent_jobs=1)
    a = s.submit(cfg(), submitter="alice")
    assert wait_until(lambda: a.state == SubmissionState.RUNNING)
    b = s.submit(cfg(), submitter="bob")  # queued behind alice
    time.sleep(0.05)
    per = s.stats()["per_submitter"]
    assert per["alice"]["running"] == 1 and per["alice"]["queued"] == 0
    assert per["bob"]["queued"] == 1 and per["bob"]["running"] == 0

    s._stub_jobs[0].finish()
    assert wait_until(lambda: a.state == SubmissionState.COMPLETED)
    assert wait_until(lambda: b.state == SubmissionState.RUNNING)
    s._stub_jobs[1].finish()
    assert wait_until(lambda: b.state == SubmissionState.COMPLETED)
    per = s.stats()["per_submitter"]
    assert per["alice"]["completed_total"] == 1
    assert per["bob"]["completed_total"] == 1
    # Goodput: both held the device for a measurable interval.
    assert per["alice"]["goodput_busy_s"] > 0
    assert per["bob"]["goodput_busy_s"] > 0
    # Bob queued behind alice's run; alice was admitted immediately.
    assert per["bob"]["mean_wait_s"] >= per["alice"]["mean_wait_s"]


# ---------------------------------------------------------------------------
# placement planner wiring: mesh="auto", structured no_estimate, partial grow
# ---------------------------------------------------------------------------


def test_auto_placement_admits_predicted_fastest(sched_factory):
    s = sched_factory(max_concurrent_jobs=1, fleet_fn=_healthy_fleet)
    sub = s.submit(cfg(mesh=MeshConfig(data=-1, fsdp=2)), mesh="auto")
    assert sub.auto_place
    assert wait_until(lambda: sub.state == SubmissionState.RUNNING)
    # data=-1 means best available: the planner lands on the full fleet.
    assert sub.admitted_gang == 8
    plan = sub.placement_plan
    assert plan and plan["feasible"] > 0 and plan["label"]
    assert plan["chosen"]["mesh"]["data"] * plan["chosen"]["mesh"]["fsdp"] * \
        plan["chosen"]["mesh"]["pipe"] * plan["chosen"]["mesh"]["model"] == 8
    assert sub.predicted_step_time_s > 0
    st = s.stats()
    assert st["auto_admissions_total"] == 1
    assert st["placement"]["plans_chosen_total"] == 1
    # The queue surface carries the chosen plan for operators.
    running = s.queue_state()["running"]
    assert running[0]["placement_plan"]["label"] == plan["label"]


def test_auto_placement_resizes_on_degraded_fleet(sched_factory):
    s = sched_factory(max_concurrent_jobs=1, fleet_fn=_degraded_fleet)
    sub = s.submit(cfg(mesh=MeshConfig(data=-1, fsdp=1)), mesh="auto")
    assert wait_until(lambda: sub.state == SubmissionState.RUNNING)
    # 7 healthy chips: the plan is sized to the healthy remainder and the
    # CRITICAL chip is never in the placement.
    assert sub.admitted_gang == 7
    assert 0 not in sub.placement and len(sub.placement) == 7


def test_auto_placement_refuses_unknown_model(sched_factory):
    s = sched_factory(max_concurrent_jobs=1)
    with pytest.raises(ValueError, match="no_estimate:nope-9b"):
        s.submit(cfg(model_name="nope-9b"), mesh="auto")
    assert s.stats()["placement"]["no_estimate_refusals_total"] == 1
    # The refusal never entered the queue.
    assert s.stats()["submitted_total"] == 0


def test_auto_placement_rejects_bad_mesh_arg(sched_factory):
    s = sched_factory(max_concurrent_jobs=1)
    with pytest.raises(ValueError, match="mesh must be"):
        s.submit(cfg(), mesh="magic")


def test_unknown_model_explicit_mesh_gets_structured_reason(sched_factory):
    """estimate_job_hbm → None for an unknown model: admission still
    proceeds capacity-only (missing telemetry must not brick the queue)
    but the queue surface names WHY there is no HBM estimate."""
    s = sched_factory(max_concurrent_jobs=1, fleet_fn=_healthy_fleet)
    sub = s.submit(cfg(model_name="nope-9b", mesh=MeshConfig(data=1, fsdp=2)))
    assert wait_until(lambda: sub.state == SubmissionState.RUNNING)
    assert sub.last_skip_reason == "no_estimate:nope-9b"
    running = s.queue_state()["running"]
    assert running[0]["last_skip_reason"] == "no_estimate:nope-9b"
    assert s.stats()["no_estimate_skips_total"] == 1


def _three_down_fleet():
    """8 chips, chips 0-2 thermally CRITICAL → 5 healthy."""
    mgr = TPUManager()
    return mgr.get_fleet_status(
        metrics=[_chip(i, temperature_c=91.0) for i in range(3)]
        + [_chip(i) for i in range(3, 8)]
    )


def test_partial_grow_back_with_chip_still_unhealthy(sched_factory):
    """Regression (ROADMAP carry-over): when SOME of the sick chips heal,
    the shrunk job grows to the largest feasible INTERMEDIATE mesh — the
    full-gang-only logic waited for a perfectly healthy fleet."""
    fleet_holder = {"fleet": _three_down_fleet()}
    s = sched_factory(
        max_concurrent_jobs=1, fleet_fn=lambda: fleet_holder["fleet"],
    )
    sub = s.submit(elastic_cfg())
    assert wait_until(lambda: sub.state == SubmissionState.RUNNING)
    assert sub.admitted_gang == 4  # data=2 × fsdp=2 on the 5 healthy
    # Chips 1-2 heal; chip 0 stays CRITICAL → 7 healthy. Full gang (8)
    # still cannot be placed, but data=3 × fsdp=2 on 6 can.
    fleet_holder["fleet"] = _degraded_fleet()
    assert wait_until(
        lambda: sub.state == SubmissionState.RUNNING
        and sub.admitted_gang == 6,
        timeout=10.0,
    )
    assert sub.shrunk_mesh["data"] == 3 and sub.shrunk_mesh["fsdp"] == 2
    assert 0 not in sub.placement
    assert s.stats()["grow_backs_total"] == 1
    # The last chip heals → the second grow reaches the full gang.
    fleet_holder["fleet"] = _healthy_fleet()
    assert wait_until(
        lambda: sub.state == SubmissionState.RUNNING
        and sub.admitted_gang == 8,
        timeout=10.0,
    )
    assert sub.shrunk_mesh is None
    assert s.stats()["grow_backs_total"] == 2


def test_grow_back_is_hbm_gated(sched_factory):
    """Healed chips whose HBM headroom cannot hold the job's projection
    must not trigger a grow-back — preempting into an admission that
    re-shrinks is a flap, not a grow."""

    def big_est(c, available=None):
        # 8 GiB/device: with the planner's 35% compile margin the grow
        # needs 10.8 GiB headroom — the 12 GiB-free healthy chips clear
        # it, the nearly-full healed chip below cannot.
        return HBMEstimate(
            model_name=c.model_name, gang_devices=8,
            params_gib=8.0, grads_gib=0.0, opt_gib=0.0, working_gib=0.0,
            activations_gib=0.0, logits_gib=0.0,
            device_total_gib=8.0, host_gib=0.0,
        )

    fleet_holder = {"fleet": _degraded_fleet()}
    s = sched_factory(
        max_concurrent_jobs=1, fleet_fn=lambda: fleet_holder["fleet"],
    )
    sub = s.submit(elastic_cfg(), estimate_fn=big_est)
    assert wait_until(lambda: sub.state == SubmissionState.RUNNING)
    assert sub.admitted_gang == 6
    # Chip 0 heals but comes back nearly full: 1 GiB free < the job's
    # margined 10.8 GiB/device projection — the full gang cannot be placed.
    mgr = TPUManager()
    fleet_holder["fleet"] = mgr.get_fleet_status(
        metrics=[_chip(0, hbm_used_gb=15.0)] + [_chip(i) for i in range(1, 8)]
    )
    time.sleep(0.3)
    assert sub.admitted_gang == 6 and sub.attempts == 1
    assert s.stats()["grow_backs_total"] == 0
    # Once the chip's HBM actually drains, the grow-back proceeds.
    fleet_holder["fleet"] = _healthy_fleet()
    assert wait_until(
        lambda: sub.state == SubmissionState.RUNNING
        and sub.admitted_gang == 8,
        timeout=10.0,
    )
    assert s.stats()["grow_backs_total"] == 1


# ---------------------------------------------------------------------------
# heterogeneity policy: rebalance-over-shrink consults, quarantine lifecycle
# ---------------------------------------------------------------------------


def _slow_rebalancer(n=2, slow=1, signals=40, **kw):
    """A live-mode rebalancer whose tracker reads process 1 at ~0.5 —
    imbalance 2.0, best rebalance goodput ~0.89 (above the 0.80 floor)."""
    from tpu_engine import hetero as hetero_mod

    trk = hetero_mod.ThroughputTracker(n)
    for _ in range(signals):
        trk.note_host_slow(slow, 1.0, 1.0)
    kw.setdefault("sustain_consults", 1)
    kw.setdefault("min_gain", 0.01)
    kw.setdefault("dry_run", False)
    return hetero_mod.HeteroRebalancer(trk, 8, **kw)


def test_hetero_prefers_consult_over_shrink_and_settles_later(sched_factory):
    s = sched_factory(max_concurrent_jobs=1, fleet_fn=_healthy_fleet,
                      poll_interval_s=60.0, hetero_cooldown_s=0.0)
    sub = s.submit(cfg())
    assert wait_until(lambda: sub.state == SubmissionState.RUNNING)
    reb = _slow_rebalancer()
    s._stub_jobs[0]._hetero = reb
    s.poll()
    # The scheduler never moves rows itself: it requests a consult that
    # the supervisor serves at its next step boundary.
    assert reb.consult_pending()
    assert reb.rebalances_total == 0
    assert sub.state == SubmissionState.RUNNING  # every chip kept
    assert s._hetero_quarantined == {}
    st = s.stats()["hetero"]
    assert st["rebalance_preferred_total"] == 1
    assert st["shrinks_avoided_total"] == 0  # nothing has settled yet
    assert st["rebalances_total"] == 0
    # Re-polling while the consult is outstanding must not double-count.
    s.poll()
    assert s.stats()["hetero"]["rebalance_preferred_total"] == 1
    # The job's rebalancer serves the consult (what the supervisor does at
    # the step boundary) — only then does the shrink count as avoided.
    plan = reb.maybe_rebalance(10)
    assert plan is not None and not plan.dry_run
    assert not reb.consult_pending()
    s.poll()
    st = s.stats()["hetero"]
    assert st["shrinks_avoided_total"] == 1
    assert st["rebalances_total"] == 1
    assert st["shrinks_total"] == 0


def test_hetero_declined_consult_is_not_counted_as_avoided(sched_factory):
    s = sched_factory(max_concurrent_jobs=1, fleet_fn=_healthy_fleet,
                      poll_interval_s=60.0, hetero_cooldown_s=0.0)
    sub = s.submit(cfg())
    assert wait_until(lambda: sub.state == SubmissionState.RUNNING)
    # min_gain=1.0: the rebalancer will always decline on the gain floor.
    reb = _slow_rebalancer(min_gain=1.0)
    s._stub_jobs[0]._hetero = reb
    s.poll()
    assert reb.consult_pending()
    assert s.stats()["hetero"]["rebalance_preferred_total"] == 1
    assert reb.maybe_rebalance(10) is None  # consult served, declined
    s.poll()
    st = s.stats()["hetero"]
    # Forgotten, not a win — and since the imbalance persists, the same
    # pass opens a fresh consult rather than silently giving up.
    assert st["shrinks_avoided_total"] == 0
    assert st["rebalances_total"] == 0
    assert st["rebalance_preferred_total"] == 2
    assert reb.consult_pending()


def test_hetero_shrink_quarantines_with_owner_and_ttl_backstop(sched_factory):
    # Fixed gang 8 so the preempted job cannot re-admit on the 4 chips
    # left after quarantine — the entries must then expire via TTL.
    s = sched_factory(max_concurrent_jobs=1, fleet_fn=_healthy_fleet,
                      poll_interval_s=60.0, grow_back=False,
                      hetero_cooldown_s=0.0, hetero_goodput_floor=2.0,
                      hetero_quarantine_ttl_s=0.05)
    sub = s.submit(cfg(mesh=MeshConfig(data=4, fsdp=2)))
    assert wait_until(lambda: sub.state == SubmissionState.RUNNING)
    job = s._stub_jobs[0]
    job._hetero = _slow_rebalancer()
    s.poll()
    # Floor unreachable -> shrink: the slow host's chips are quarantined
    # with their owner recorded, and the job is preempt-requeued.
    assert sub.state == SubmissionState.PREEMPTING
    assert set(s._hetero_quarantined) == {4, 5, 6, 7}
    assert all(e["owner"] == sub.submission_id
               for e in s._hetero_quarantined.values())
    assert s.stats()["hetero"]["shrinks_total"] == 1
    assert wait_until(lambda: not job.is_alive)
    s.poll()  # reap -> requeue; gang 8 > 4 eligible -> stays QUEUED
    assert sub.state == SubmissionState.QUEUED
    assert set(s._hetero_quarantined) == {4, 5, 6, 7}
    # TTL is the backstop for exactly this shape: the requeued attempt has
    # no tracker that could ever vouch for the quarantined chips.
    time.sleep(0.06)
    s.poll()  # heal runs after _admit: this pass only releases the chips
    assert s._hetero_quarantined == {}
    s.poll()  # ...and the next one admits the full gang again
    assert sub.state == SubmissionState.RUNNING
    assert sub.admitted_gang == 8


def test_hetero_quarantine_released_when_owner_reaches_terminal_state(sched_factory):
    s = sched_factory(max_concurrent_jobs=1, fleet_fn=_healthy_fleet,
                      poll_interval_s=60.0, grow_back=False,
                      hetero_cooldown_s=0.0, hetero_goodput_floor=2.0)
    sub = s.submit(cfg(mesh=MeshConfig(data=4, fsdp=2)))
    assert wait_until(lambda: sub.state == SubmissionState.RUNNING)
    job = s._stub_jobs[0]
    job._hetero = _slow_rebalancer()
    s.poll()
    assert set(s._hetero_quarantined) == {4, 5, 6, 7}
    # The owner is cancelled while quarantined: terminal submissions stay
    # in scheduler history forever, so the entries must not wait for them.
    s.cancel(sub.submission_id)
    assert wait_until(lambda: not job.is_alive)
    s.poll()  # reap -> CANCELLED (terminal, but kept in history)
    assert wait_until(lambda: sub.state == SubmissionState.CANCELLED)
    s.poll()
    assert s._hetero_quarantined == {}


def test_hetero_quarantine_no_tracker_release_on_readmission(sched_factory):
    # Elastic gang: after the shrink the job re-admits on the remaining 4
    # chips — the fresh attempt has no heterogeneity plane, so nothing can
    # ever vouch for the quarantined chips and they are released at once
    # (the detector re-quarantines if the host is still slow).
    s = sched_factory(max_concurrent_jobs=1, fleet_fn=_healthy_fleet,
                      poll_interval_s=60.0, grow_back=False,
                      hetero_cooldown_s=0.0, hetero_goodput_floor=2.0)
    sub = s.submit(elastic_cfg())
    assert wait_until(lambda: sub.state == SubmissionState.RUNNING)
    job = s._stub_jobs[0]
    job._hetero = _slow_rebalancer()
    s.poll()
    assert set(s._hetero_quarantined) == {4, 5, 6, 7}
    assert wait_until(lambda: not job.is_alive)
    s.poll()  # reap -> requeue -> shrunk re-admit -> heal (no tracker)
    assert sub.state == SubmissionState.RUNNING
    assert sub.admitted_gang == 4
    assert 4 not in sub.placement  # admitted around the quarantine
    assert s._hetero_quarantined == {}


def test_hetero_quarantine_heals_per_process_estimate(sched_factory):
    s = sched_factory(max_concurrent_jobs=1, fleet_fn=_healthy_fleet,
                      poll_interval_s=60.0, grow_back=False)
    sub = s.submit(cfg())
    assert wait_until(lambda: sub.state == SubmissionState.RUNNING)
    s._stub_jobs[0]._hetero = _slow_rebalancer()  # proc 0 at 1.0, proc 1 ~0.5
    now = time.time()
    s._hetero_quarantined[0] = {"owner": sub.submission_id, "ts": now}
    s._hetero_quarantined[7] = {"owner": sub.submission_id, "ts": now}
    s.poll()
    # Chip 0 belongs to the healthy process (1.0 >= heal threshold 0.95);
    # chip 7's process still reads ~0.5 and stays out of admission.
    assert 0 not in s._hetero_quarantined
    assert 7 in s._hetero_quarantined


# ---------------------------------------------------------------------------
# Metrics scrape cost: index-backed, read-only
# ---------------------------------------------------------------------------


def test_metrics_scrape_is_readonly_and_index_backed(sched_factory):
    """A scrape (``stats()``) reads the state indexes: it never iterates
    ``_subs`` — so its cost is O(queued + running + tenants), not O(every
    submission the scheduler has ever seen) — and never mutates state."""
    s = sched_factory(max_concurrent_jobs=4)
    done = [s.submit(cfg()) for _ in range(12)]
    for _ in range(200):
        for j in s._stub_jobs:
            j.finish()
        if all(d.state == SubmissionState.COMPLETED for d in done):
            break
        time.sleep(0.02)
    assert all(d.state == SubmissionState.COMPLETED for d in done)
    s.max_concurrent_jobs = 0  # freeze admission: deterministic queue
    queued = [s.submit(cfg(), priority=JobPriority.LOW) for _ in range(6)]

    class CountingSubs(dict):
        scans = 0

        def values(self):
            CountingSubs.scans += 1
            return super().values()

        def items(self):
            CountingSubs.scans += 1
            return super().items()

        def __iter__(self):
            CountingSubs.scans += 1
            return super().__iter__()

    states_before = {sid: sub.state for sid, sub in s._subs.items()}
    s._subs = CountingSubs(s._subs)
    CountingSubs.scans = 0
    try:
        first = s.stats()
        second = s.stats()
        assert CountingSubs.scans == 0, (
            "stats() scanned _subs — scrape cost grew with terminal history"
        )
        # queue_state() reads the queued/running/finished indexes too:
        # rendering "finished" is O(terminal) because that is the size of
        # the answer, never a _subs scan.
        qs = s.queue_state()
        assert CountingSubs.scans == 0, (
            "queue_state() scanned _subs — history surface lost its index"
        )
    finally:
        s._subs = dict(s._subs)
    # Read-only: repeated scrapes agree (modulo the wall-clock age of the
    # oldest queued entry) and no submission changed state.
    first.pop("oldest_queued_wait_s")
    second.pop("oldest_queued_wait_s")
    assert first == second
    assert {sid: sub.state for sid, sub in s._subs.items()} == states_before
    assert [q["submission_id"] for q in qs["queued"]] == [
        q.submission_id for q in queued
    ]
    assert len(qs["finished"]) == 12


# ---------------------------------------------------------------------------
# A pass samples the fleet lazily and at most once: only when a decision of
# that pass depends on it
# ---------------------------------------------------------------------------


class CountingFleet:
    """A ``fleet_fn`` that counts its calls; ``fleet`` is what it returns
    (swap it to heal or degrade the fleet) and ``broken`` makes it raise."""

    def __init__(self, fleet=None):
        self.fleet = fleet if fleet is not None else _healthy_fleet()
        self.calls = 0
        self.broken = False

    def __call__(self):
        self.calls += 1
        if self.broken:
            raise RuntimeError("telemetry source down")
        return self.fleet


def _hand_pumped(sched_factory, fleet, **kw):
    """A scheduler whose only passes are the test's own ``poll()`` calls (no
    pump thread), so that samples can be counted pass by pass."""
    s = sched_factory(fleet_fn=fleet, **kw)
    s._ensure_thread = lambda: None
    return s


def _one_pass(s, fleet):
    """Run one pass; returns the samples it took, by the caller's count and
    by the scheduler's own."""
    calls, counted = fleet.calls, s.stats()["fleet_samples_total"]
    s.poll()
    took = fleet.calls - calls
    assert s.stats()["fleet_samples_total"] - counted == took
    return took


def _steady_one_job_empty_queue(s):
    s.submit(cfg())
    return None


def _head_behind_equal_priority(s):
    s.submit(cfg(), priority=JobPriority.NORMAL)
    s.poll()
    return s.submit(cfg(), priority=JobPriority.NORMAL)


def _head_behind_non_preemptible(s):
    # No checkpoint_dir → no emergency-save path → nobody to evict.
    s.submit(cfg(checkpoint_dir=None), priority=JobPriority.LOW)
    s.poll()
    return s.submit(cfg(), priority=JobPriority.CRITICAL)


def _head_behind_an_eviction_in_flight(s):
    low = [s.submit(cfg(), priority=JobPriority.LOW) for _ in range(2)]
    s.poll()
    s._set_state(low[0], SubmissionState.PREEMPTING)  # its save has not landed
    return s.submit(cfg(), priority=JobPriority.HIGH)


@pytest.mark.parametrize("arrange, slots", [
    (_steady_one_job_empty_queue, 1),
    (_head_behind_equal_priority, 1),
    (_head_behind_non_preemptible, 1),
    (_head_behind_an_eviction_in_flight, 2),
], ids=lambda v: v.__name__.lstrip("_") if callable(v) else None)
def test_a_pass_with_nothing_to_decide_takes_no_sample(sched_factory, arrange, slots):
    """One job beside an empty queue (every benchmark cell's steady state),
    and a queued head at ``max_concurrent_jobs`` that no eviction can help:
    N passes, no call of ``fleet_fn``; the head keeps its skip reason."""
    fleet = CountingFleet()
    s = _hand_pumped(sched_factory, fleet, max_concurrent_jobs=slots)
    head = arrange(s)
    s.poll()  # admits what fits (that pass samples: admission is the decision)
    settled = s.stats()
    assert settled["running"] == slots and settled["fleet_samples_total"] >= 1
    calls = fleet.calls
    for _ in range(10):
        assert _one_pass(s, fleet) == 0
    after = s.stats()
    assert fleet.calls == calls
    assert after["fleet_samples_total"] - settled["fleet_samples_total"] == 0
    assert after["poll_passes_total"] - settled["poll_passes_total"] == 10
    assert after["preemptions_total"] == settled["preemptions_total"]
    assert all(j.is_alive and not j.watcher.fired.is_set() for j in s._stub_jobs)
    if head is not None:
        assert head.state == SubmissionState.QUEUED
        assert head.last_skip_reason == "at max_concurrent_jobs capacity"


@pytest.mark.parametrize("broken", [False, True], ids=["sampled", "fleet_fn_raises"])
def test_a_head_at_capacity_samples_only_once_it_has_a_victim(sched_factory, broken):
    """A lower-priority preemptible job runs: now whether the head could be
    placed at all decides the eviction, so the pass samples (once) and the
    victim goes PREEMPTING. A ``fleet_fn`` that raises on that lazy first
    use degrades the pass to capacity-only, which evicts too."""
    fleet = CountingFleet()
    s = _hand_pumped(sched_factory, fleet, max_concurrent_jobs=1)
    low = s.submit(cfg(), priority=JobPriority.LOW)
    s.poll()
    assert low.state == SubmissionState.RUNNING
    fleet.broken = broken
    high = s.submit(cfg(), priority=JobPriority.HIGH)
    assert _one_pass(s, fleet) == 1
    assert low.state == SubmissionState.PREEMPTING
    assert low.job.watcher.fired.is_set()
    assert high.last_skip_reason == "at max_concurrent_jobs capacity"
    assert s.preemptions_total == 1
    # The eviction in flight is fleet-free knowledge: no sample while it lands.
    low.job.join(timeout=5.0)
    fleet.broken = False
    assert _one_pass(s, fleet) == 1  # reap → requeue → a free slot → admission
    assert high.state == SubmissionState.RUNNING and low.state == SubmissionState.QUEUED


def test_a_victim_is_not_evicted_for_a_head_the_fleet_can_never_place(sched_factory):
    """The sample a victim triggers still protects it: a head whose gang
    exceeds the healthy fleet evicts nobody, pass after pass."""
    fleet = CountingFleet()
    s = _hand_pumped(sched_factory, fleet, max_concurrent_jobs=1)
    low = s.submit(cfg(), priority=JobPriority.LOW)
    s.poll()
    fleet.fleet = _degraded_fleet()  # 7 healthy chips
    head = s.submit(cfg(mesh=MeshConfig(data=4, fsdp=2)), priority=JobPriority.HIGH)
    for _ in range(3):
        assert _one_pass(s, fleet) == 1
    assert low.state == SubmissionState.RUNNING and s.preemptions_total == 0
    assert head.last_skip_reason == "at max_concurrent_jobs capacity"


def test_a_shrunk_job_is_sampled_for_only_past_its_cooldown(sched_factory):
    """Grow-back asks who could grow before it asks the fleet: a shrunk job
    inside ``grow_back_cooldown_s`` costs no sample; past it the pass
    samples, and the healed fleet grows the job back."""
    fleet = CountingFleet(_degraded_fleet())
    s = _hand_pumped(
        sched_factory, fleet, max_concurrent_jobs=1, grow_back_cooldown_s=3600.0,
        precompile_before_grow=False,
    )
    sub = s.submit(elastic_cfg())
    assert _one_pass(s, fleet) == 1
    assert sub.state == SubmissionState.RUNNING and sub.admitted_gang == 6
    fleet.fleet = _healthy_fleet()
    for _ in range(5):
        assert _one_pass(s, fleet) == 0
    assert s.stats()["grow_backs_total"] == 0
    s.grow_back_cooldown_s = 0.0
    assert _one_pass(s, fleet) == 1
    assert s.stats()["grow_backs_total"] == 1
    assert sub.state == SubmissionState.PREEMPTING
    sub.job.join(timeout=5.0)
    assert _one_pass(s, fleet) == 1  # reap → requeue → admission at the full gang
    assert sub.state == SubmissionState.RUNNING and sub.admitted_gang == 8


def test_a_pass_that_admits_and_grows_takes_exactly_one_sample(sched_factory):
    """Admission and grow-back of one pass read the same sample."""
    fleet = CountingFleet(_degraded_fleet())
    s = _hand_pumped(
        sched_factory, fleet, max_concurrent_jobs=2, precompile_before_grow=False,
    )
    shrunk = s.submit(elastic_cfg())
    s.poll()
    assert shrunk.admitted_gang == 6
    fleet.fleet = _healthy_fleet()
    s.drain()  # hold every decision until both are due in one pass
    late = s.submit(cfg())
    assert _one_pass(s, fleet) == 0  # a draining pass reaps and decides nothing
    s.resume_admission()
    admitted, grown = s.admitted_total, s.grow_backs_total
    assert _one_pass(s, fleet) == 1
    assert late.state == SubmissionState.RUNNING
    assert s.admitted_total == admitted + 1 and s.grow_backs_total == grown + 1
    assert shrunk.state == SubmissionState.PREEMPTING


def test_healing_a_quarantine_shares_the_pass_sample(sched_factory):
    """Two quarantined chips whose running owner has a tracker: the heal
    asks the fleet's size once for both, not once an entry."""
    fleet = CountingFleet()
    s = _hand_pumped(sched_factory, fleet, max_concurrent_jobs=1)
    sub = s.submit(cfg())
    s.poll()
    s._stub_jobs[0]._hetero = _slow_rebalancer()
    now = time.time()
    for idx in (0, 7):
        s._hetero_quarantined[idx] = {"owner": sub.submission_id, "ts": now}
    assert _one_pass(s, fleet) == 1
    assert 0 not in s._hetero_quarantined and 7 in s._hetero_quarantined


def test_callers_outside_a_pass_sample_when_they_ask(sched_factory):
    """``fleet_hbm_utilization`` (the telemetry's caller) and ``_fleet`` (the
    launcher's plan) are no pass: each call is a fresh sample, none is a
    pass's, and one from another thread while a pass is open is not the
    pass's either."""
    fleet = CountingFleet()
    s = _hand_pumped(sched_factory, fleet, max_concurrent_jobs=1)
    assert s.fleet_hbm_utilization() is not None and s._fleet() is fleet.fleet
    assert fleet.calls == 2 and s.stats()["fleet_samples_total"] == 0

    seen = []
    s.submit(cfg())

    def during_the_pass():
        fleet.calls += 1
        if threading.current_thread() is threading.main_thread():
            t = threading.Thread(target=lambda: seen.append(s._fleet()))
            t.start()
            t.join(timeout=5.0)
        return fleet.fleet

    s.fleet_fn = during_the_pass
    s.poll()  # admission samples; the other thread's call goes to fleet_fn itself
    assert fleet.calls == 4 and seen == [fleet.fleet]
    assert s.stats()["fleet_samples_total"] == 1 and s.stats()["poll_passes_total"] == 1
