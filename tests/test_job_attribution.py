"""Per-chip job attribution in the fleet view (VERDICT r2 item 4).

The reference fleet reports, per GPU, the live process table
(``gpu_manager.py:27-33``, populated ``:174-184``) so an operator can see
what occupies a device. TPU runtimes expose no foreign-process table, so
the analogue is the control plane's OWN supervised jobs: each supervisor
claims its mesh's local chip ids while running
(``telemetry.register_job_devices``) and the fleet snapshot attributes
them per device.
"""

from __future__ import annotations

import time

import jax
import pytest

from tpu_engine import telemetry
from tpu_engine.launcher import TPULauncher
from tpu_engine.mesh_runtime import MeshConfig
from tpu_engine.sharding import Precision, TPUTrainConfig
from tpu_engine.supervisor import JobStatus
from tpu_engine.tpu_manager import TPUManager


@pytest.fixture(autouse=True)
def _clean_claims():
    yield
    # Never leak claims across tests.
    for did_jobs in telemetry.job_attribution().values():
        for ref in did_jobs:
            telemetry.unregister_job_devices(ref["job_id"])


def test_registry_attributes_exactly_the_claimed_chips():
    telemetry.register_job_devices("job-a", [0, 2], 0, lambda: "running")
    telemetry.register_job_devices("job-b", [2, 3], 1, lambda: "compiling")
    att = telemetry.job_attribution()
    assert {r["job_id"] for r in att[0]} == {"job-a"}
    assert {r["job_id"] for r in att[2]} == {"job-a", "job-b"}
    assert att[3] == [{"job_id": "job-b", "status": "compiling", "process_index": 1}]
    assert 1 not in att
    telemetry.unregister_job_devices("job-a")
    assert "job-a" not in {r["job_id"] for refs in telemetry.job_attribution().values() for r in refs}


def test_status_fn_failure_reports_unknown():
    def boom():
        raise RuntimeError("job object gone")

    telemetry.register_job_devices("job-x", [1], 0, boom)
    assert telemetry.job_attribution()[1][0]["status"] == "unknown"


def test_fleet_snapshot_attributes_running_job_to_its_mesh_chips():
    """Launch a real (tiny) supervised job on the 8-device CPU mesh and
    assert the LIVE fleet snapshot pins it to exactly its mesh's chips.

    (A mesh must cover every visible device in one process, so here "its
    chips" is the full host; subset exactness — a job claiming 4 of 8 —
    is pinned by ``test_registry_attributes_exactly_the_claimed_chips``,
    and per-process halves by the two-process distributed smoke.)"""
    launcher = TPULauncher()
    cfg = TPUTrainConfig(
        model_name="gpt-tiny", mesh=MeshConfig(data=2, fsdp=4),
        micro_batch_size=1, seq_len=32, precision=Precision.FP32,
        total_steps=5000, warmup_steps=2, activation_checkpointing=False,
    )
    res = launcher.launch(cfg, dry_run=False, block=False)
    assert res.status == "launched"
    job = launcher.get_job(res.job_id)
    manager = TPUManager()
    try:
        deadline = time.time() + 120
        held = []
        while time.time() < deadline:
            fleet = manager.get_fleet_status()
            held = [
                d for d in fleet.devices
                if any(r.job_id == res.job_id for r in d.jobs)
            ]
            if held:
                break
            assert job.status not in (JobStatus.FAILED, JobStatus.COMPLETED), (
                job.status, job.error,
            )
            time.sleep(0.2)
        assert held, "job never appeared in the fleet attribution"
        # Exactly the chips of its mesh, nothing else.
        mesh_ids = {
            int(d.id) for d in job.program.runtime.mesh.devices.flat
        }
        assert {d.index for d in held} == mesh_ids
        ref = next(r for r in held[0].jobs if r.job_id == res.job_id)
        assert ref.status in ("running", "compiling")
        assert ref.process_index == jax.process_index()
    finally:
        launcher.stop_job(res.job_id)
        job.join()

    # Terminal job releases its chips.
    fleet = manager.get_fleet_status()
    assert not any(
        r.job_id == res.job_id for d in fleet.devices for r in d.jobs
    )


# ---------------------------------------------------------------------------
# Own load is not a fault and not a stranger's (PR 21: first live HBM
# telemetry — a full-width job read as CRITICAL on its own chip).
# ---------------------------------------------------------------------------


class _FullChip:
    """A runtime device whose memory_stats reads 96% full."""

    platform = "tpu"
    device_kind = "TPU v5 lite"
    process_index = 0
    coords = (0, 0, 0)
    core_on_chip = 0

    def __init__(self, id_=0, used_frac=0.96):
        self.id = id_
        self._used = used_frac

    def memory_stats(self):
        limit = 16 * 2**30
        return {"bytes_limit": limit, "bytes_in_use": int(limit * self._used)}


@pytest.fixture
def _busy_duty():
    """The derived source reports the chips 99% busy; no SDK, no CLI."""
    src = telemetry.DerivedDutySource()
    telemetry.set_sources([src])
    yield src
    telemetry.set_sources(None)


def test_own_footprint_and_duty_do_not_read_as_fault_or_unavailable(_busy_duty):
    _busy_duty.observe(0.99, 1.0, device_ids=[0])
    mgr = TPUManager(devices=[_FullChip(0)])
    # Nobody here placed this load: the reference thresholds hold.
    (dev,) = mgr.get_fleet_status().devices
    assert dev.hbm_utilization_pct >= 95 and dev.duty_cycle_pct == 99.0
    assert dev.health_status.value == "critical" and not dev.is_available
    # The same chip, carrying this control plane's job: full and busy by
    # design — healthy, schedulable, nothing for a supervisor to heal.
    telemetry.register_job_devices("own-job", [0], 0, lambda: "running")
    (dev,) = mgr.get_fleet_status().devices
    assert dev.carries_own_load and [j.job_id for j in dev.jobs] == ["own-job"]
    assert dev.health_status.value == "healthy" and dev.alerts == []
    assert dev.is_available
    # A real fault still shows through own load.
    from tpu_engine import faults

    faults.activate(faults.FaultPlan(specs=[faults.FaultSpec(
        kind=faults.FaultKind.CHIP_UNHEALTHY, at_step=0, device_index=0,
    )]))
    try:
        (dev,) = mgr.get_fleet_status().devices
        assert dev.health_status.value == "critical" and not dev.is_available
    finally:
        faults.clear_active()


def test_injected_snapshots_keep_the_load_thresholds():
    """Injected snapshots carry no job claims: 80% HBM / 90% duty still
    make a chip unschedulable, 95% HBM still reads CRITICAL."""
    telemetry.register_job_devices("own-job", [0], 0, lambda: "running")
    fleet = TPUManager().get_fleet_status(metrics=[
        {"index": 0, "hbm_total_gb": 16.0, "hbm_used_gb": 15.5},
        {"index": 1, "hbm_total_gb": 16.0, "hbm_used_gb": 4.0,
         "duty_cycle_pct": 92.0},
    ])
    assert fleet.devices[0].health_status.value == "critical"
    assert [d.is_available for d in fleet.devices] == [False, False]


def test_job_end_drops_its_duty_reading(_busy_duty):
    """A chip must not read busy for max_age_s after its job left: the
    next admission (requeue, resume, the job after) would sit queued."""
    telemetry.register_job_devices("done-job", [0], 0, lambda: "running")
    telemetry.observe_step(0.99, 1.0, device_ids=[0])
    _busy_duty.observe(0.99, 1.0, device_ids=[0])
    mgr = TPUManager(devices=[_FullChip(0, used_frac=0.1)])
    telemetry.unregister_job_devices("done-job")
    # The process-wide source forgot the scope ...
    assert "0" not in telemetry.derived_duty().staleness()["scope_ages_s"]
    # ... so with that source registered the chip reads idle again.
    telemetry.set_sources([telemetry.derived_duty()])
    (dev,) = mgr.get_fleet_status().devices
    assert dev.duty_cycle_pct is None and dev.is_available


def test_delete_job_releases_device_state():
    """delete_job gives the chips back even though the scheduler's
    submission record still references the job object."""
    launcher = TPULauncher()
    cfg = TPUTrainConfig(
        model_name="gpt-tiny", mesh=MeshConfig(data=2, fsdp=4),
        micro_batch_size=1, seq_len=32, precision=Precision.FP32,
        total_steps=100, warmup_steps=2, activation_checkpointing=False,
    )
    res = launcher.launch(cfg, max_steps=2, block=True)
    job = launcher.get_job(res.job_id)
    assert job.status == JobStatus.COMPLETED and job._state is not None
    sub = launcher.scheduler.get(res.submission_id)
    assert launcher.delete_job(res.job_id)
    assert sub.job is job  # history keeps the husk ...
    assert job._state is None and job.program is None  # ... not the HBM
    assert job.describe()["status"] == "completed"
    assert job.describe()["attention_impl"] == "xla"
    with pytest.raises(RuntimeError, match="no initialized state"):
        job.generate_sample([[1, 2, 3]])
    assert launcher.scheduler.stats()["reserved_hbm_gib"] == 0.0
