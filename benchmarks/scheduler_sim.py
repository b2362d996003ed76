"""Fleet-scheduler simulation: mixed-priority trace + real preempt-resume.

Two phases, both deterministic and both runnable on CPU
(``JAX_PLATFORMS=cpu python -m benchmarks.scheduler_sim``):

**Phase A — 20-job mixed-priority trace on the mock fleet.** FakeJobs
(thread-backed, timed "work", honoring the scheduler's stop/preempt verbs)
drive :class:`~tpu_engine.scheduler.FleetScheduler` against
``TPUManager.get_mock_fleet()`` (8 chips, chip 5 hot → 7 healthy). Measures
makespan, mean admission wait, and goodput (completed work-seconds per
wall-second) against the analytic **serial FIFO** baseline the reference
launcher amounts to (one job at a time, submission order, no queue). The
trace includes:

- a HIGH-priority gang-8 job that can never be placed (7 healthy chips) —
  backfill admits the jobs behind it while its skip reason says why, and it
  is cancelled at the end (chip 5 never heals);
- a CRITICAL job arriving mid-trace that preempts the lowest-priority
  running job through the emergency-save seam; the victim requeues and
  finishes with **zero lost work** (progress survives the preempt);
- per-device HBM demands that make the reservation ledger matter (two
  5 GiB jobs cannot stack on one 9.6 GiB-free chip).

**Phase C — warm-admission virtual lane.** A seeded single-slot queue of
jobs over a handful of mesh layouts, priced through a real (in-memory)
:class:`~tpu_engine.compile_index.CompileCacheIndex`: the first job on a
layout compiles cold, later ones hit the warm cache. The same job list is
admitted twice — strict FIFO vs warm-preferring (the scheduler/planner's
cache-aware admission: among queued jobs, one whose layout the index says
is warm goes first). Warm-preferring front-loads cache hits, so mean
admission wait drops; the delta is the cache-aware-admission headline.

**Phase B — real checkpoint-preempt-requeue round trip.** A LOW-priority
gpt-tiny job (40 steps, checkpoint interval beyond the horizon so only the
emergency save can persist progress) is preempted by a HIGH-priority job on
a one-slot scheduler: watcher fires → synchronous Orbax save → requeue →
HIGH runs → LOW re-admitted and resumes from exactly the saved step.
Asserts ``resumed_from_step == step at preemption`` — zero lost steps.

Prints one JSON document. The mock fleet's jobs are fakes with timed
"work": what the trace shows is that the queue packs and loses nothing, not
how fast anything runs.
"""

from __future__ import annotations

import json
import os
import random
import sys
import tempfile
import threading
import time
from typing import Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpu_engine.goodput import GoodputLedger, set_ledger  # noqa: E402
from tpu_engine.hbm_estimate import HBMEstimate, gang_size  # noqa: E402
from tpu_engine.mesh_runtime import MeshConfig  # noqa: E402
from tpu_engine.scheduler import (  # noqa: E402
    FleetScheduler,
    JobPriority,
    SubmissionState,
)
from tpu_engine.sharding import TPUTrainConfig  # noqa: E402
from tpu_engine.supervisor import JobStatus  # noqa: E402
from tpu_engine.tpu_manager import TPUManager  # noqa: E402
from tpu_engine.twin import warm_admission_lane  # noqa: E402

# ---------------------------------------------------------------------------
# Phase A: FakeJob trace on the mock fleet.
# ---------------------------------------------------------------------------

_TICK = 0.02  # one FakeJob "step" in seconds


class _FakeWatcher:
    """The one verb the scheduler speaks to a watcher."""

    def __init__(self, job: "FakeJob"):
        self._job = job

    def simulate_interruption(self) -> None:
        self._job._preempt.set()


class FakeJob:
    """Thread-backed stand-in for TrainingJob: timed work instead of train
    steps, same lifecycle surface the scheduler drives (status / is_alive /
    start / join / _stop / watcher). Progress lives in a shared registry
    keyed by submission id, so a preempted attempt's work survives — the
    FakeJob analogue of the emergency checkpoint."""

    def __init__(self, sub, duration_s: float, progress: dict[str, float]):
        self.job_id = sub.job_id
        self.config = sub.config
        self.status = JobStatus.PENDING
        self.error: Optional[str] = None
        self._stop = threading.Event()
        self._preempt = threading.Event()
        self.watcher = _FakeWatcher(self)
        self._progress = progress
        self._key = sub.submission_id
        self.duration_s = duration_s
        done = progress.get(self._key, 0.0)
        self.current_step = int(done / _TICK)
        self.resumed_from_step = self.current_step or None
        self._thread = threading.Thread(target=self._run, daemon=True)

    @property
    def is_alive(self) -> bool:
        return self._thread.is_alive()

    def start(self) -> None:
        self._thread.start()

    def join(self, timeout: Optional[float] = None) -> None:
        self._thread.join(timeout)

    def describe(self) -> dict:
        return {
            "job_id": self.job_id,
            "status": self.status.value,
            "current_step": self.current_step,
        }

    def _run(self) -> None:
        self.status = JobStatus.RUNNING
        done = self._progress.get(self._key, 0.0)
        while done < self.duration_s:
            if self._stop.is_set():
                self._progress[self._key] = done
                self.status = JobStatus.STOPPED
                return
            if self._preempt.is_set():
                self._progress[self._key] = done  # the "emergency save"
                self.status = JobStatus.PREEMPTED
                return
            time.sleep(_TICK)
            done += _TICK
            self.current_step = int(done / _TICK)
        self._progress[self._key] = self.duration_s
        self.status = JobStatus.COMPLETED


def _trace_config(tag: int, gang: int) -> TPUTrainConfig:
    """One trace job's config. ``micro_batch_size`` carries the trace tag
    (FakeJobs never train, the field is free); the mesh encodes the gang."""
    fsdp = min(gang, 4)
    return TPUTrainConfig(
        model_name="gpt-tiny",
        mesh=MeshConfig(data=gang // fsdp, fsdp=fsdp),
        micro_batch_size=tag,
        seq_len=32,
        precision="fp32",
        total_steps=10,
        activation_checkpointing=False,
        checkpoint_dir=f"/tmp/sched_sim/{tag}",  # preemptibility flag only
    )


# (priority, gang devices, duration s, per-device HBM GiB) per trace job.
# Healthy mock chips have 9.6 GiB free, so two 5 GiB jobs cannot share a
# chip — the reservation ledger must spread or serialise them.
_TRACE: list[tuple[JobPriority, int, float, float]] = [
    (JobPriority.NORMAL, 4, 0.50, 2.0),
    (JobPriority.LOW, 2, 0.70, 5.0),
    (JobPriority.NORMAL, 1, 0.30, 1.0),
    (JobPriority.LOW, 4, 0.60, 3.0),
    (JobPriority.HIGH, 2, 0.40, 2.0),
    (JobPriority.NORMAL, 2, 0.50, 5.0),
    (JobPriority.LOW, 1, 0.80, 1.5),
    (JobPriority.NORMAL, 4, 0.40, 2.5),
    (JobPriority.HIGH, 1, 0.30, 1.0),
    (JobPriority.LOW, 2, 0.60, 4.0),
    (JobPriority.NORMAL, 1, 0.50, 2.0),
    (JobPriority.LOW, 4, 0.70, 3.0),
    (JobPriority.NORMAL, 2, 0.40, 1.5),
    (JobPriority.HIGH, 4, 0.50, 2.0),
    (JobPriority.LOW, 1, 0.30, 1.0),
    (JobPriority.NORMAL, 2, 0.60, 2.5),
    (JobPriority.LOW, 2, 0.50, 3.5),
    (JobPriority.NORMAL, 1, 0.40, 1.0),
    (JobPriority.LOW, 4, 0.60, 2.0),
    (JobPriority.NORMAL, 2, 0.50, 1.5),
]
_CRITICAL_LATECOMER = (JobPriority.CRITICAL, 4, 0.60, 2.0)


def run_trace(max_concurrent_jobs: int = 3) -> dict:
    """Phase A. Returns the measured trace metrics vs the serial baseline."""
    # Fresh process-wide ledger: the scheduler's submit/finish hooks track
    # and finalize every submission's trace through it, so Phase A gets a
    # real wall-clock decomposition for free (FakeJobs record no attempt
    # spans — queue wait comes from submit events + admission spans, the
    # rest of the root window counts productive).
    ledger = GoodputLedger()
    set_ledger(ledger)
    progress: dict[str, float] = {}
    durations: dict[int, float] = {}
    hbm_by_tag: dict[int, float] = {}

    def factory(sub):
        return FakeJob(sub, durations[sub.config.micro_batch_size], progress)

    def estimate(cfg, n_avail):
        # Trace jobs carry their HBM demand out-of-band (keyed by tag);
        # everything else about the estimate mirrors the analytic plane.
        gib = hbm_by_tag[cfg.micro_batch_size]
        return HBMEstimate(
            model_name=cfg.model_name, gang_devices=gang_size(cfg, n_avail),
            params_gib=gib, grads_gib=0.0, opt_gib=0.0, working_gib=0.0,
            activations_gib=0.0, logits_gib=0.0, device_total_gib=gib,
            host_gib=0.0,
        )

    sched = FleetScheduler(
        max_concurrent_jobs=max_concurrent_jobs,
        fleet_fn=TPUManager.get_mock_fleet,
        job_factory=factory,
        estimate_fn=estimate,
        backfill_depth=4,
        poll_interval_s=0.02,
    )

    t0 = time.time()
    subs = []
    for i, (prio, gang, dur, gib) in enumerate(_TRACE):
        tag = i + 1
        durations[tag] = dur
        hbm_by_tag[tag] = gib
        subs.append(sched.submit(_trace_config(tag, gang), priority=prio))

    # The unplaceable head: gang 8 > 7 healthy chips, HIGH priority so it
    # sits at the front of the queue and backfill must route around it.
    blocked_tag = len(_TRACE) + 1
    durations[blocked_tag] = 1.0
    hbm_by_tag[blocked_tag] = 1.0
    blocked = sched.submit(
        _trace_config(blocked_tag, gang=8), priority=JobPriority.HIGH
    )

    # Mid-trace CRITICAL arrival → preempts a running lower-priority job.
    time.sleep(0.3)
    prio, gang, dur, gib = _CRITICAL_LATECOMER
    crit_tag = len(_TRACE) + 2
    durations[crit_tag] = dur
    hbm_by_tag[crit_tag] = gib
    crit = sched.submit(_trace_config(crit_tag, gang), priority=prio)

    deadline = time.time() + 120
    while time.time() < deadline:
        open_subs = [
            s for s in subs + [crit]
            if s.state not in (SubmissionState.COMPLETED, SubmissionState.FAILED,
                               SubmissionState.CANCELLED)
        ]
        if not open_subs:
            break
        time.sleep(0.05)
    makespan = time.time() - t0

    # Chip 5 never heals: the gang-8 job is honestly unplaceable — cancel.
    blocked_reason = blocked.last_skip_reason
    sched.cancel(blocked.submission_id)
    stats = sched.stats()
    sched.shutdown()

    finished = [s for s in subs + [crit] if s.state == SubmissionState.COMPLETED]
    assert len(finished) == len(_TRACE) + 1, (
        f"{len(finished)} of {len(_TRACE) + 1} jobs completed; "
        f"states: {[s.state.value for s in subs + [crit]]}"
    )
    work_done = sum(durations[s.config.micro_batch_size] for s in finished)
    waits = [s.wait_s for s in finished if s.wait_s is not None]

    # Serial FIFO baseline (the reference's launcher: one at a time, strict
    # submission order, the unplaceable job refused rather than queued):
    # makespan = sum of durations, each job waits for every prior job.
    serial_durs = [d for (_, _, d, _) in _TRACE] + [_CRITICAL_LATECOMER[2]]
    serial_makespan = sum(serial_durs)
    acc, serial_waits = 0.0, []
    for d in serial_durs:
        serial_waits.append(acc)
        acc += d

    crit_progress = progress.get(crit.submission_id, 0.0)
    preempt_victims = [s for s in subs if s.preemptions > 0]
    gp = ledger.snapshot()
    return {
        "jobs": len(_TRACE) + 1,
        "slots": max_concurrent_jobs,
        "healthy_chips": 7,
        "makespan_s": round(makespan, 2),
        "serial_makespan_s": round(serial_makespan, 2),
        "speedup_vs_serial": round(serial_makespan / makespan, 2),
        "mean_wait_s": round(sum(waits) / len(waits), 3) if waits else 0.0,
        "serial_mean_wait_s": round(sum(serial_waits) / len(serial_waits), 3),
        "goodput_work_s_per_wall_s": round(work_done / makespan, 2),
        "serial_goodput": 1.0,
        "preemptions": stats["preemptions_total"],
        "requeues": stats["requeues_total"],
        "preempted_jobs_completed": all(
            s.state == SubmissionState.COMPLETED for s in preempt_victims
        ),
        "zero_lost_work": all(
            abs(progress[s.submission_id]
                - durations[s.config.micro_batch_size]) < 1e-6
            for s in preempt_victims
        ),
        "critical_completed": crit.state == SubmissionState.COMPLETED,
        "critical_work_s": round(crit_progress, 2),
        "gang8_skip_reason": blocked_reason,
        "gang8_final_state": blocked.state.value,
        "goodput_ledger": {
            "categories_s": {
                c: v for c, v in gp["categories"].items() if v > 0
            },
            "goodput_fraction": gp["goodput_fraction"],
            "traces_accounted": gp["traces_accounted"],
            "invariant_violations": gp["invariant_violations"],
        },
    }


# ---------------------------------------------------------------------------
# Phase C: warm-admission virtual lane (no threads, no sleeps — the twin's
# single-slot queue over a seeded job list, priced through a real
# CompileCacheIndex).
# ---------------------------------------------------------------------------

SIM_COLD_COMPILE_S = 15.0  # first compile of a layout (virtual seconds)
SIM_WARM_COMPILE_S = 1.5   # persistent-cache hit on a layout already seen


def _admission_lane(
    jobs: list[tuple[str, float]], prefer_warm: bool
) -> dict:
    """Cache-aware admission A/B leg — one slot, compile + work per job;
    the lane itself lives in :func:`tpu_engine.twin.warm_admission_lane`."""
    return warm_admission_lane(
        jobs, prefer_warm,
        cold_compile_s=SIM_COLD_COMPILE_S,
        warm_compile_s=SIM_WARM_COMPILE_S,
    )


def run_warm_admission(seed: int = 0, n_jobs: int = 16) -> dict:
    """Phase C. Same seeded job list, FIFO vs warm-preferring admission."""
    rng = random.Random(seed)
    layouts = [f"sim|data{g}xfsdp2" for g in (1, 2, 4)]
    jobs = [
        (rng.choice(layouts), round(rng.uniform(4.0, 12.0), 2))
        for _ in range(n_jobs)
    ]
    fifo = _admission_lane(jobs, prefer_warm=False)
    warm = _admission_lane(jobs, prefer_warm=True)
    return {
        "seed": seed,
        "jobs": n_jobs,
        "layouts": len(layouts),
        "cold_compile_s": SIM_COLD_COMPILE_S,
        "warm_compile_s": SIM_WARM_COMPILE_S,
        "fifo": fifo,
        "warm_preferring": warm,
        "mean_wait_fifo_s": fifo["mean_wait_s"],
        "mean_wait_warm_s": warm["mean_wait_s"],
        "wait_reduction_pct": round(
            100.0 * (1.0 - warm["mean_wait_s"] / fifo["mean_wait_s"]), 2
        ) if fifo["mean_wait_s"] else 0.0,
    }


# ---------------------------------------------------------------------------
# Phase B: real gpt-tiny checkpoint-preempt-requeue round trip.
# ---------------------------------------------------------------------------


def run_preempt_resume(low_steps: int = 40, high_steps: int = 5) -> dict:
    """Phase B. Returns the round-trip facts; asserts zero lost steps."""
    with tempfile.TemporaryDirectory(prefix="sched_sim_") as root:
        cfg = dict(
            model_name="gpt-tiny",
            mesh=MeshConfig(data=1, fsdp=1),
            micro_batch_size=1,
            seq_len=32,
            precision="fp32",
            activation_checkpointing=False,
            warmup_steps=1,
            # Interval beyond the horizon: ONLY the preemption emergency
            # save can persist progress — if resume works, it worked.
            checkpoint_interval_steps=1000,
        )
        sched = FleetScheduler(
            max_concurrent_jobs=1, checkpoint_root=root, poll_interval_s=0.05
        )
        try:
            import jax.numpy as jnp

            def slow_batch(step: int):
                # gpt-tiny steps take ~2 ms on CPU once compiled — the whole
                # 40-step run would outrace the preemption. Throttle the LOW
                # job's input pipeline so the preempt lands mid-run.
                time.sleep(0.02)
                return jnp.zeros((1, 1, cfg["seq_len"]), jnp.int32)

            low = sched.submit(
                TPUTrainConfig(total_steps=low_steps, **cfg),
                priority=JobPriority.LOW,
                job_kwargs={"data_fn": slow_batch},
            )
            deadline = time.time() + 300
            while time.time() < deadline:
                if low.job is not None and low.job.current_step >= 3:
                    break
                time.sleep(0.1)
            assert low.job is not None and low.job.current_step >= 3, (
                "low-priority job never got going"
            )

            high = sched.submit(
                TPUTrainConfig(total_steps=high_steps, **cfg),
                priority=JobPriority.HIGH,
            )
            high = sched.wait(high.submission_id, timeout=300)
            assert high.state == SubmissionState.COMPLETED, high.describe()

            low = sched.wait(low.submission_id, timeout=300)
            assert low.state == SubmissionState.COMPLETED, low.describe()
            assert low.preemptions == 1 and low.attempts == 2, low.describe()
            saved_step = low.job.resumed_from_step
            assert saved_step is not None and saved_step >= 3
            assert low.job.current_step == low_steps
            return {
                "low_total_steps": low_steps,
                "high_total_steps": high_steps,
                "preempted_at_step": saved_step,
                "resumed_from_step": saved_step,
                "zero_lost_steps": True,
                "low_attempts": low.attempts,
                "low_preemptions": low.preemptions,
                "high_wait_s": round(high.wait_s or 0.0, 2),
                "stats": sched.stats(),
            }
        finally:
            sched.shutdown()


def main() -> None:
    trace = run_trace()
    print(json.dumps({"phase": "trace", **trace}, indent=2))
    warm = run_warm_admission()
    print(json.dumps({"phase": "warm_admission", **warm}, indent=2))
    roundtrip = run_preempt_resume()
    print(json.dumps({"phase": "preempt_resume", **roundtrip}, indent=2))
    ok = (
        trace["speedup_vs_serial"] >= 1.0
        and trace["zero_lost_work"]
        and roundtrip["zero_lost_steps"]
        and warm["mean_wait_warm_s"] < warm["mean_wait_fifo_s"]
    )
    print(json.dumps({
        "metric": "scheduler_goodput_vs_serial_fifo",
        "value": trace["goodput_work_s_per_wall_s"],
        "unit": "work-seconds per wall-second (serial FIFO = 1.0)",
        "speedup_vs_serial": trace["speedup_vs_serial"],
        "zero_lost_steps": roundtrip["zero_lost_steps"],
        "ok": ok,
    }))
    print(json.dumps({
        "metric": "scheduler_warm_admission_wait",
        "value": warm["wait_reduction_pct"],
        "unit": "% mean-wait reduction, warm-preferring vs FIFO admission",
        "mean_wait_fifo_s": warm["mean_wait_fifo_s"],
        "mean_wait_warm_s": warm["mean_wait_warm_s"],
        "ok": ok,
    }))
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
