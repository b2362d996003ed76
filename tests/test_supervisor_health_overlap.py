"""The supervisor's live fleet sample runs between a step's dispatch and the
blocking read of its metrics (under the device's step), and the verdict stays
after the read: where the sample's time is booked, the order of an iteration,
the steps that poll, the self-heal latency (both halves), and the cases that
take no sample at all.
"""

import threading
import time

import jax
import jax.profiler
import pytest

from tpu_engine import faults
from tpu_engine.faults import FaultInjector, FaultKind, FaultPlan, FaultSpec
from tpu_engine.mesh_runtime import MeshConfig
from tpu_engine.sharding import Precision, TPUTrainConfig
from tpu_engine.supervisor import JobStatus, TrainingJob
from tpu_engine.tpu_manager import TPUDevice, TPUFleetStatus, TPUHealthStatus
from tpu_engine.train import build_train_program

P = "tpu_engine.supervisor."


@pytest.fixture(autouse=True)
def _no_process_injector():
    faults.clear_active()
    yield
    faults.clear_active()


def _cfg(**kw) -> TPUTrainConfig:
    base = dict(
        model_name="gpt-tiny", mesh=MeshConfig(data=2, fsdp=4), micro_batch_size=1,
        gradient_accumulation_steps=1, seq_len=32, precision=Precision.FP32,
        total_steps=1000, activation_checkpointing=False, warmup_steps=2,
    )
    base.update(kw)
    return TPUTrainConfig(**base)


@pytest.fixture(scope="module")
def program():
    """One compiled program for every job of this file (each job draws its
    own state from it)."""
    return build_train_program(_cfg())


def _fleet(critical=()) -> TPUFleetStatus:
    return TPUFleetStatus(devices=[
        TPUDevice(index=i, health_status=(
            TPUHealthStatus.CRITICAL if i in critical else TPUHealthStatus.HEALTHY))
        for i in range(len(jax.devices()))
    ])


def _run(program, steps, pending_preemption=None, **kw) -> TrainingJob:
    job = TrainingJob("overlap", _cfg(), program=program, max_steps=steps,
                      hetero_detection=False, **kw)
    job.preemption_reason = pending_preemption
    job.start()
    job.join(timeout=300)
    assert not job.is_alive
    return job


class Events:
    """One ordered log of an iteration's seams: the phase clock's annotations
    (``jax.profiler.TraceAnnotation`` replaced), ``fleet_fn`` and the
    injector's ``observe_step`` / ``chip_overlay``."""

    def __init__(self, monkeypatch, on=None):
        self.log = []
        events, on = self, on or (lambda *a: None)

        class Annotation:
            def __init__(self, name, **ids):
                self.name, self.ids = name, ids

            def note(self, what):
                if self.name.startswith(P) and threading.current_thread().name.startswith("job-"):
                    event = (self.name[len(P):], what, self.ids.get("step"))
                    events.log.append(event)
                    on(*event)

            def __enter__(self):
                self.note("enter")

            def __exit__(self, *exc):
                self.note("exit")

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)

    def fleet_fn(self, fleet=_fleet):
        def fn():
            self.log.append(("fleet_fn", "call", None))
            return fleet()
        return fn

    def injector(self, specs=()):
        events = self

        class Logged(FaultInjector):
            def observe_step(self, step):
                events.log.append(("observe_step", "call", step))
                super().observe_step(step)

            def chip_overlay(self):
                events.log.append(("verdict", "call", None))
                return super().chip_overlay()

        inj = Logged(FaultPlan(specs=list(specs)))
        inj.arm()
        return inj

    def iterations(self):
        """The log cut at every ``other`` annotation's entry (an iteration's
        begin), names only, the whole-iteration annotation left out."""
        out = []
        for name, what, _ in self.log:
            if name == "other":
                if what == "enter":
                    out.append([])
                continue
            out[-1].append(name if what == "call" else f"{name}:{what}")
        return out


# -- (a) where the sample's time is booked ------------------------------------


def test_a_slow_sample_is_device_wait_not_health(program):
    def slow_fleet():
        time.sleep(0.05)
        return _fleet()

    job = _run(program, 6, fleet_fn=slow_fleet, self_heal=True)
    assert job.status == JobStatus.COMPLETED, job.error
    d = job.describe()
    phases = d["profile"]["phases"]
    assert d["profile"]["steps_seen"] == 6
    assert 0 < phases["health"]["p50_ms"] < 10  # entered every iteration, and short
    assert job.profiler.last_step()[0]["health"] > 0
    assert phases["device"]["p50_ms"] >= 50
    assert d["health_samples_total"] == 6
    assert d["health_sample_ms"]["p50_ms"] >= 50
    assert d["health_sample_ms"]["p95_ms"] >= d["health_sample_ms"]["p50_ms"]
    # Every phase but ``device`` is what the chip would wait for: not the sample.
    assert sum(v["p50_ms"] for p, v in phases.items() if p != "device") < 50


# -- (b) the order of an iteration, and the steps that poll --------------------

ITERATION = ["data:enter", "data:exit", "dispatch:enter", "dispatch:exit", "device:enter",
             "health_sample:enter", "fleet_fn", "health_sample:exit", "device:exit",
             "health:enter", "observe_step", "verdict", "health:exit"]
SAMPLE = ["health_sample:enter", "fleet_fn", "health_sample:exit"]


@pytest.mark.parametrize("interval", [1, 3])
def test_b_dispatch_then_sample_then_read_then_verdict(program, monkeypatch, interval):
    ev = Events(monkeypatch)
    job = _run(program, 7, fleet_fn=ev.fleet_fn(), fault_injector=ev.injector(),
               self_heal=True, health_check_interval_steps=interval)
    assert job.status == JobStatus.COMPLETED, job.error
    its = [[e for e in it if e.split(":")[0] not in ("anomaly", "monitor", "checkpoint")]
           for it in ev.iterations()]
    assert len(its) == 7
    polled = [s for s in range(1, 8) if s % interval == 0]  # the steps today's check polls
    quiet = [e for e in ITERATION if e not in SAMPLE and e != "verdict"]
    for step, it in enumerate(its, start=1):
        assert it == (ITERATION if step in polled else quiet), (step, it)
    # The sample's annotation carries the iteration's id, as its phases do.
    assert [s for n, w, s in ev.log if n == "health_sample" and w == "enter"] == [s - 1 for s in polled]
    assert [s for n, w, s in ev.log if n == "observe_step"] == list(range(1, 8))
    assert job.describe()["health_samples_total"] == len(polled)


# -- (c) the self-heal latency, both halves ------------------------------------


def _detected(job):
    assert job.status == JobStatus.PREEMPTED, (job.status, job.error)
    kinds = [(e["kind"], e["step"]) for e in job.recovery_events]
    assert kinds[0][0] == "detected"
    return kinds[0][1]


@pytest.mark.parametrize("k", [1, 3])
def test_c_critical_from_the_kth_sample_heals_at_step_k(program, k):
    calls = []

    def fleet_fn():
        calls.append(1)
        return _fleet(critical={2} if len(calls) >= k else ())

    job = _run(program, 8, fleet_fn=fleet_fn, self_heal=True)
    assert _detected(job) == k
    assert job.current_step == k and job.unhealthy_devices == [2]
    assert job.describe()["health_samples_total"] == k


@pytest.mark.parametrize("moment, heals_at", [
    (("dispatch", "enter"), 3),       # the chip is bad when step 3 is dispatched: same step
    (("health_sample", "exit"), 4),   # it turns bad while step 3 runs: the next sample sees it
])
def test_c_a_fault_at_dispatch_heals_that_step_one_during_the_step_the_next(
        program, monkeypatch, moment, heals_at):
    chip = {"critical": False}

    def on(name, what, it):
        if (name, what) == moment and it == 2:  # the iteration that reaches step 3
            chip["critical"] = True

    ev = Events(monkeypatch, on=on)
    job = _run(program, 8, self_heal=True,
               fleet_fn=ev.fleet_fn(lambda: _fleet(critical={5} if chip["critical"] else ())))
    assert _detected(job) == heals_at
    assert job.unhealthy_devices == [5]


@pytest.mark.parametrize("with_fleet_fn", [True, False])
def test_c_an_injected_chip_fault_heals_at_the_step_it_was_injected_for(
        program, monkeypatch, with_fleet_fn):
    ev = Events(monkeypatch)
    inj = ev.injector([FaultSpec(kind=FaultKind.CHIP_UNHEALTHY, at_step=3, device_index=1)])
    job = _run(program, 8, fault_injector=inj, self_heal=True,
               fleet_fn=ev.fleet_fn() if with_fleet_fn else None)
    assert _detected(job) == 3
    assert job.unhealthy_devices == [1] and job.current_step == 3
    kinds = [(e.kind, e.step) for e in inj.events]
    assert kinds.index(("chip-unhealthy", 3)) < kinds.index(("recovery:detected", 3))
    assert job.describe()["health_samples_total"] == (3 if with_fleet_fn else 0)


# -- (d) where nothing polled, nothing is sampled -------------------------------


@pytest.mark.parametrize("case", ["self_heal_off", "no_fleet_fn", "preemption_pending"])
def test_d_no_sample_where_today_nothing_polls(program, case):
    calls = []

    def fleet_fn():
        calls.append(1)
        return _fleet(critical={0})

    job = _run(program, 4, self_heal=case != "self_heal_off",
               fleet_fn=None if case == "no_fleet_fn" else fleet_fn,
               pending_preemption="drain requested" if case == "preemption_pending" else None)
    assert job.status == (JobStatus.PREEMPTED if case == "preemption_pending" else JobStatus.COMPLETED), job.error
    assert job.current_step == 4 and not job.recovery_events
    d = job.describe()
    assert calls == [] and d["health_samples_total"] == 0
    assert d["health_sample_ms"] == {"mean_ms": 0.0, "p50_ms": 0.0, "p95_ms": 0.0}
    assert d["profile"]["phases"]["health"]["p50_ms"] > 0  # the phase is entered all the same


# -- (e) a fleet view that cannot be had ---------------------------------------


def test_e_a_fleet_fn_that_raises_takes_the_loop_nowhere(program):
    def broken():
        raise ConnectionError("metrics endpoint gone")

    job = _run(program, 5, fleet_fn=broken, self_heal=True)
    assert job.status == JobStatus.COMPLETED, job.error
    assert job.current_step == 5 and not job.recovery_events and job.recovery_state is None
    assert job.describe()["health_samples_total"] == 5
