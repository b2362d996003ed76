"""Profiler subsystem: step breakdown stats, MFU accounting, trace session,
and the /api/v1/profile routes."""

import time

import pytest

from tpu_engine import profiler
from tpu_engine.profiler import (
    PEAK_FLOPS_BF16,
    StepProfiler,
    TraceSession,
    ctl_span,
    mfu,
    pipeline_tick_account,
)


# The clock knows no loop: each owner names itself and its phases.
LOOP = dict(loop="supervisor", phases=("data", "dispatch", "device"))


def test_step_profiler_phases_and_stats():
    prof = StepProfiler(**LOOP, window=10, tokens_per_step=1000, n_devices=2)
    for _ in range(4):  # begin-to-begin: four begins close three iterations
        prof.begin_step()
        with prof.phase("data"):
            time.sleep(0.01)
        with prof.phase("dispatch"):
            time.sleep(0.02)
        with prof.phase("device"):
            time.sleep(0.005)
        time.sleep(0.004)  # work under no phase lands in "other"

    s = prof.summary()
    assert s["steps_seen"] == 3
    assert s["window"] == 3
    assert s["total"]["p50_ms"] >= 39
    assert s["phases"]["data"]["mean_ms"] == pytest.approx(10, rel=0.8)
    assert s["phases"]["dispatch"]["mean_ms"] > s["phases"]["device"]["mean_ms"]
    assert s["phases"]["other"]["mean_ms"] >= 3.5
    # Fractions cover the whole step.
    assert prof.phases == ("data", "dispatch", "device", "other")
    fracs = sum(s["phases"][p]["fraction"] for p in prof.phases)
    assert fracs == pytest.approx(1.0, abs=0.02)
    # Throughput is derived from mean total.
    assert s["tokens_per_sec"] > 0
    # Both values are rounded to 0.1 independently.
    assert s["tokens_per_sec_per_chip"] == pytest.approx(s["tokens_per_sec"] / 2, abs=0.06)


def test_phases_and_other_sum_to_the_begin_to_begin_total():
    """A step runs from one begin_step to the next: what follows the last
    phase (bookkeeping, a sleep between calls) is in the total and in
    ``other``, not lost."""
    prof = StepProfiler(loop="batcher", phases=("stage", "emit"))
    t0 = time.perf_counter()
    prof.begin_step()
    with prof.phase("stage"):
        time.sleep(0.004)
    with prof.phase("stage"):  # a phase entered twice accumulates
        time.sleep(0.004)
    time.sleep(0.006)
    assert prof.last_step() is None
    so_far, elapsed = prof.open_step()
    assert so_far["stage"] >= 0.008 and elapsed >= 0.014
    prof.begin_step()
    wall = time.perf_counter() - t0
    phases, total = prof.last_step()
    assert total == pytest.approx(wall, abs=2e-3)
    assert sum(phases.values()) == pytest.approx(total, abs=1e-9)
    assert phases["stage"] >= 0.008 and phases["other"] >= 0.006
    assert prof.end_step() is not None and prof.end_step() is None
    s = prof.summary()
    assert s["steps_seen"] == 2 and set(s["phases"]) == {"stage", "emit", "other"}


def test_a_phase_that_raises_still_closes_and_annotations_nest_in_order(monkeypatch):
    import jax.profiler

    log = []

    class Annotation:
        def __init__(self, name, **ids):
            self.name, self.ids = name, ids

        def __enter__(self):
            log.append(("enter", self.name, self.ids))

        def __exit__(self, *exc):
            log.append(("exit", self.name, self.ids))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    prof = StepProfiler(**LOOP)
    prof.begin_step()
    with prof.phase("data", step=7):
        pass
    with pytest.raises(RuntimeError):
        with prof.phase("device", step=7):
            time.sleep(0.002)
            raise RuntimeError("device read failed")
    with pytest.raises(KeyError):
        prof.phase("no-such-phase").__enter__()
    prof.end_step()
    assert log == [
        ("enter", "tpu_engine.supervisor.other", {}),  # the whole iteration: phases lie inside it
        ("enter", "tpu_engine.supervisor.data", {"step": 7}),
        ("exit", "tpu_engine.supervisor.data", {"step": 7}),
        ("enter", "tpu_engine.supervisor.device", {"step": 7}),
        ("exit", "tpu_engine.supervisor.device", {"step": 7}),
        ("exit", "tpu_engine.supervisor.other", {}),
    ]
    phases, _ = prof.last_step()
    assert phases["device"] >= 0.002


def _spin(seconds):
    """Burn ``seconds`` of this thread's CPU time, however long that takes."""
    t0 = time.thread_time()
    while time.thread_time() - t0 < seconds:
        pass


@pytest.mark.parametrize("work, off_cpu", [(time.sleep, True), (_spin, False)], ids=["sleeps", "spins"])
def test_blocked_is_the_wall_time_a_phase_spent_off_the_cpu(work, off_cpu, monkeypatch):
    """wall - thread CPU: a phase that sleeps 20 ms reads blocked ~ wall, one
    that burns 20 ms of CPU reads blocked = wall - 20 ms (~ 0 on a machine
    that leaves it the CPU); what follows the phases is ``other``'s, and an
    iteration's blocked seconds are its phases' and ``other``'s. The phases
    read the thread's clock in iterations that begin under a profiler session."""
    monkeypatch.setattr(profiler, "_tracing", lambda: True)
    prof = StepProfiler(**LOOP, window=10)
    for _ in range(5):
        prof.begin_step()
        with prof.phase("device"):
            work(0.02)
        time.sleep(0.01)  # under no phase: ``other`` was off the CPU
    prof.end_step()
    on_cpu = [w - b for b, w in zip(prof._blocked["device"], prof._phases["device"])]
    assert min(prof._phases["device"]) >= 0.02
    assert on_cpu == pytest.approx([0.0 if off_cpu else 0.02] * 5, abs=2e-3)
    s = prof.summary()
    device, other = s["phases"]["device"], s["phases"]["other"]
    assert set(device["blocked_ms"]) == {"mean", "p50", "p95"}
    assert other["p50_ms"] >= 10 and other["blocked_ms"]["p50"] >= 0.9 * 10
    assert s["phases"]["data"]["blocked_ms"] == {"mean": 0.0, "p50": 0.0, "p95": 0.0}  # never entered
    for it in range(5):
        parts = sum(prof._blocked[p][it] for p in prof.phases)
        assert prof._totals_blocked[it] == pytest.approx(parts, abs=1e-3)
    for phase in list(s["phases"].values()) + [s["total"]]:
        assert -0.1 <= phase["blocked_ms"]["mean"] <= phase["mean_ms"] + 1e-9


def test_with_no_session_nothing_reads_the_threads_clock(monkeypatch):
    """``time.thread_time`` is a system call: the clock reads it around the
    iteration and every phase of it only in an iteration that began under a
    profiler session, and ``summary()`` holds ``blocked_ms`` only of those."""
    reads = []
    monkeypatch.setattr(profiler, "thread_time", lambda: reads.append(1) or time.thread_time())
    prof = StepProfiler(**LOOP)
    for _ in range(3):
        prof.begin_step()
        with prof.phase("data"):
            pass
        with prof.phase("device"):
            time.sleep(0.01)
    prof.end_step()
    assert not reads
    s = prof.summary()
    assert all("blocked_ms" not in p for p in list(s["phases"].values()) + [s["total"]])
    monkeypatch.setattr(profiler, "_tracing", lambda: True)
    prof.begin_step()  # an iteration that begins under a session
    with prof.phase("device"):
        time.sleep(0.01)
    monkeypatch.setattr(profiler, "_tracing", lambda: False)  # the session ends inside it
    with prof.phase("data"):
        pass
    prof.end_step()
    assert len(reads) == 2 + 2 * 2  # the iteration's two and each phase's
    s = prof.summary()
    assert s["phases"]["device"]["blocked_ms"]["mean"] >= 9
    # Of the four iterations only the traced one is in ``blocked_ms``, and it
    # slept nearly all of its own wall time (the thread's CPU time in it is well
    # under 2 ms however loaded the machine: time descheduled is off the CPU
    # too). Held against that iteration's own total, not the p95 of all four: a
    # loaded machine (six workers) stretches another iteration's 10 ms sleep.
    assert len(prof._totals_blocked) == 1 and len(prof._totals) == 4
    assert s["total"]["blocked_ms"]["mean"] == pytest.approx(prof._totals[-1] * 1e3, abs=2)
    assert prof._totals_blocked[0] <= prof._totals[-1]


def _yardstick(pc=time.perf_counter):
    for _ in range(26):  # 2.0 us on the CPU the budgets were set on, undisturbed
        pc()


def _inside_budget(fn, budget_us, n=5000, batches=60):
    """Whether ``fn`` costs at most ``budget_us`` a call in some batch of
    up to ``batches`` short ones. A machine busy with other work (six test
    workers on eight cores) reads everything high, so a batch also passes
    where ``fn`` costs at most ``budget_us / 2.0`` times what the yardstick
    loop (2.0 us when undisturbed) cost right beside it."""
    def batch(f):
        t0 = time.perf_counter()
        for _ in range(n):
            f()
        return (time.perf_counter() - t0) / n * 1e6

    for _ in range(batches):
        yard, cost = batch(_yardstick), batch(fn)
        if cost <= budget_us or cost / yard <= budget_us / 2.0:
            return True
    return False


def test_with_no_session_a_phase_and_a_ctl_span_stay_inside_their_budget():
    """ISSUE 38: a phase <= 3.5 us, a ``ctl_span`` <= 2 us on the CPU with
    no profiler session (ten phases a dispatch of >= 36 ms is < 0.1 %)."""
    prof = StepProfiler(**LOOP)
    prof.begin_step()

    def phase():
        with prof.phase("device", step=7):
            pass

    def span():
        with ctl_span("test", "budget"):
            pass

    assert _inside_budget(phase, 3.5)
    assert _inside_budget(span, 2.0)
    prof.end_step()


def test_ctl_span_is_nothing_with_no_session_and_an_annotation_under_one(monkeypatch):
    import jax

    none = ctl_span("test", "quiet", fid="req_1")
    assert none is ctl_span("test", "other")  # one shared object: no name built, no clock read
    with none as span:
        span.set_metadata(queued=2)  # nothing to write on
    with pytest.raises(RuntimeError):
        with ctl_span("test", "quiet"):
            raise RuntimeError("a span swallows nothing")
    monkeypatch.setattr(profiler, "_tracing", lambda: True)
    assert isinstance(ctl_span("test", "loud", fid="req_1"), jax.profiler.TraceAnnotation)
    with pytest.raises(RuntimeError):
        with ctl_span("test", "loud") as span:
            span.set_metadata(queued=2)
            raise RuntimeError("nor does the annotation")


def test_step_profiler_window_bounded():
    prof = StepProfiler(**LOOP, window=5)
    for _ in range(21):
        prof.begin_step()
    s = prof.summary()
    assert s["steps_seen"] == 20
    assert s["window"] == 5  # deque bounded — no unbounded growth
    assert all(len(v) == 5 for v in prof._phases.values())


def test_mfu_accounting():
    # On the CPU test mesh there is no known peak → None.
    assert mfu(1e9, 1e4) is None or isinstance(mfu(1e9, 1e4), float)

    # Against a known chip entry the math is exact.
    class FakeDev:
        device_kind = "TPU v5e"

    v = mfu(1e9, 88_650.0, device=FakeDev())  # 88650 tok/s × 1 GF/tok / 197 TF
    assert v == pytest.approx(88_650e9 / PEAK_FLOPS_BF16["v5e"], rel=1e-6)


def test_unknown_tpu_kind_is_an_error_not_a_default():
    """A utilization against a guessed peak is not a measurement: a TPU
    whose device_kind is missing from the table raises; off-TPU there is
    simply no peak."""
    from tpu_engine.profiler import peak_flops_per_chip

    class NewChip:
        platform = "tpu"
        device_kind = "TPU v9 mystery"

    class Cpu:
        platform = "cpu"
        device_kind = "cpu"

    with pytest.raises(ValueError, match="TPU v9 mystery"):
        peak_flops_per_chip(NewChip())
    assert peak_flops_per_chip(Cpu()) is None


def test_pipeline_tick_account():
    # Off the pipelined path there is nothing to account.
    assert pipeline_tick_account("gpipe", 1, 8) is None
    zb = pipeline_tick_account("zb", 4, 16)
    f1b = pipeline_tick_account("1f1b", 4, 16)
    assert 0 < zb["busy_fraction"] <= 1
    assert zb["busy_fraction"] > f1b["busy_fraction"]
    # Growing M amortises the fixed bubble: busy fraction rises.
    assert (
        pipeline_tick_account("zb", 4, 32)["busy_fraction"]
        > zb["busy_fraction"]
    )


def test_bubble_adjusted_mfu_in_summary():
    """With a pipeline account attached the summary exposes the schedule's
    tick/busy accounting, and — when an MFU is computable — divides it by
    the busy fraction so pipelined runs stop being under-reported."""
    acct = pipeline_tick_account("zb", 4, 16)
    prof = StepProfiler(**LOOP, window=4, tokens_per_step=1000,
                        flops_per_token=1e6, pipeline_account=acct)
    for _ in range(2):
        prof.begin_step()
        with prof.phase("device"):
            time.sleep(0.005)
    prof.end_step()
    s = prof.summary()
    pipe = s["pipeline"]
    assert pipe["schedule"] == "zb"
    assert pipe["ticks"] == acct["ticks"]
    assert pipe["busy_fraction"] == pytest.approx(acct["busy_fraction"], abs=1e-4)
    assert pipe["bubble_fraction"] == pytest.approx(1 - pipe["busy_fraction"], abs=1e-3)
    # On the CPU test mesh mfu is None → no adjusted figure either.
    if s.get("mfu") is not None:
        assert s["mfu_bubble_adjusted"] == pytest.approx(
            s["mfu"] / pipe["busy_fraction"], rel=1e-3
        )
    else:
        assert "mfu_bubble_adjusted" not in s


def test_trace_session_lifecycle(tmp_path):
    ts = TraceSession()
    assert ts.status() == {"active": False}
    with pytest.raises(RuntimeError):
        ts.stop()
    info = ts.start(str(tmp_path / "trace"))
    assert info["active"] and ts.active
    with pytest.raises(RuntimeError):
        ts.start(str(tmp_path / "other"))  # one at a time
    out = ts.stop()
    assert out["active"] is False
    assert not ts.active
