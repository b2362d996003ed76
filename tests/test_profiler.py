"""Profiler subsystem: step breakdown stats, MFU accounting, trace session,
and the /api/v1/profile routes."""

import time

import pytest

from tpu_engine.profiler import (
    PEAK_FLOPS_BF16,
    StepProfiler,
    TraceSession,
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
