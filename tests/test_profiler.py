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


def test_step_profiler_phases_and_stats():
    prof = StepProfiler(window=10, tokens_per_step=1000, n_devices=2)
    for _ in range(3):
        prof.begin_step()
        time.sleep(0.01)
        prof.mark("data")
        time.sleep(0.02)
        prof.mark("dispatch")
        time.sleep(0.005)
        prof.mark("device")
        total = prof.end_step()
        assert total >= 0.035

    s = prof.summary()
    assert s["steps_seen"] == 3
    assert s["window"] == 3
    assert s["phases"]["data"]["mean_ms"] == pytest.approx(10, rel=0.8)
    assert s["phases"]["dispatch"]["mean_ms"] > s["phases"]["device"]["mean_ms"]
    # Fractions cover the whole step.
    fracs = sum(s["phases"][p]["fraction"] for p in StepProfiler.PHASES)
    assert fracs == pytest.approx(1.0, abs=0.02)
    # Throughput is derived from mean total.
    assert s["tokens_per_sec"] > 0
    # Both values are rounded to 0.1 independently.
    assert s["tokens_per_sec_per_chip"] == pytest.approx(s["tokens_per_sec"] / 2, abs=0.06)


def test_step_profiler_window_bounded():
    prof = StepProfiler(window=5)
    for _ in range(20):
        prof.begin_step()
        prof.end_step()
    s = prof.summary()
    assert s["steps_seen"] == 20
    assert s["window"] == 5  # deque bounded — no unbounded growth


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
    prof = StepProfiler(window=4, tokens_per_step=1000,
                        flops_per_token=1e6, pipeline_account=acct)
    for _ in range(2):
        prof.begin_step()
        time.sleep(0.005)
        prof.mark("device")
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
