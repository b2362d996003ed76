"""Live telemetry stack: libtpu SDK parsing, derived duty cycle, overlay
merge into the fleet manager, health thresholds firing on the live schema.

The fake ``tpumonitoring`` module speaks the exact string formats documented
by ``libtpu.sdk.tpumonitoring.get_metric(name).description()`` (captured on
a real v5e host — see tpu_engine/telemetry.py module docstring), so these
tests exercise the same parse path production hits.
"""

import time

import pytest

from tpu_engine import telemetry
from tpu_engine.telemetry import (
    DerivedDutySource,
    LibtpuSdkSource,
    parse_float_list,
    parse_indexed_scores,
    parse_link_scores,
)
from tpu_engine.tpu_manager import TPUHealthStatus, TPUManager


@pytest.fixture(autouse=True)
def _restore_sources():
    yield
    telemetry.set_sources(None)
    telemetry.derived_duty().reset()


# -- parsers (documented formats) -------------------------------------------


def test_parse_float_list_documented_format():
    assert parse_float_list(["0.00", "20.00", "0.00", "0.00"]) == [0.0, 20.0, 0.0, 0.0]


def test_parse_float_list_tolerates_indexed_entries():
    assert parse_float_list(["0: 12.5", "1: 37.5", "junk"]) == [12.5, 37.5]


def test_parse_throttle_scores_documented_format():
    # "['0-0', '1-1', '2-0', '3-0']" — chip 1 throttled by 10%.
    assert parse_indexed_scores(["0-0", "1-1", "2-0", "3-0"]) == {0: 0, 1: 1, 2: 0, 3: 0}


def test_parse_ici_links_documented_format():
    links = parse_link_scores(["tray1.chip3.ici0.int: 0", "tray1.chip3.ici1.int: 10"])
    assert links == [("tray1.chip3.ici0.int", 0), ("tray1.chip3.ici1.int", 10)]


def test_ici_link_alert_severity_bands():
    alerts = telemetry.ici_link_alerts(
        [("a", 0), ("b", 3), ("c", 7), ("d", 10)]
    )
    assert len(alerts) == 3  # score 0 is healthy, no alert
    assert "transient" in alerts[0] and "b" in alerts[0]
    assert "persistent" in alerts[1] and "c" in alerts[1]
    assert alerts[2].startswith("CRITICAL") and "d" in alerts[2]


# -- libtpu SDK source -------------------------------------------------------


class FakeMetric:
    def __init__(self, data):
        self._data = data

    def data(self):
        return self._data


class FakeMonitoring:
    """Stand-in for libtpu.sdk.tpumonitoring with the documented data shapes."""

    def __init__(self, metrics):
        self.metrics = metrics

    def list_supported_metrics(self):
        return list(self.metrics)

    def get_metric(self, name):
        return FakeMetric(self.metrics[name])


def _fake_monitoring_4chip():
    gib = 2**30
    return FakeMonitoring(
        {
            "duty_cycle_pct": ["62.00", "97.50", "12.00", "0.00"],
            # Two cores per chip — per-chip means: 55, 90, 10, 0.
            "tensorcore_util": [
                "50.00", "60.00", "88.00", "92.00", "10.00", "10.00", "0.00", "0.00",
            ],
            "hbm_capacity_total": [str(16 * gib)] * 4,
            "hbm_capacity_usage": [str(4 * gib), str(14 * gib), str(gib), "0"],
            "tpu_throttle_score": ["0-0", "1-7", "2-1", "3-0"],
            "ici_link_health": ["tray0.chip1.ici0.int: 10", "tray0.chip2.ici1.int: 0"],
        }
    )


def test_libtpu_sdk_source_sample():
    src = LibtpuSdkSource(monitoring=_fake_monitoring_4chip())
    snap = src.sample(4)
    assert snap is not None and snap.source == "libtpu_sdk"
    assert [c["duty_cycle_pct"] for c in snap.per_chip] == [62.0, 97.5, 12.0, 0.0]
    assert [c["tensorcore_util_pct"] for c in snap.per_chip] == [55.0, 90.0, 10.0, 0.0]
    assert snap.per_chip[1]["hbm_used_gb"] == 14.0
    assert snap.per_chip[1]["throttle_score"] == 7
    assert snap.ici_links == [("tray0.chip1.ici0.int", 10), ("tray0.chip2.ici1.int", 0)]


def test_libtpu_sdk_source_empty_data_is_none():
    # SDK importable, every metric empty (libtpu not driving the chips).
    empty = FakeMonitoring({n: [] for n in _fake_monitoring_4chip().metrics})
    assert LibtpuSdkSource(monitoring=empty).sample(4) is None


def test_libtpu_sdk_source_missing_module_is_none():
    src = LibtpuSdkSource()
    src._probed, src._monitoring = True, None
    assert src.sample(4) is None


# -- derived duty source -----------------------------------------------------


def test_derived_duty_from_step_timings():
    src = DerivedDutySource()
    for _ in range(10):
        src.observe(device_s=0.08, wall_s=0.1)
    snap = src.sample(2)
    assert snap is not None
    assert [c["duty_cycle_pct"] for c in snap.per_chip] == [80.0, 80.0]


def test_derived_duty_expires_when_idle():
    src = DerivedDutySource(max_age_s=0.05)
    src.observe(device_s=0.5, wall_s=1.0)
    assert src.sample(1) is not None
    time.sleep(0.08)
    assert src.sample(1) is None


def test_derived_duty_empty_before_any_step():
    assert DerivedDutySource().sample(1) is None


def test_derived_duty_scoped_to_job_devices():
    """A job driving a subset of the host's chips must not stamp its duty
    cycle onto the idle chips (round-2 review finding)."""
    import jax

    src = DerivedDutySource()
    first_four = [int(d.id) for d in jax.devices()[:4]]
    src.observe(device_s=0.8, wall_s=1.0, device_ids=first_four)
    snap = src.sample(8)
    assert [bool(c) for c in snap.per_chip] == [True] * 4 + [False] * 4
    assert snap.per_chip[0]["duty_cycle_pct"] == 80.0


def test_derived_duty_concurrent_jobs_do_not_blend():
    """Two jobs on disjoint chip subsets keep separate duty readings
    (round-2 review finding: a shared window would blend their timings)."""
    import jax

    src = DerivedDutySource()
    ids = [int(d.id) for d in jax.devices()]
    src.observe(device_s=0.9, wall_s=1.0, device_ids=ids[:4])   # busy job
    src.observe(device_s=0.1, wall_s=1.0, device_ids=ids[4:8])  # idle-ish job
    snap = src.sample(8)
    assert [c.get("duty_cycle_pct") for c in snap.per_chip] == (
        [90.0] * 4 + [10.0] * 4
    )


# -- overlay merge + live-path health ---------------------------------------


def test_overlay_priority_first_source_wins():
    libtpu = LibtpuSdkSource(monitoring=_fake_monitoring_4chip())
    derived = DerivedDutySource()
    derived.observe(0.5, 1.0)  # 50% — must NOT override libtpu's numbers
    telemetry.set_sources([libtpu, derived])
    overlay = telemetry.sample_overlay(4)
    assert overlay.per_chip[0]["duty_cycle_pct"] == 62.0
    assert overlay.sources == ["libtpu_sdk"]


def test_overlay_falls_back_to_derived():
    telemetry.set_sources([LibtpuSdkSource(monitoring=FakeMonitoring({}))])
    derived = DerivedDutySource()
    derived.observe(0.9, 1.0)
    telemetry.set_sources([LibtpuSdkSource(monitoring=FakeMonitoring({})), derived])
    overlay = telemetry.sample_overlay(2)
    assert overlay.sources == ["derived"]
    assert overlay.per_chip[0]["duty_cycle_pct"] == 90.0


def test_live_fleet_health_fires_from_libtpu_schema():
    """The VERDICT gap: thresholds must fire on the LIVE path, fed by the
    telemetry stack — not only on injected snapshots."""
    telemetry.set_sources([LibtpuSdkSource(monitoring=_fake_monitoring_4chip())])
    fleet = TPUManager().get_fleet_status()  # 8 CPU test devices
    assert fleet.telemetry_sources == ["libtpu_sdk"]
    # chip 1: duty 97.5 >= 95 (warning) AND throttle 7 >= 6 (critical).
    chip1 = fleet.devices[1]
    assert chip1.duty_cycle_pct == 97.5
    assert chip1.throttle_score == 7
    assert chip1.health_status == TPUHealthStatus.CRITICAL
    assert any("throttled by 70%" in a for a in chip1.alerts)
    assert any("duty cycle" in a for a in chip1.alerts)
    # chip 2: throttle 1 → warning only.
    assert fleet.devices[2].health_status == TPUHealthStatus.WARNING
    # ICI link problems surface as fleet alerts.
    assert any("ICI link tray0.chip1.ici0.int unusable" in a for a in fleet.fleet_alerts)
    assert fleet.ici_links[0] == ("tray0.chip1.ici0.int", 10)


def test_live_fleet_derived_duty_when_sdk_unreachable():
    """The SDK plane has no data: only the engine-derived source does."""
    derived = DerivedDutySource()
    for _ in range(5):
        derived.observe(device_s=0.45, wall_s=0.5)
    telemetry.set_sources([derived])
    fleet = TPUManager().get_fleet_status()
    assert fleet.telemetry_sources == ["derived"]
    assert all(d.duty_cycle_pct == 90.0 for d in fleet.devices)
    assert fleet.average_duty_cycle_pct == 90.0


def test_supervisor_feed_helper():
    telemetry.observe_step(device_s=0.3, wall_s=0.4)
    snap = telemetry.derived_duty().sample(1)
    assert snap is not None and snap.per_chip[0]["duty_cycle_pct"] == 75.0


def test_injected_metrics_bypass_overlay():
    # Injected snapshots are the canned-telemetry seam; live sources must
    # not leak into them.
    derived = DerivedDutySource()
    derived.observe(0.9, 1.0)
    telemetry.set_sources([derived])
    fleet = TPUManager().get_fleet_status(
        metrics=[{"index": 0, "hbm_total_gb": 16.0, "hbm_used_gb": 1.0}]
    )
    assert fleet.telemetry_sources == []
    assert fleet.devices[0].duty_cycle_pct is None


# -- tpu-info CLI fallback source (SURVEY §2.2; reference nvidia-smi parse
# seam, gpu_manager.py:100-117) ---------------------------------------------

_TPU_INFO_OUTPUT = """\
TPU Chips
┏━━━━━━━━━━━━━┳━━━━━━━━━━━━━┳━━━━━━━━━┳━━━━━┓
┃ Chip        ┃ Type        ┃ Devices ┃ PID ┃
┡━━━━━━━━━━━━━╇━━━━━━━━━━━━━╇━━━━━━━━━╇━━━━━┩
│ /dev/accel0 │ TPU v5 lite │ 1       │ 777 │
│ /dev/accel1 │ TPU v5 lite │ 1       │ 777 │
└─────────────┴─────────────┴─────────┴─────┘
TPU Runtime Utilization
┏━━━━━━━━┳━━━━━━━━━━━━━━━━━━━━━━━┳━━━━━━━━━━━━┓
┃ Device ┃ Memory usage          ┃ Duty cycle ┃
┡━━━━━━━━╇━━━━━━━━━━━━━━━━━━━━━━━╇━━━━━━━━━━━━┩
│ 0      │ 1.50 GiB / 15.75 GiB  │     12.00% │
│ 1      │ 14.20 GiB / 15.75 GiB │     97.50% │
└────────┴───────────────────────┴────────────┘
TensorCore Utilization
┏━━━━━━━━━┳━━━━━━━━━━━━━━━━━━━━━━━━┓
┃ Chip ID ┃ TensorCore Utilization ┃
┡━━━━━━━━━╇━━━━━━━━━━━━━━━━━━━━━━━━┩
│ 0       │ 34.20%                 │
│ 1       │ 88.00%                 │
└─────────┴────────────────────────┘
"""


def test_tpu_info_cli_source_parses_canned_output():
    src = telemetry.TpuInfoCliSource(runner=lambda: _TPU_INFO_OUTPUT)
    snap = src.sample(2)
    assert snap is not None and snap.source == "tpu_info_cli"
    assert snap.per_chip[0] == {
        "hbm_used_gb": 1.5, "hbm_total_gb": 15.75,
        "duty_cycle_pct": 12.0, "tensorcore_util_pct": 34.2,
        "holder_pid": 777,
    }
    assert snap.per_chip[1]["duty_cycle_pct"] == 97.5
    assert snap.per_chip[1]["hbm_used_gb"] == 14.2
    # Chips-table PID column (the process HOLDING each chip — possibly one
    # this control plane never launched; reference gpu_manager.py:174-184).
    assert snap.per_chip[1]["holder_pid"] == 777


def test_tpu_info_cli_holder_pid_absent_when_cell_empty():
    text = _TPU_INFO_OUTPUT.replace("│ 777 │", "│     │")
    fields = telemetry.TpuInfoCliSource.parse(text)
    assert "holder_pid" not in fields.get(0, {})
    assert fields[0]["hbm_used_gb"] == 1.5  # other tables still parse


def test_tpu_info_cli_source_degrades_to_none():
    assert telemetry.TpuInfoCliSource(runner=lambda: "").sample(2) is None
    assert telemetry.TpuInfoCliSource(runner=lambda: "no tables here").sample(2) is None

    def boom():
        raise RuntimeError("binary exploded")

    assert telemetry.TpuInfoCliSource(runner=boom).sample(2) is None
    # No runner + no binary on PATH → None, never an exception.
    assert telemetry.TpuInfoCliSource(binary="definitely-not-a-binary").sample(2) is None


def test_tpu_info_cli_registered_between_sdk_and_derived():
    names = [type(s).__name__ for s in telemetry.sources()]
    assert names == ["LibtpuSdkSource", "TpuInfoCliSource", "DerivedDutySource"]


def test_overlay_sdk_beats_cli_beats_derived():
    sdk = LibtpuSdkSource(monitoring=FakeMonitoring({"duty_cycle_pct": ["50.00", "60.00"]}))
    cli = telemetry.TpuInfoCliSource(runner=lambda: _TPU_INFO_OUTPUT)
    telemetry.set_sources([sdk, cli, telemetry.derived_duty()])
    overlay = telemetry.sample_overlay(2)
    # SDK wins on duty; CLI fills what the SDK lacks (HBM, tensorcore).
    assert overlay.per_chip[0]["duty_cycle_pct"] == 50.0
    assert overlay.per_chip[0]["tensorcore_util_pct"] == 34.2
    assert overlay.per_chip[0]["hbm_total_gb"] == 15.75
    assert overlay.sources == ["libtpu_sdk", "tpu_info_cli"]


def test_tpu_info_cli_rate_limits_subprocess_invocations(monkeypatch):
    src = telemetry.TpuInfoCliSource(min_interval_s=60.0)
    calls = []

    def fake_invoke():
        calls.append(1)
        return _TPU_INFO_OUTPUT

    monkeypatch.setattr(src, "_invoke", fake_invoke)
    for _ in range(5):
        assert src.sample(2) is not None
    assert len(calls) == 1  # one fork per interval, cached in between
