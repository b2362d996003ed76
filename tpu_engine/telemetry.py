"""Live chip telemetry sources for the TPU fleet manager.

The reference reads live temperature / utilization / power / process tables
from hardware on every poll by shelling out to ``nvidia-smi``
(``ai_engine/gpu_manager.py:100-117,138-215``). The TPU-native equivalent has
no subprocess parse; telemetry comes from layered in-process sources, merged
in priority order by :func:`sample_overlay`:

1. :class:`LibtpuSdkSource` — the libtpu SDK monitoring API
   (``libtpu.sdk.tpumonitoring``), the same source the ``tpu-info`` CLI
   renders. Supplies per-chip TensorCore duty cycle, per-core TensorCore
   utilization, HBM capacity/usage, the device throttle score (the hardware's
   own thermal/power-throttling signal — TPU metrics expose *throttling*
   rather than raw die temperature), and per-link ICI health.
2. :class:`DerivedDutySource` — duty cycle derived from the engine's own step
   profiler (device-phase wall time / step wall time). The supervisor feeds
   it after every train step, so fleets report a live duty cycle even where
   the libtpu metrics service has no data.

Injected snapshots (``TPUManager.parse_metrics``) bypass this module entirely
— they are the canned-telemetry test seam, parity with the reference's
``parse_xml(xml_str=...)``.

Metric string formats are parsed exactly as documented by
``tpumonitoring.get_metric(name).description()``:

- ``duty_cycle_pct`` / ``tensorcore_util``: ``["0.00", "20.00", ...]``
  (percent per chip / per core);
- ``hbm_capacity_usage`` / ``hbm_capacity_total``: ``["1073741824", ...]``
  (integer bytes per chip);
- ``tpu_throttle_score``: ``["0-0", "1-1", ...]`` (``<chip>-<score>``,
  score 0 = not throttled, 1-10 = throttled by 10-100%);
- ``ici_link_health``: ``["tray1.chip3.ici0.int: 0", ...]`` (``<loc>: <score>``,
  0 healthy, 1-5 transient, 6-9 persistent minor, 10 unusable).
"""

from __future__ import annotations

import re
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Optional, Protocol, Sequence

# ---------------------------------------------------------------------------
# Snapshot / source protocol
# ---------------------------------------------------------------------------


@dataclass
class TelemetrySnapshot:
    """One source's reading: per-chip overlay dicts + fleet-level extras."""

    source: str
    sampled_at: float
    # Overlay fields per chip position (0..n_chips-1). Recognised keys:
    # duty_cycle_pct, tensorcore_util_pct, throttle_score, temperature_c,
    # power_draw_w, power_limit_w, hbm_total_gb, hbm_used_gb.
    per_chip: list[dict[str, Any]] = field(default_factory=list)
    # (location, score) per ICI link, scores per the libtpu scale (0-10).
    ici_links: list[tuple[str, int]] = field(default_factory=list)


class TelemetrySource(Protocol):
    name: str

    def sample(self, n_chips: int) -> Optional[TelemetrySnapshot]: ...


# ---------------------------------------------------------------------------
# Parsers for the documented libtpu metric string formats
# ---------------------------------------------------------------------------


def parse_float_list(data: Sequence[str]) -> list[float]:
    """``["0.00", "20.00"]`` → floats; tolerates ``"<idx>: <val>"`` entries."""
    out: list[float] = []
    for item in data:
        s = str(item).strip()
        if ":" in s:
            s = s.rsplit(":", 1)[1].strip()
        try:
            out.append(float(s))
        except ValueError:
            continue
    return out


def parse_indexed_scores(data: Sequence[str]) -> dict[int, int]:
    """``["0-0", "1-1"]`` → {chip: score}; tolerates ``"<idx>: <score>"``."""
    out: dict[int, int] = {}
    for item in data:
        s = str(item).strip()
        sep = "-" if "-" in s else (":" if ":" in s else None)
        if sep is None:
            continue
        left, _, right = s.rpartition(sep)
        try:
            out[int(left.strip())] = int(float(right.strip()))
        except ValueError:
            continue
    return out


def parse_link_scores(data: Sequence[str]) -> list[tuple[str, int]]:
    """``["tray1.chip3.ici0.int: 0"]`` → [(location, score)]."""
    out: list[tuple[str, int]] = []
    for item in data:
        s = str(item).strip()
        loc, sep, score = s.rpartition(":")
        if not sep:
            continue
        try:
            out.append((loc.strip(), int(float(score.strip()))))
        except ValueError:
            continue
    return out


def _per_chip_from_cores(values: list[float], n_chips: int) -> list[float]:
    """Collapse a per-core list to per-chip means (cores enumerate
    contiguously per chip). Falls back to 1:1 when counts don't divide."""
    if n_chips <= 0 or not values:
        return []
    if len(values) % n_chips == 0:
        k = len(values) // n_chips
        return [sum(values[i * k : (i + 1) * k]) / k for i in range(n_chips)]
    return values[:n_chips]


# ---------------------------------------------------------------------------
# Source: libtpu SDK monitoring
# ---------------------------------------------------------------------------


class LibtpuSdkSource:
    """Reads ``libtpu.sdk.tpumonitoring`` (the ``tpu-info`` data source).

    ``monitoring=`` injects a stand-in module for tests; the default imports
    lazily and degrades to unavailable when libtpu (or its SDK) is absent.
    A sample with no data in any metric returns None — e.g. when the local
    libtpu is not the runtime actually driving the chips.
    """

    name = "libtpu_sdk"

    def __init__(self, monitoring: Any = None):
        self._monitoring = monitoring
        self._probed = monitoring is not None

    def _mod(self) -> Any:
        if not self._probed:
            self._probed = True
            try:
                from libtpu.sdk import tpumonitoring  # type: ignore

                self._monitoring = tpumonitoring
            except Exception:
                self._monitoring = None
        return self._monitoring

    def _data(self, supported: set[str], name: str) -> list[str]:
        if name not in supported:
            return []
        try:
            return list(self._mod().get_metric(name).data())
        except Exception:
            return []

    def sample(self, n_chips: int) -> Optional[TelemetrySnapshot]:
        mod = self._mod()
        if mod is None:
            return None
        try:
            supported = set(mod.list_supported_metrics())
        except Exception:
            return None

        duty = parse_float_list(self._data(supported, "duty_cycle_pct"))
        util = parse_float_list(self._data(supported, "tensorcore_util"))
        hbm_used = parse_float_list(self._data(supported, "hbm_capacity_usage"))
        hbm_total = parse_float_list(self._data(supported, "hbm_capacity_total"))
        throttle = parse_indexed_scores(self._data(supported, "tpu_throttle_score"))
        links = parse_link_scores(self._data(supported, "ici_link_health"))
        if not any((duty, util, hbm_used, hbm_total, throttle, links)):
            return None

        util_per_chip = _per_chip_from_cores(util, n_chips)
        per_chip: list[dict[str, Any]] = []
        for i in range(n_chips):
            entry: dict[str, Any] = {}
            if i < len(duty):
                entry["duty_cycle_pct"] = round(duty[i], 2)
            if i < len(util_per_chip):
                entry["tensorcore_util_pct"] = round(util_per_chip[i], 2)
            if i < len(hbm_total) and hbm_total[i] > 0:
                entry["hbm_total_gb"] = round(hbm_total[i] / 2**30, 3)
            if i < len(hbm_used):
                entry["hbm_used_gb"] = round(hbm_used[i] / 2**30, 3)
            if i in throttle:
                entry["throttle_score"] = throttle[i]
            per_chip.append(entry)
        return TelemetrySnapshot(
            source=self.name,
            sampled_at=time.time(),
            per_chip=per_chip,
            ici_links=links,
        )


# ---------------------------------------------------------------------------
# Source: engine-derived duty cycle
# ---------------------------------------------------------------------------


class DerivedDutySource:
    """Duty cycle from the engine's own step timing.

    The train loop calls :meth:`observe` with each step's device-phase and
    total wall seconds; ``sample`` reports
    ``100 · Σ device / Σ wall`` over a rolling window, applied to every chip
    of the (SPMD-synchronous) local mesh. Readings expire after
    ``max_age_s`` so an idle engine stops claiming a duty cycle.
    """

    name = "derived"

    def __init__(self, window: int = 50, max_age_s: float = 30.0):
        # Observations are kept PER DEVICE SCOPE (the frozenset of chip
        # ids a job's mesh drives; None = the whole host): two concurrent
        # jobs on disjoint chip subsets must not blend their step timings
        # into one meaningless ratio.
        self._scopes: dict[
            Optional[frozenset[int]], tuple[deque[tuple[float, float]], float]
        ] = {}
        self._window = window
        self._max_age_s = max_age_s
        self._lock = threading.Lock()
        # Staleness visibility: a dead telemetry source must be
        # distinguishable from a never-alive one — age of the newest
        # sample ever seen, plus how many scopes expired unread.
        self._last_observed_at: Optional[float] = None
        self.dropped_stale_total = 0

    def observe(
        self,
        device_s: float,
        wall_s: float,
        device_ids: Optional[Sequence[int]] = None,
    ) -> None:
        """Record one step. ``device_ids`` scopes the reading to the chips
        the step's mesh actually drives (None = every visible chip) — a
        4-chip job on an 8-chip host must not report the 4 idle chips as
        busy."""
        if wall_s <= 0:
            return
        key = (
            frozenset(int(i) for i in device_ids)
            if device_ids is not None
            else None
        )
        with self._lock:
            window, _ = self._scopes.get(key) or (deque(maxlen=self._window), 0.0)
            window.append((max(device_s, 0.0), wall_s))
            now = time.time()
            self._scopes[key] = (window, now)
            self._last_observed_at = now

    def forget(self, device_ids: Sequence[int]) -> None:
        """Drop the scope of a job that has ended: its last duty reading
        describes work that is over, and left to age out it would keep the
        chips reading busy — unschedulable — for ``max_age_s`` after they
        went idle."""
        with self._lock:
            self._scopes.pop(frozenset(int(i) for i in device_ids), None)

    def reset(self) -> None:
        with self._lock:
            self._scopes.clear()
            self._last_observed_at = None
            self.dropped_stale_total = 0

    def staleness(self) -> dict[str, Any]:
        """Freshness surface: age of the newest sample (None = never fed),
        per-scope ages, and how many scopes were silently expired — the
        difference between "engine idle" and "telemetry wiring dead"."""
        now = time.time()
        with self._lock:
            scope_ages = {
                (
                    "host"
                    if key is None
                    else ",".join(str(i) for i in sorted(key))
                ): round(now - last, 3)
                for key, (_, last) in self._scopes.items()
            }
            return {
                "last_sample_age_s": (
                    round(now - self._last_observed_at, 3)
                    if self._last_observed_at is not None
                    else None
                ),
                "scope_ages_s": scope_ages,
                "scopes": len(scope_ages),
                "max_age_s": self._max_age_s,
                "dropped_stale_total": self.dropped_stale_total,
            }

    def sample(self, n_chips: int) -> Optional[TelemetrySnapshot]:
        now = time.time()
        duties: list[tuple[Optional[frozenset[int]], float]] = []
        with self._lock:
            for key, (window, last) in list(self._scopes.items()):
                if now - last > self._max_age_s:
                    del self._scopes[key]  # stale scope: job gone idle
                    self.dropped_stale_total += 1
                    continue
                device = sum(d for d, _ in window)
                wall = sum(w for _, w in window)
                if wall > 0:
                    duties.append(
                        (key, round(min(100.0 * device / wall, 100.0), 2))
                    )
        if not duties:
            return None
        chip_ids: list[Optional[int]] = list(range(n_chips))
        try:
            import jax

            chip_ids = [
                getattr(d, "id", i) for i, d in enumerate(jax.devices()[:n_chips])
            ] + [None] * max(0, n_chips - len(jax.devices()))
        except Exception:
            pass
        per_chip: list[dict[str, Any]] = []
        for cid in chip_ids:
            entry: dict[str, Any] = {}
            # A scoped (per-job) reading beats the unscoped whole-host one.
            for key, duty in sorted(duties, key=lambda kv: kv[0] is None):
                if key is None or (cid is not None and cid in key):
                    entry = {"duty_cycle_pct": duty}
                    break
            per_chip.append(entry)
        return TelemetrySnapshot(
            source=self.name, sampled_at=now, per_chip=per_chip
        )


# ---------------------------------------------------------------------------
# Registry + merge
# ---------------------------------------------------------------------------


@dataclass
class TelemetryOverlay:
    """Priority-merged view across sources, ready to lay over the runtime
    device table."""

    per_chip: list[dict[str, Any]]
    ici_links: list[tuple[str, int]]
    sources: list[str]  # names that contributed, priority order


_derived = DerivedDutySource()
_sources: Optional[list[TelemetrySource]] = None
_sources_lock = threading.Lock()


def derived_duty() -> DerivedDutySource:
    """The process-wide derived-duty source the train loop feeds."""
    return _derived


def observe_step(
    device_s: float,
    wall_s: float,
    device_ids: Optional[Sequence[int]] = None,
) -> None:
    """Record one train step's (device seconds, wall seconds), optionally
    scoped to the device ids the step's mesh drives."""
    _derived.observe(device_s, wall_s, device_ids=device_ids)


# ---------------------------------------------------------------------------
# Source: `tpu-info` CLI fallback
# ---------------------------------------------------------------------------


# Table rows of interest in `tpu-info`'s output (box-drawing or ASCII pipes):
#   TPU Runtime Utilization:  │ 0 │ 1.50 GiB / 31.75 GiB │ 12.00% │
#   TensorCore Utilization:   │ 0 │ 34.20%               │
_CLI_SEP = r"[│┃|]"
_CLI_RUNTIME_ROW = re.compile(
    rf"{_CLI_SEP}?\s*(\d+)\s*{_CLI_SEP}\s*([\d.]+)\s*GiB\s*/\s*([\d.]+)\s*GiB"
    rf"\s*{_CLI_SEP}\s*([\d.]+)\s*%"
)
_CLI_TC_ROW = re.compile(
    rf"{_CLI_SEP}?\s*(\d+)\s*{_CLI_SEP}\s*([\d.]+)\s*%\s*{_CLI_SEP}?\s*$"
)
# TPU Chips table: │ /dev/accel0 │ TPU v5 lite │ 1 │ 777 │ — the trailing
# PID column is the process HOLDING the chip (possibly a process this
# control plane never launched — the reference's per-GPU foreign process
# table, ``gpu_manager.py:174-184``). An empty PID cell = unheld.
_CLI_CHIP_ROW = re.compile(
    rf"/dev/[\w/]*?(\d+)\s*{_CLI_SEP}.*{_CLI_SEP}\s*(\d+)\s*{_CLI_SEP}?\s*$"
)


class TpuInfoCliSource:
    """Parses the ``tpu-info`` CLI — the fallback telemetry source SURVEY
    §2.2 specifies ("use libtpu metrics API, fall back to `tpu-info` CLI
    parse"), and the TPU analogue of the reference's injectable
    ``nvidia-smi`` parse (``gpu_manager.py:100-117``).

    A second *external* reader matters precisely when the in-process SDK
    plane is empty: ``tpu-info`` talks to the runtime's gRPC metrics
    endpoint from outside this process. (On the v5e hosts of PR 21 the SDK
    reports duty cycle and HBM, and no ``tpu-info`` binary is installed.)

    ``runner=`` injects a callable returning canned CLI output for tests
    (the exact affordance the reference builds for nvidia-smi). Without it,
    the real binary is invoked — when present — with a hard timeout, and
    any failure degrades to "no data" (never an exception on the fleet
    path).

    Fleet polls and /metrics scrapes hit ``sample`` on their hot path, so
    real subprocess invocations are rate-limited: at most one fork per
    ``min_interval_s``; between runs the cached text (including a cached
    miss) is served. Injected runners are not cached — tests control their
    own output.
    """

    name = "tpu_info_cli"

    def __init__(self, runner: Any = None, binary: str = "tpu-info",
                 timeout_s: float = 5.0, min_interval_s: float = 10.0):
        self._runner = runner
        self._binary = binary
        self._timeout_s = timeout_s
        self._min_interval_s = min_interval_s
        self._cached: Optional[str] = None
        self._cached_at = float("-inf")
        self._which: Optional[bool] = None  # PATH probe, done once
        self._lock = threading.Lock()

    def _invoke(self) -> Optional[str]:
        import shutil
        import subprocess

        if self._which is None:
            self._which = shutil.which(self._binary) is not None
        if not self._which:
            return None
        try:
            proc = subprocess.run(
                [self._binary], capture_output=True, text=True,
                timeout=self._timeout_s,
            )
        except Exception:
            return None
        return proc.stdout if proc.returncode == 0 else None

    def _output(self) -> Optional[str]:
        if self._runner is not None:
            try:
                return self._runner()
            except Exception:
                return None
        with self._lock:
            now = time.time()
            if now - self._cached_at < self._min_interval_s:
                return self._cached
            self._cached = self._invoke()
            self._cached_at = now
            return self._cached

    @staticmethod
    def parse(text: str) -> dict[int, dict[str, Any]]:
        """CLI table text → {device index: overlay fields}."""
        out: dict[int, dict[str, Any]] = {}
        for line in text.splitlines():
            m = _CLI_CHIP_ROW.search(line)
            if m and "/dev/" in line:
                idx = int(m.group(1))
                out.setdefault(idx, {})["holder_pid"] = int(m.group(2))
                continue
            m = _CLI_RUNTIME_ROW.search(line)
            if m:
                idx = int(m.group(1))
                entry = out.setdefault(idx, {})
                entry["hbm_used_gb"] = round(float(m.group(2)), 3)
                entry["hbm_total_gb"] = round(float(m.group(3)), 3)
                entry["duty_cycle_pct"] = round(float(m.group(4)), 2)
                continue
            m = _CLI_TC_ROW.search(line)
            if m and "GiB" not in line:
                idx = int(m.group(1))
                out.setdefault(idx, {})["tensorcore_util_pct"] = round(
                    float(m.group(2)), 2
                )
        return out

    def sample(self, n_chips: int) -> Optional[TelemetrySnapshot]:
        text = self._output()
        if not text:
            return None
        fields = self.parse(text)
        if not fields:
            return None
        per_chip = [dict(fields.get(i, {})) for i in range(n_chips)]
        return TelemetrySnapshot(
            source=self.name, sampled_at=time.time(), per_chip=per_chip
        )


# ---------------------------------------------------------------------------
# Per-chip job attribution
# ---------------------------------------------------------------------------
#
# The reference fleet view reports, per GPU, the live process table — pid,
# name, memory (``gpu_manager.py:27-33``, populated ``:174-184``) — so an
# operator can see WHAT occupies a device. TPU runtimes expose no foreign
# process table, but this control plane *owns* its supervised jobs: each
# supervisor registers the chip ids its mesh drives on this host while the
# job runs, and the fleet snapshot lays the claims over the device table.

_claims: dict[str, "JobDeviceClaim"] = {}
_claims_lock = threading.Lock()


@dataclass
class JobDeviceClaim:
    """One running job's hold on a set of local chips."""

    job_id: str
    device_ids: frozenset[int]
    process_index: int
    # Live status read (e.g. ``lambda: job.status.value``) so the fleet
    # shows compiling/running without the registry chasing transitions.
    status_fn: Any


def register_job_devices(
    job_id: str,
    device_ids: Sequence[int],
    process_index: int,
    status_fn,
) -> None:
    """Claim ``device_ids`` for ``job_id`` until :func:`unregister_job_devices`."""
    with _claims_lock:
        _claims[job_id] = JobDeviceClaim(
            job_id=job_id,
            device_ids=frozenset(int(i) for i in device_ids),
            process_index=int(process_index),
            status_fn=status_fn,
        )


def unregister_job_devices(job_id: str) -> None:
    """Release ``job_id``'s claim, and with it the derived duty cycle its
    steps fed (scoped to the same chips)."""
    with _claims_lock:
        claim = _claims.pop(job_id, None)
    if claim is not None:
        _derived.forget(claim.device_ids)


def job_attribution() -> dict[int, list[dict[str, Any]]]:
    """device id → jobs holding it, each ``{job_id, status, process_index}``."""
    with _claims_lock:
        claims = list(_claims.values())
    out: dict[int, list[dict[str, Any]]] = {}
    for c in claims:
        try:
            status = str(c.status_fn())
        except Exception:
            status = "unknown"
        ref = {
            "job_id": c.job_id,
            "status": status,
            "process_index": c.process_index,
        }
        for did in c.device_ids:
            out.setdefault(did, []).append(ref)
    return out


def sources() -> list[TelemetrySource]:
    global _sources
    with _sources_lock:
        if _sources is None:
            # Priority: in-process SDK > external CLI > engine-derived.
            _sources = [LibtpuSdkSource(), TpuInfoCliSource(), _derived]
        return list(_sources)


def set_sources(srcs: Optional[list[TelemetrySource]]) -> None:
    """Replace the registry (None restores the default stack). Test seam."""
    global _sources
    with _sources_lock:
        _sources = list(srcs) if srcs is not None else None


def sample_overlay(n_chips: int) -> Optional[TelemetryOverlay]:
    """Sample every registered source and merge per-chip fields,
    first-source-wins. None when no source has data."""
    merged: list[dict[str, Any]] = [{} for _ in range(n_chips)]
    links: list[tuple[str, int]] = []
    contributed: list[str] = []
    for src in sources():
        try:
            snap = src.sample(n_chips)
        except Exception:
            continue
        if snap is None:
            continue
        used = False
        for i, entry in enumerate(snap.per_chip[:n_chips]):
            for k, v in entry.items():
                if v is not None and k not in merged[i]:
                    merged[i][k] = v
                    used = True
        if snap.ici_links and not links:
            links = list(snap.ici_links)
            used = True
        if used:
            contributed.append(snap.source)
    if not contributed:
        return None
    return TelemetryOverlay(per_chip=merged, ici_links=links, sources=contributed)


def ici_link_alerts(links: Sequence[tuple[str, int]]) -> list[str]:
    """Fleet alert lines from ICI link scores (libtpu scale: 0 healthy,
    1-5 transient problem, 6-9 persistent minor problem, 10 unusable)."""
    alerts: list[str] = []
    for loc, score in links:
        if score >= 10:
            alerts.append(f"CRITICAL: ICI link {loc} unusable (score {score})")
        elif score >= 6:
            alerts.append(
                f"WARNING: persistent ICI problem on link {loc} (score {score})"
            )
        elif score >= 1:
            alerts.append(
                f"WARNING: transient ICI problem on link {loc} (score {score})"
            )
    return alerts
