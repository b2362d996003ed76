"""Profiling & tracing: per-step wall-clock breakdown, MFU accounting, and
on-demand ``jax.profiler`` trace capture.

The reference delegates all profiling to DeepSpeed config flags
(``wall_clock_breakdown``, ``dump_state`` — ``ai_engine/deepspeed_launcher.py:79-80,
129-130``) and carries throughput as a passive, never-analysed field
(``ai_engine/loss_monitor.py:50``). Here the engine owns the numbers
(SURVEY.md §5 tracing plan):

- :class:`StepProfiler` — the phase clock inside the supervisor loop and
  ``ContinuousBatcher.step``: every phase of an iteration by name, on the
  host clock (rolling mean/p50/p95, bounded window) and as
  ``tpu_engine.<loop>.<phase>`` annotations on the profiler's clock;
- :func:`mfu` / :func:`peak_flops_per_chip` — tokens/sec/chip → model-FLOPs
  utilisation against the chip's bf16 peak (the BASELINE.json north-star
  metric);
- :class:`TraceSession` — start/stop ``jax.profiler`` traces (XPlane/
  TensorBoard format) with an optional auto-stop duration, safe to drive
  from the HTTP control plane.
"""

from __future__ import annotations

import contextlib
import statistics
import threading
import time
from collections import deque
from typing import Any, Optional

import jax

# Peak bf16 FLOP/s per chip by device kind (public spec sheets).
PEAK_FLOPS_BF16 = {
    "v4": 275e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6 lite": 918e12,
    "v6e": 918e12,
    "trillium": 918e12,
}


def peak_flops_per_chip(device: Optional[jax.Device] = None) -> Optional[float]:
    """Peak bf16 FLOP/s for ``device`` (default: first visible). None off
    TPU (CPU test meshes have no peak); a TPU whose ``device_kind`` is not
    in the table is an error, not a default — a utilization against a
    guessed peak is not a measurement."""
    if device is None:
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "").lower()
    for key, flops in PEAK_FLOPS_BF16.items():
        if key in kind:
            return flops
    if getattr(device, "platform", None) == "tpu":
        raise ValueError(
            f"no peak FLOP/s entry for TPU device_kind "
            f"{getattr(device, 'device_kind', None)!r}; known: "
            f"{sorted(PEAK_FLOPS_BF16)} (add it to PEAK_FLOPS_BF16 with its source)"
        )
    return None


def mfu(
    flops_per_token: float,
    tokens_per_sec_per_chip: float,
    device: Optional[jax.Device] = None,
) -> Optional[float]:
    """Model-FLOPs utilisation in [0, 1], or None off known TPU chips.

    Uses *model* FLOPs (6N + attention), not hardware FLOPs: remat recompute
    is deliberately not credited, matching the standard MFU definition.

    Accounting basis under int8 quantized training (``quant_training=
    'int8'``, tpu_engine/quant_train.py): the numerator stays MODEL FLOPs
    and the denominator stays the chip's BF16 peak — quantization changes
    neither the model nor this definition. What it changes is the
    ACHIEVABLE roofline: int8×int8→int32 MXU throughput is up to 2× the
    bf16 rate, so a quantized run can legitimately report >100%
    "bf16-MFU" on matmul-bound configs. Compare quantized runs by
    step time / tokens-per-sec, and read their MFU as a fraction of the
    bf16 roofline, not of the hardware's int8 ceiling.
    """
    peak = peak_flops_per_chip(device)
    if peak is None or tokens_per_sec_per_chip <= 0:
        return None
    return flops_per_token * tokens_per_sec_per_chip / peak


def pipeline_tick_account(
    schedule: str, n_stages: int, microbatches: int
) -> Optional[dict[str, Any]]:
    """Analytic tick / busy-lane account for a pipelined run, or None off
    the pipelined path (``n_stages <= 1``).

    Thin re-export of ``tpu_engine.parallel.pipeline_zb.schedule_account``
    so profiler consumers (supervisor telemetry, bench.py) don't import the
    schedule module directly. ``busy_fraction`` is useful lane F-units over
    total lane F-units — see the schedule module for the cost model.
    """
    if n_stages <= 1:
        return None
    from tpu_engine.parallel.pipeline_zb import schedule_account

    return schedule_account(schedule, n_stages, microbatches)


class StepProfiler:
    """The phase clock of one host loop: a rolling wall-clock breakdown of
    its iterations, on the host clock and, while a ``jax.profiler`` session
    runs, on the profiler's clock beside the device ops.

    A step runs **from one** :meth:`begin_step` **to the next**, so
    ``summary()["total"]`` (and the rates derived from it) covers the whole
    iteration, not just the part up to the blocking device read. Inside it
    ``with prof.phase(name, **ids)`` adds the interval's host seconds to
    ``name`` and holds a ``jax.profiler.TraceAnnotation(
    "tpu_engine.<loop>.<name>", **ids)`` for exactly that interval. With no
    profiler session the annotation is a flag test; together with two
    ``perf_counter`` reads a phase that is the whole cost of the clock.
    Phases do not nest. ``other`` is the un-attributed remainder of the
    iteration and is reported like any phase: its annotation spans the whole
    iteration, so in a trace the phases lie inside it and whatever they leave
    uncovered reads ``tpu_engine.<loop>.other``. All in seconds.

    ``loop`` and ``phases`` are the owning loop's: it names itself and the
    phases of its body (``other`` is appended here).
    """

    def __init__(self, *, loop: str, phases: tuple[str, ...], window: int = 100,
                 tokens_per_step: Optional[int] = None,
                 flops_per_token: Optional[float] = None, n_devices: int = 1,
                 pipeline_account: Optional[dict[str, Any]] = None):
        self.window = window
        self.tokens_per_step = tokens_per_step
        self.flops_per_token = flops_per_token
        self.n_devices = max(n_devices, 1)
        # Analytic schedule account for pipelined runs (from
        # pipeline_tick_account): enables bubble-adjusted MFU — raw MFU
        # divided by the schedule's busy-lane fraction, i.e. utilisation of
        # the lanes the schedule actually keeps busy. Without it RESULTS.md
        # under-reports pipelined MFU: the bubble is a schedule property,
        # not a kernel-efficiency loss.
        self.pipeline_account = pipeline_account
        self.loop = loop
        self.phases = tuple(phases) + ("other",)
        self._span_names = {p: f"tpu_engine.{loop}.{p}" for p in self.phases}
        self._iteration: Optional[Any] = None  # the open iteration's ``other`` annotation
        self._phases: dict[str, deque[float]] = {p: deque(maxlen=window) for p in self.phases}
        self._totals: deque[float] = deque(maxlen=window)
        self._steps_seen = 0
        self._lock = threading.Lock()
        self._t_step_start: Optional[float] = None
        self._current: dict[str, float] = {}
        self._last: dict[str, float] = {}
        self._last_total: Optional[float] = None

    # -- recording ----------------------------------------------------------

    def begin_step(self) -> Optional[float]:
        """Open an iteration, closing the one before it; returns the closed
        iteration's total seconds (None when none was open)."""
        now = time.perf_counter()
        closed = self._close(now)
        self._t_step_start = now
        self._current = {}
        self._iteration = jax.profiler.TraceAnnotation(self._span_names["other"])
        self._iteration.__enter__()
        return closed

    def end_step(self) -> Optional[float]:
        """Close the open iteration without opening another (the loop has
        left its body for the last time). Returns its total seconds."""
        return self._close(time.perf_counter())

    def _close(self, now: float) -> Optional[float]:
        if self._t_step_start is None:
            return None
        self._iteration.__exit__(None, None, None)
        total = now - self._t_step_start
        cur = self._current
        cur["other"] = max(total - sum(cur.values()), 0.0)
        with self._lock:
            for p in self.phases:
                self._phases[p].append(cur.get(p, 0.0))
            self._totals.append(total)
            self._steps_seen += 1
        self._last, self._last_total = cur, total
        self._t_step_start = None
        return total

    @contextlib.contextmanager
    def phase(self, name: str, **ids: Any):
        """Attribute the enclosed interval to ``name``; ``ids`` (step,
        request id, slot …) travel on the profiler annotation only."""
        with jax.profiler.TraceAnnotation(self._span_names[name], **ids):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                cur = self._current
                cur[name] = cur.get(name, 0.0) + (time.perf_counter() - t0)

    def open_step(self) -> tuple[dict[str, float], float]:
        """(phase seconds so far, seconds since it began) of the open
        iteration."""
        if self._t_step_start is None:
            return {}, 0.0
        return dict(self._current), time.perf_counter() - self._t_step_start

    def last_step(self) -> Optional[tuple[dict[str, float], float]]:
        """(phase seconds, whole-iteration seconds) of the most recently
        closed iteration; None before any has closed."""
        if self._last_total is None:
            return None
        return dict(self._last), self._last_total

    # -- views --------------------------------------------------------------
    @staticmethod
    def _stats(xs: list[float]) -> dict[str, float]:
        if not xs:
            return {"mean_ms": 0.0, "p50_ms": 0.0, "p95_ms": 0.0}
        xs_sorted = sorted(xs)
        p95 = xs_sorted[min(int(0.95 * (len(xs_sorted) - 1)), len(xs_sorted) - 1)]
        return {
            "mean_ms": statistics.fmean(xs) * 1e3,
            "p50_ms": statistics.median(xs_sorted) * 1e3,
            "p95_ms": p95 * 1e3,
        }

    def summary(self) -> dict[str, Any]:
        with self._lock:
            totals = list(self._totals)
            phases = {p: list(v) for p, v in self._phases.items()}
            steps_seen = self._steps_seen
        out: dict[str, Any] = {
            "steps_seen": steps_seen,
            "window": len(totals),
            "total": self._stats(totals),
            "phases": {p: self._stats(v) for p, v in phases.items()},
        }
        mean_total = statistics.fmean(totals) if totals else 0.0
        if totals and mean_total > 0:
            for p, v in phases.items():
                out["phases"][p]["fraction"] = round(statistics.fmean(v) / mean_total, 4)
        if self.tokens_per_step and mean_total > 0:
            tps = self.tokens_per_step / mean_total
            out["tokens_per_sec"] = round(tps, 1)
            out["tokens_per_sec_per_chip"] = round(tps / self.n_devices, 1)
            if self.flops_per_token:
                u = mfu(self.flops_per_token, tps / self.n_devices)
                out["mfu"] = round(u, 4) if u is not None else None
        if self.pipeline_account is not None:
            acct = self.pipeline_account
            busy = acct.get("busy_fraction", 1.0) or 1.0
            out["pipeline"] = {
                "schedule": acct.get("schedule"),
                "n_stages": acct.get("n_stages"),
                "microbatches": acct.get("microbatches"),
                "ticks": acct.get("ticks"),
                "busy_fraction": round(busy, 4),
                "bubble_fraction": round(acct.get("bubble_fraction", 0.0), 4),
            }
            if out.get("mfu") is not None:
                out["mfu_bubble_adjusted"] = round(out["mfu"] / busy, 4)
        return out


class TraceActiveError(RuntimeError):
    """Raised on double-start; carries the active capture's coordinates so
    callers (the ``/api/v1/profile/start`` route, the anomaly auto-trace
    hook) can report a structured conflict instead of a bare string."""

    def __init__(self, log_dir: str, started_at: float):
        self.log_dir = log_dir
        self.started_at = started_at
        super().__init__(f"trace already active (dir={log_dir})")

    def describe(self) -> dict[str, Any]:
        return {
            "log_dir": self.log_dir,
            "started_at": self.started_at,
            "elapsed_s": round(time.time() - self.started_at, 3),
        }


class TraceSession:
    """On-demand ``jax.profiler`` trace capture (one at a time per process).

    Produces XPlane traces viewable in TensorBoard / Perfetto. Drive from
    code or the ``/api/v1/profile`` routes.
    """

    def __init__(self):
        # RLock: start() reports via status() while still holding the lock.
        self._lock = threading.RLock()
        self._active_dir: Optional[str] = None
        self._started_at: Optional[float] = None
        self._auto_timer: Optional[threading.Timer] = None

    @property
    def active(self) -> bool:
        return self._active_dir is not None

    def start(self, log_dir: str, duration_s: Optional[float] = None) -> dict[str, Any]:
        with self._lock:
            if self._active_dir is not None:
                raise TraceActiveError(
                    self._active_dir, self._started_at or time.time()
                )
            jax.profiler.start_trace(log_dir)
            self._active_dir = log_dir
            self._started_at = time.time()
            if duration_s is not None and duration_s > 0:
                self._auto_timer = threading.Timer(duration_s, self._auto_stop)
                self._auto_timer.daemon = True
                self._auto_timer.start()
            return self.status()

    def _auto_stop(self) -> None:
        try:
            self.stop()
        except Exception:
            pass

    def stop(self) -> dict[str, Any]:
        with self._lock:
            if self._active_dir is None:
                raise RuntimeError("no active trace")
            if self._auto_timer is not None:
                self._auto_timer.cancel()
                self._auto_timer = None
            jax.profiler.stop_trace()
            info = {
                "log_dir": self._active_dir,
                "duration_s": round(time.time() - (self._started_at or time.time()), 3),
                "active": False,
            }
            self._active_dir = None
            self._started_at = None
            return info

    def status(self) -> dict[str, Any]:
        with self._lock:
            if self._active_dir is None:
                return {"active": False}
            return {
                "active": True,
                "log_dir": self._active_dir,
                "elapsed_s": round(time.time() - (self._started_at or time.time()), 3),
            }
