"""Profiling & tracing: per-step wall-clock breakdown, MFU accounting, and
on-demand ``jax.profiler`` trace capture.

The reference delegates all profiling to DeepSpeed config flags
(``wall_clock_breakdown``, ``dump_state`` — ``ai_engine/deepspeed_launcher.py:79-80,
129-130``) and carries throughput as a passive, never-analysed field
(``ai_engine/loss_monitor.py:50``). Here the engine owns the numbers
(SURVEY.md §5 tracing plan):

- :class:`StepProfiler` — the phase clock inside the supervisor loop and
  ``ContinuousBatcher.step``: every phase of an iteration by name, on the
  host clock (rolling mean/p50/p95, bounded window; wall seconds and the
  part of them the loop's thread spent off the CPU) and as
  ``tpu_engine.<loop>.<phase>`` annotations on the profiler's clock;
- :func:`ctl_span` — what the control plane's *other* threads do beside such
  a loop (the scheduler's pump, the fleet sample, the serving fleet's
  request plane), as ``tpu_ctl.<owner>.<what>`` annotations;
- :func:`mfu` / :func:`peak_flops_per_chip` — tokens/sec/chip → model-FLOPs
  utilisation against the chip's bf16 peak (the BASELINE.json north-star
  metric);
- :class:`TraceSession` — start/stop ``jax.profiler`` traces (XPlane/
  TensorBoard format) with an optional auto-stop duration, safe to drive
  from the HTTP control plane.
"""

from __future__ import annotations

import statistics
import threading
import time
from collections import deque
from time import perf_counter, thread_time
from typing import Any, Optional

import jax

_tracing = jax.profiler.TraceAnnotation.is_enabled  # a profiler session is running

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
    so profiler consumers (supervisor telemetry) don't import the
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

    **Blocked seconds.** ``blocked = wall - thread CPU`` (``time.
    thread_time``): a thread that waits for the interpreter lock, a mutex, a
    sleep or the device is off the CPU, one that runs Python or dispatches is
    on it. So host code that took 12 ms because another thread held the
    interpreter reads 12 ms wall, 11 ms blocked. The thread's clock is a
    system call (no vDSO), which a sandboxed kernel can make expensive (6-11
    us a read on the benchmark's machine; 22 reads a dispatch cost
    serve-batch 1.5 %), so the clock reads it **only in iterations that began
    while a profiler session ran**, around the iteration and every phase of
    it: ``summary()["total"]["blocked_ms"]`` and ``["phases"][p]
    ["blocked_ms"]`` cover the last ``window`` such iterations and are absent
    before the first, and each phase's annotation carries ``blocked_us=``
    (the iteration's ``other`` annotation the whole iteration's). Its
    resolution is the platform's too: where the kernel accounts CPU time in
    10 ms ticks a single value says little and the mean over many iterations
    is what to read, so nothing is clamped (a tick credited to a 2 ms phase
    reads -8 ms). The thread's clock is the calling thread's: one loop drives
    one clock from one thread.

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
        # the lanes the schedule actually keeps busy. Without it a report
        # under-states pipelined MFU: the bubble is a schedule property,
        # not a kernel-efficiency loss.
        self.pipeline_account = pipeline_account
        self.loop = loop
        self.phases = tuple(phases) + ("other",)
        self._span_names = {p: f"tpu_engine.{loop}.{p}" for p in self.phases}
        self._iteration: Optional[Any] = None  # the open iteration's ``other`` annotation
        self._phases: dict[str, deque[float]] = {p: deque(maxlen=window) for p in self.phases}
        self._blocked: dict[str, deque[float]] = {p: deque(maxlen=window) for p in self.phases}
        self._totals: deque[float] = deque(maxlen=window)
        self._totals_blocked: deque[float] = deque(maxlen=window)
        self._steps_seen = 0
        self._lock = threading.Lock()
        self._t_step_start: Optional[float] = None
        self._cpu_step_start = 0.0
        self._current: dict[str, float] = {}
        self._current_blocked: Optional[dict[str, float]] = None  # None: this iteration's phases read no CPU clock
        self._last: dict[str, float] = {}
        self._last_total: Optional[float] = None

    # -- recording ----------------------------------------------------------

    def begin_step(self) -> Optional[float]:
        """Open an iteration, closing the one before it; returns the closed
        iteration's total seconds (None when none was open)."""
        now = perf_counter()
        closed = self._close(now)
        self._t_step_start = now
        self._current = {}
        # The thread's clock is read only in an iteration that begins under a session.
        if _tracing():
            self._current_blocked, self._cpu_step_start = {}, thread_time()
        else:
            self._current_blocked = None
        self._iteration = jax.profiler.TraceAnnotation(self._span_names["other"])
        self._iteration.__enter__()
        return closed

    def end_step(self) -> Optional[float]:
        """Close the open iteration without opening another (the loop has
        left its body for the last time). Returns its total seconds."""
        return self._close(perf_counter())

    def _close(self, now: float) -> Optional[float]:
        if self._t_step_start is None:
            return None
        total = now - self._t_step_start
        cur, blk = self._current, self._current_blocked
        cur["other"] = max(total - sum(cur.values()), 0.0)
        blocked = 0.0
        if blk is not None:
            blocked = total - (thread_time() - self._cpu_step_start)
            blk["other"] = blocked - sum(blk.values())
            if _tracing():
                self._iteration.set_metadata(blocked_us=round(blocked * 1e6))
        self._iteration.__exit__(None, None, None)
        with self._lock:
            for p in self.phases:
                self._phases[p].append(cur.get(p, 0.0))
                if blk is not None:
                    self._blocked[p].append(blk.get(p, 0.0))
            self._totals.append(total)
            if blk is not None:
                self._totals_blocked.append(blocked)
            self._steps_seen += 1
        self._last, self._last_total = cur, total
        self._t_step_start = None
        return total

    def phase(self, name: str, **ids: Any) -> "_Phase":
        """Attribute the enclosed interval to ``name``; ``ids`` (step,
        request id, slot …) travel on the profiler annotation only."""
        return _Phase(self, name, jax.profiler.TraceAnnotation(self._span_names[name], **ids))

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

    @classmethod
    def _blocked_stats(cls, xs: list[float]) -> dict[str, float]:
        s = cls._stats(xs)
        return {"mean": s["mean_ms"], "p50": s["p50_ms"], "p95": s["p95_ms"]}

    def summary(self) -> dict[str, Any]:
        with self._lock:
            totals = list(self._totals)
            totals_blocked = list(self._totals_blocked)
            phases = {p: list(v) for p, v in self._phases.items()}
            blocked = {p: list(v) for p, v in self._blocked.items()}
            steps_seen = self._steps_seen
        out: dict[str, Any] = {
            "steps_seen": steps_seen,
            "window": len(totals),
            "total": self._stats(totals),
            "phases": {p: self._stats(v) for p, v in phases.items()},
        }
        if totals_blocked:  # iterations that began under a profiler session
            out["total"]["blocked_ms"] = self._blocked_stats(totals_blocked)
            for p, v in blocked.items():
                out["phases"][p]["blocked_ms"] = self._blocked_stats(v)
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


class _Phase:
    """One ``with prof.phase(name)`` interval: wall seconds always, and in an
    iteration that began under a profiler session the thread's CPU seconds,
    read at entry and exit inside the phase's annotation (a class, not a
    generator: the clock runs ten times a dispatch)."""

    __slots__ = ("_prof", "_name", "_ann", "_t0", "_cpu0")

    def __init__(self, prof: StepProfiler, name: str, ann: Any):
        self._prof, self._name, self._ann = prof, name, ann

    def __enter__(self) -> None:
        self._ann.__enter__()
        self._cpu0 = thread_time() if self._prof._current_blocked is not None else None
        self._t0 = perf_counter()

    def __exit__(self, *exc: Any) -> None:
        wall = perf_counter() - self._t0
        prof, name = self._prof, self._name
        cur, blk = prof._current, prof._current_blocked
        cur[name] = cur.get(name, 0.0) + wall
        if self._cpu0 is not None and blk is not None:  # same iteration as at entry
            cpu = thread_time() - self._cpu0
            blk[name] = blk.get(name, 0.0) + wall - cpu
            if _tracing():
                self._ann.set_metadata(blocked_us=round((wall - cpu) * 1e6))
        self._ann.__exit__(*exc)


class _NoSpan:
    """What :func:`ctl_span` hands out while no profiler session runs."""

    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc: Any) -> None:
        pass

    def set_metadata(self, **ids: Any) -> None:
        pass


_NO_SPAN = _NoSpan()


def ctl_span(owner: str, what: str, **ids: Any) -> Any:
    """``with ctl_span(owner, what, **ids) as span``: something the control
    plane does on a thread that is *not* a loop feeding the chip (the
    scheduler's pump, a fleet sample, the serving fleet's request plane), so
    that a trace shows what ran beside the loop.

    While a ``jax.profiler`` session runs it is a ``TraceAnnotation(
    "tpu_ctl.<owner>.<what>", thread=<the calling thread's name>, **ids)``
    (``span.set_metadata(**more)`` adds arguments known only at the end);
    with no session a flag test and nothing else. Whoever wants its count or
    its seconds with no trace keeps them itself (``FleetScheduler.poll``
    does). The prefix is ``tpu_ctl.`` and not ``tpu_engine.`` on purpose:
    readers of the phase clock merge all host threads and keep the innermost
    ``tpu_engine.*`` annotation, so one of those on another thread would
    rename the loop's idle gaps."""
    if not _tracing():
        return _NO_SPAN
    return jax.profiler.TraceAnnotation(
        f"tpu_ctl.{owner}.{what}", thread=threading.current_thread().name, **ids)


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
