"""The program's names in a traced run's ``.xplane.pb``, host *line* by host
line: what ``program_trace`` (which merges every host thread and keeps the
innermost ``tpu_engine.*`` annotation) cannot tell.

A host line is one thread. The loop that feeds the chip (the supervisor's, or
``ContinuousBatcher.step``'s driver) holds one ``tpu_engine.<loop>.other``
annotation an iteration, begin to begin, with the phases of the iteration
inside it; since PR 38, in a traced run, every one of them carries
``blocked_us=`` (wall minus the thread's CPU time, ``StepProfiler``). The
control plane's *other* work is ``tpu_ctl.<owner>.<what>`` (``profiler.ctl_span``):
the scheduler's pump, ``TPUManager.get_fleet_status``, the serving fleet's
``tick`` / ``route`` / ``result``. Such a span counts as *beside* the loop
when it lies on another line than the loop's.

- ``iterations``: the loop's iterations, each with its phases;
- ``dispatch_host_ms``: an iteration's length minus the first chip's busy
  time inside it;
- ``blocked_ms``: the blocked seconds of an iteration's host-only phases;
- ``read_lags_ms``: how long after the chip's last program ended the
  loop's blocking read (phase ``device``) returned;
- ``idle_beside``: the first chip's idle seconds, and those during which a
  ``tpu_ctl.*`` span was open beside the loop;
- ``span_seconds`` / ``span_period_ms``: any span's summed time inside the
  device's window, and the median distance of its starts.

A parent commit's program writes none of the new names (no ``blocked_us``
on its iterations, no ``tpu_ctl.*``): ``of_run`` then returns None and every
reader over it returns None. The file is parsed once a process.
"""

from __future__ import annotations

import bisect
import json
import statistics
from collections import defaultdict
from functools import lru_cache

from . import program_trace, trace_reduce

LOOP_PREFIX, CTL_PREFIX = "tpu_engine.", "tpu_ctl."
# The phases of a loop in which the host may be waiting for the chip by design.
DEVICE_PHASES = {"batcher": {"prefill", "device", "idle"}, "supervisor": {"device"}}


def host_lines(data) -> list[list[tuple[float, float, str, dict]]]:
    """Per host line that holds any, its ``tpu_engine.*`` and ``tpu_ctl.*``
    events as (start_ns, end_ns, full name, arguments), by start."""
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name, {k: str(v) for k, v in ev.stats})
                   for ev in ln.events if ev.name.startswith((LOOP_PREFIX, CTL_PREFIX))]
            if evs:
                out.append(sorted(evs, key=lambda e: (e[0], -e[1])))
    return out


def _loop_line(lines) -> tuple[int, str] | None:
    """(index of the loop's line, "<loop>"): the line that holds the most
    ``tpu_engine.<loop>.other`` annotations."""
    best = None
    for i, evs in enumerate(lines):
        count: dict[str, int] = defaultdict(int)
        for _, _, name, _ in evs:
            if name.startswith(LOOP_PREFIX) and name.endswith(".other"):
                count[name[len(LOOP_PREFIX):-len(".other")]] += 1
        for loop, n in count.items():
            if best is None or n > best[0]:
                best = (n, i, loop)
    return (best[1], best[2]) if best else None


def _num(args: dict, key: str) -> float | None:
    try:
        return float(args[key])
    except (KeyError, ValueError):
        return None


def iterations(evs, loop: str) -> list[dict]:
    """The loop line's iterations: {"t0", "t1", "args", "phases": [(t0, t1,
    phase, args)]}; a phase belongs to the iteration it began in (an annotation
    nested in a phase, ``supervisor.health_sample``, is listed like one)."""
    other = LOOP_PREFIX + loop + ".other"
    its = [{"t0": a, "t1": b, "args": args, "phases": []} for a, b, name, args in evs if name == other]
    starts = [it["t0"] for it in its]
    prefix = LOOP_PREFIX + loop + "."
    for a, b, name, args in evs:
        if name == other or not name.startswith(prefix):
            continue
        k = bisect.bisect_right(starts, a) - 1
        if k >= 0 and a < its[k]["t1"]:
            its[k]["phases"].append((a, b, name[len(prefix):], args))
    return its


class _Union:
    """Sorted disjoint intervals with their starts kept for bisection."""

    def __init__(self, intervals):
        self.ivs = trace_reduce._union(intervals)
        self.starts = [a for a, _ in self.ivs]

    def inside(self, t0: float, t1: float) -> float:
        k = max(bisect.bisect_right(self.starts, t0) - 1, 0)
        total = 0.0
        while k < len(self.ivs) and self.ivs[k][0] < t1:
            total += max(min(self.ivs[k][1], t1) - max(self.ivs[k][0], t0), 0.0)
            k += 1
        return total


def read(data) -> dict | None:
    """Everything the readers take, or None where the trace holds no loop
    whose iterations carry ``blocked_us`` (a parent's program)."""
    lines = host_lines(data)
    found = _loop_line(lines)
    if found is None:
        return None
    at, loop = found
    its = iterations(lines[at], loop)
    if not any("blocked_us" in it["args"] for it in its):
        return None
    planes = trace_reduce._device_planes(data)
    ops = trace_reduce._line(planes[0], trace_reduce.OPS_LINE) if planes else None
    mods = trace_reduce._line(planes[0], trace_reduce.MODULES_LINE) if planes else None
    if ops is None or not len(list(ops.events)):
        ops = mods
    op_ivs = [(ev.start_ns, ev.start_ns + ev.duration_ns) for ev in ops.events] if ops is not None else []
    gaps, _ = program_trace.first_chip_gaps(data)
    spans = [(a, b, name, args, i != at) for i, evs in enumerate(lines) for a, b, name, args in evs
             if name.startswith(CTL_PREFIX) or name == LOOP_PREFIX + "supervisor.health_sample"]
    return {
        "loop": loop, "iterations": its, "busy": _Union(op_ivs), "gaps": gaps,
        "window": (min(a for a, _ in op_ivs), max(b for _, b in op_ivs)) if op_ivs else None,
        "module_ends": sorted(ev.start_ns + ev.duration_ns for ev in mods.events) if mods is not None else [],
        "spans": sorted(spans, key=lambda s: s[0]),  # (t0, t1, name, args, beside the loop)
    }


def dispatches(tr: dict) -> list[dict]:
    """The iterations that dispatched to the chip and read it back: those
    that hold a ``device`` phase."""
    return [it for it in tr["iterations"] if any(p[2] == "device" for p in it["phases"])]


def dispatch_host_ms(tr: dict) -> list[float]:
    """Per dispatching iteration: its length minus the first chip's busy
    time inside it, in ms."""
    return [((it["t1"] - it["t0"]) - tr["busy"].inside(it["t0"], it["t1"])) / 1e6 for it in dispatches(tr)]


def blocked_ms(tr: dict) -> list[float]:
    """Per dispatching iteration: the blocked (off-CPU) milliseconds of its
    host-only phases; ``other``'s are the iteration's less every phase's.
    Unclamped, as the program writes them: where the kernel accounts a
    thread's CPU time in ticks (10 ms on the benchmark's machine) a tick
    credited to a short phase reads negative, and the mean over the window
    is the number to read."""
    waits = DEVICE_PHASES.get(tr["loop"], {"device"})
    out = []
    for it in dispatches(tr):
        whole = _num(it["args"], "blocked_us")
        if whole is None:
            continue
        waiting = sum(_num(args, "blocked_us") or 0.0 for _, _, phase, args in it["phases"] if phase in waits)
        out.append((whole - waiting) / 1e3)
    return out


def read_lags_ms(tr: dict) -> list[float]:
    """Per ``device`` phase: its end minus the end of the last program run
    of the first chip that ended inside it; 0 where none did (the program
    had ended before the read began)."""
    ends = tr["module_ends"]
    out = []
    for it in tr["iterations"]:
        for t0, t1, phase, _ in it["phases"]:
            if phase != "device":
                continue
            k = bisect.bisect_right(ends, t1) - 1
            out.append((t1 - ends[k]) / 1e6 if k >= 0 and ends[k] >= t0 else 0.0)
    return out


def idle_beside(tr: dict) -> dict:
    """{"idle_s", "beside_s", "by_span": {name: seconds}} of the first chip's
    idle gaps: the part during which some ``tpu_ctl.*`` span was open on a
    line other than the loop's, and each name's own part (nested spans both
    count, so the names may add up to more)."""
    by_name: dict[str, list] = defaultdict(list)
    for a, b, name, _, beside in tr["spans"]:
        if beside and name.startswith(CTL_PREFIX):
            by_name[name].append((a, b))
    every = _Union([iv for ivs in by_name.values() for iv in ivs])
    each = {name: _Union(ivs) for name, ivs in by_name.items()}
    idle = sum(b - a for a, b in tr["gaps"])
    beside = sum(every.inside(a, b) for a, b in tr["gaps"])
    by_span = {name: sum(u.inside(a, b) for a, b in tr["gaps"]) / 1e9 for name, u in each.items()}
    return {"idle_s": idle / 1e9, "beside_s": beside / 1e9,
            "by_span": dict(sorted(by_span.items(), key=lambda kv: -kv[1]))}


def span_seconds(tr: dict, name: str) -> float | None:
    """Summed seconds of the spans ``name`` inside the device's window (a
    pass runs on one thread at a time, so the sum is its duty); None where
    the trace holds none."""
    found = [(a, b) for a, b, n, _, _ in tr["spans"] if n == name]
    if not found or tr["window"] is None:
        return None
    lo, hi = tr["window"]
    return sum(max(min(b, hi) - max(a, lo), 0.0) for a, b in found) / 1e9


def span_ms(tr: dict, name: str) -> list[float]:
    return [(b - a) / 1e6 for a, b, n, _, _ in tr["spans"] if n == name]


def span_period_ms(tr: dict, name: str) -> float | None:
    """Median distance between consecutive starts of ``name``."""
    starts = sorted(a for a, _, n, _, _ in tr["spans"] if n == name)
    steps = [(y - x) / 1e6 for x, y in zip(starts, starts[1:])]
    return statistics.median(steps) if steps else None


@lru_cache(maxsize=4)
def load(cell_name: str) -> dict | None:
    from jax.profiler import ProfileData

    path = program_trace.find_xplane(cell_name)
    return read(ProfileData.from_file(path)) if path else None


def of_run(run: dict) -> dict | None:
    """For a reader: the traced run's loop and what ran beside it; None where
    the run was not traced or its program writes none of the new names."""
    if not run.get("trace"):
        return None
    return load(run["cell"]["cell"]["name"])


def say(metric: str, **numbers) -> None:
    """What a reader found beside its one value, on an earlier line of
    standard output (the result line stays last)."""
    print(json.dumps({"reader": metric, **numbers}), flush=True)
