"""From a profiler trace (``.xplane.pb``) to numbers, with nothing but
``jax.profiler.ProfileData`` (the program's ``benchmarks/trace_breakdown.py``
needs the image's xprof converter; this does not).

A TPU chip's plane is ``/device:TPU:<n>``; its line ``XLA Ops`` holds one
event per executed HLO operation, serial on the core, and ``XLA Modules`` one
per program run. Host threads are lines of ``/host:CPU``; the program's phase
clock holds a ``tpu_engine.<loop>.<phase>`` annotation there around every
phase of its loops, and the harness's own spans are named ``onchip.<what>``.

- busy: the union of the op intervals of a chip; idle share is 1 - busy over
  the traced window (first to last event on any chip). Averaged over chips.
- a kernel's time: the sum of its events' durations (``tpu_custom_call``).
- idle gaps: the longest complements of busy, each named by the innermost
  program phase that covers its middle on the host (``supervisor.health``,
  ``batcher.admit``); where no phase does, by the innermost harness span
  (``client.wait``), else ``host``. The phases are the loop's that feeds the
  chip; a harness span on another thread is merely concurrent.
"""

from __future__ import annotations

import re
from collections import defaultdict

KERNEL = re.compile(r"custom-call|custom_call|pallas|mosaic", re.I)
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIXES = ("tpu_engine.", "onchip.")  # the program's phases first, then the harness's spans


def _union(intervals):
    """Sorted, merged copy of [(start, end)]."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _device_planes(data):
    planes = [p for p in data.planes if p.name.startswith("/device:TPU:")]
    return planes or [p for p in data.planes if p.name.startswith("/device:")]


def _line(plane, name):
    for ln in plane.lines:
        if ln.name == name:
            return ln
    return None


HLO = re.compile(r"^%?([^ =]+) = \(?([a-z0-9]+\[[0-9,]*\])?")
CONTAINER = re.compile(r"^%?(while|conditional|call)[.\d]* = ")


def _event_label(name: str) -> str:
    """An op event's name is its whole HLO instruction; keep the op's name and
    its (first) result type: ``fusion.197 bf16[16,14336]``. A custom call is
    marked as the kernel it is."""
    m = HLO.match(name)
    if not m:
        return name[:80]
    label = m.group(1) + (" " + m.group(2) if m.group(2) else "")
    return label + (" custom-call" if "custom-call(" in name or "custom_call_target" in name else "")


def reduce(xplane_path: str | None, chips: int) -> dict:
    from jax.profiler import ProfileData

    if not xplane_path:
        raise SystemExit("the traced run left no .xplane.pb")
    data = ProfileData.from_file(xplane_path)
    return reduce_data(data, chips)


def reduce_data(data, chips: int) -> dict:
    planes = _device_planes(data)[:chips] if chips else _device_planes(data)
    if not planes:
        raise SystemExit("the trace has no device plane: no operation ran on the device")
    per_chip, op_time, kernel_time = [], defaultdict(float), defaultdict(float)
    lo, hi = float("inf"), float("-inf")
    gaps_all = []
    module_runs: dict[str, list[tuple[float, float]]] = defaultdict(list)
    for plane in planes:
        ops = _line(plane, OPS_LINE)
        mods = _line(plane, MODULES_LINE)
        src = ops if ops is not None and len(list(ops.events)) else mods
        if src is None:
            continue
        ivs = []
        for ev in src.events:
            a, d = ev.start_ns, ev.duration_ns
            ivs.append((a, a + d))
            if src is ops and not CONTAINER.match(ev.name):  # a while's time is its body's ops'
                label = _event_label(ev.name)
                op_time[label] += d / len(planes)
                if KERNEL.search(label):
                    kernel_time[label] += d / len(planes)
        if mods is not None and plane is planes[0]:
            for ev in mods.events:
                module_runs[ev.name].append((ev.start_ns, ev.start_ns + ev.duration_ns))
        if ivs:
            lo, hi = min(lo, min(a for a, _ in ivs)), max(hi, max(b for _, b in ivs))
        per_chip.append(_union(ivs))
    if not per_chip or hi <= lo:
        raise SystemExit("no operation ran on the device in the traced window")
    busy = [sum(b - a for a, b in u) for u in per_chip]
    window = hi - lo
    spans = _host_spans(data)
    for u in per_chip[:1]:  # gaps of the first chip name the host's doings
        edges = [(lo, lo)] + [tuple(x) for x in u] + [(hi, hi)]
        for (_, e0), (s1, _) in zip(edges, edges[1:]):
            if s1 > e0:
                gaps_all.append((s1 - e0, (e0 + s1) / 2))
    gaps_all.sort(reverse=True)
    idle_gaps = [[_covering(spans, mid), g / 1e9] for g, mid in gaps_all[:10]]
    gap_sizes = sorted(g for g, _ in gaps_all)
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": window / 1e9,
        "chips_traced": len(per_chip),
        "op_seconds": {k: v / 1e9 for k, v in op_time.items()},
        "kernel_seconds": {k: v / 1e9 for k, v in kernel_time.items()},
        "gaps_s": [g / 1e9 for g in gap_sizes],
        "module_runs": {k: [((b - a) / 1e9) for a, b in v] for k, v in module_runs.items()},
        "module_gaps_s": _module_gaps(module_runs),
        "breakdown": {"device_ops": [[k, v / 1e9] for k, v in top_ops], "idle_gaps": idle_gaps},
    }


def _module_gaps(module_runs) -> list[float]:
    """Idle seconds between consecutive program runs on the first chip."""
    runs = sorted(iv for v in module_runs.values() for iv in v)
    return [(nxt[0] - prev[1]) / 1e9 for prev, nxt in zip(runs, runs[1:]) if nxt[0] > prev[1]]


def _host_spans(data):
    spans = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                rank = next((i for i, p in enumerate(SPAN_PREFIXES) if ev.name.startswith(p)), None)
                if rank is not None:
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name[len(SPAN_PREFIXES[rank]):], rank))
    return spans


def _covering(spans, t) -> str:
    covering = [(rank, b - a, name) for a, b, name, rank in spans if a <= t <= b]
    return min(covering)[2] if covering else "host"
