"""The program's own names in a traced run's ``.xplane.pb``: what
``trace_reduce`` (whose gaps are named by the harness's ``onchip.*`` spans)
does not read.

- the ``tpu_engine.<loop>.<phase>`` host annotations the program's phase clock
  (``tpu_engine/profiler.py::StepProfiler.phase``) holds around every phase of
  the supervisor loop and of ``ContinuousBatcher.step``, with their arguments
  (``step=``, ``rid=``, ``slot=``, ``chunk=``);
- the first chip's idle time — the complement of the union of its op
  intervals, as ``trace_reduce`` computes busy — split by the innermost
  program phase that covers it, and the part no phase covers;
- device seconds by ``jax.named_scope``: a v5e trace carries an op's scope
  path nowhere ``ProfileData`` shows (an event's name is its HLO instruction
  without metadata, its stats are offsets) but in the ``tf_op`` stat of the
  op's *event metadata*, ``jit(decode_chunk)/while/body/attn/decode_attn/dot_general:``
  (looked at with ``tools/dump_trace.py`` and the raw proto, PR 24). So the
  few fields that hold it are decoded from the file's bytes here
  (``op_scopes``); an op counts under every name on its path.

A parent commit's program has no such annotation and no such scope: every
function here then returns empty numbers and the readers return None. The
file is parsed once a process (``load``).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from functools import lru_cache

from . import trace_reduce
from .manifest import BENCH_DIR

PREFIX = "tpu_engine."


def find_xplane(cell_name: str) -> str | None:
    """The newest trace of a ``--trace 1`` run of this cell in this checkout."""
    pattern = os.path.join(BENCH_DIR, "out", "trace", cell_name + ".seed*.trace1", "**", "*.xplane.pb")
    found = sorted(glob.glob(pattern, recursive=True), key=os.path.getmtime)
    return found[-1] if found else None


def annotations(data) -> list[tuple[float, float, str, dict]]:
    """(start_ns, end_ns, "<loop>.<phase>", arguments) of every program
    annotation on a host plane, by start."""
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name.startswith(PREFIX):
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name[len(PREFIX):],
                                {k: str(v) for k, v in ev.stats}))
    out.sort(key=lambda a: (a[0], a[1]))
    return out


def innermost(spans) -> list[tuple[float, float, str]]:
    """Disjoint (t0, t1, name) pieces: at every instant some span covers, the
    covering span that began last (the innermost of nested ones)."""
    edges = sorted({t for a, b, *_ in spans for t in (a, b)})
    pieces, active, i = [], [], 0
    for t0, t1 in zip(edges, edges[1:]):
        while i < len(spans) and spans[i][0] <= t0:
            active.append(spans[i])
            i += 1
        active = [s for s in active if s[1] > t0]
        if active:
            name = max(active, key=lambda s: (s[0], -s[1]))[2]
            if pieces and pieces[-1][2] == name and pieces[-1][1] == t0:
                pieces[-1] = (pieces[-1][0], t1, name)
            else:
                pieces.append((t0, t1, name))
    return pieces


def first_chip_gaps(data) -> tuple[list[tuple[float, float]], float]:
    """The first chip's idle intervals inside its traced window (first to
    last op), and its busy nanoseconds."""
    planes = trace_reduce._device_planes(data)
    if not planes:
        return [], 0.0
    line = trace_reduce._line(planes[0], trace_reduce.OPS_LINE)
    if line is None or not len(list(line.events)):
        line = trace_reduce._line(planes[0], trace_reduce.MODULES_LINE)
    if line is None:
        return [], 0.0
    busy = trace_reduce._union([(ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events])
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]
    return gaps, float(sum(b - a for a, b in busy))


def idle_by_phase(data) -> dict:
    """{"idle_s", "unnamed_s", "by_phase": {"<loop>.<phase>": seconds}}"""
    gaps, _ = first_chip_gaps(data)
    pieces = innermost(annotations(data))
    starts = [p[0] for p in pieces]
    by_phase: dict[str, float] = defaultdict(float)
    idle = 0.0
    for g0, g1 in gaps:
        idle += g1 - g0
        k = max(bisect.bisect_right(starts, g0) - 1, 0)
        while k < len(pieces) and pieces[k][0] < g1:
            p0, p1, name = pieces[k]
            if min(p1, g1) > max(p0, g0):
                by_phase[name] += min(p1, g1) - max(p0, g0)
            k += 1
    named = sum(by_phase.values())
    return {"idle_s": idle / 1e9, "unnamed_s": (idle - named) / 1e9,
            "by_phase": {k: v / 1e9 for k, v in sorted(by_phase.items(), key=lambda kv: -kv[1])}}


STRUCTURE = {"main", "while", "body", "cond", "jvp", "transpose", "vmap", "pjit", "closed_call", "checkpoint",
             "rematted_computation", "custom_jvp_call", "custom_vjp_call", "branch_0_fun", "branch_1_fun"}
WRAPPED = re.compile(r"[A-Za-z_]+\((.*)\)$")


def scope_path(tf_op: str) -> list[str]:
    """The names on an op's ``tf_op`` path, outermost first: the jitted
    program and the ``jax.named_scope`` s, without the wrappers autodiff and
    control flow put around them (``transpose(jvp(attn))`` is ``attn``) and
    without the primitive at the end."""
    names = []
    for part in tf_op.rstrip(":").split("/")[:-1]:
        while (w := WRAPPED.match(part)):
            part = w.group(1)
        if part and part not in STRUCTURE:
            names.append(part)
    return names


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return val, i


def _fields(buf: bytes):
    """(field number, wire type, value) of one protobuf message: varints as
    ints, length-delimited fields as bytes; fixed-width fields are skipped."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield field, wire, val
        elif wire == 2:
            size, i = _varint(buf, i)
            yield field, wire, buf[i:i + size]
            i += size
        else:
            i += 8 if wire == 1 else 4


def op_scopes(xspace: bytes) -> dict[str, str]:
    """{op event name: its ``tf_op`` path} of the first TPU plane, from the
    serialized ``XSpace``: planes = 1; XPlane name = 2, event_metadata = 4,
    stat_metadata = 5 (maps: key = 1, value = 2); XEventMetadata name = 2,
    stats = 5; XStat metadata_id = 1, str_value = 5, ref_value = 7;
    XStatMetadata name = 2 (tsl/profiler/protobuf/xplane.proto)."""
    for f, _, plane in _fields(xspace):
        if f != 1:
            continue
        top = list(_fields(plane))
        name = next((v for g, _, v in top if g == 2), b"")
        if not name.startswith(b"/device:TPU:"):
            continue
        stat_names = {}
        for g, _, entry in top:
            if g == 5:
                kv = dict((h, v) for h, _, v in _fields(entry))
                stat_names[kv.get(1, 0)] = next((v for h, _, v in _fields(kv.get(2, b"")) if h == 2), b"")
        tf_op = next((k for k, v in stat_names.items() if v == b"tf_op"), None)
        scopes = {}
        for g, _, entry in top:
            if g != 4 or tf_op is None:
                continue
            meta = next((v for h, _, v in _fields(entry) if h == 2), b"")
            ev_name, path = b"", None
            for h, _, v in _fields(meta):
                if h == 2:
                    ev_name = v
                elif h == 5:
                    stat = dict((k, x) for k, _, x in _fields(v))
                    if stat.get(1) == tf_op:
                        path = stat[5] if 5 in stat else stat_names.get(stat.get(7), b"")
            if path:
                scopes[ev_name.decode("utf-8", "replace")] = path.decode("utf-8", "replace")
        return scopes
    return {}


def scope_seconds(data, scopes: dict[str, str]) -> dict:
    """{"busy_s", "by_scope": {scope: seconds}, "unscoped_s"} of the first
    chip's op line, ``scopes`` being ``op_scopes`` of the same trace; a
    ``while``'s own event is left out (its time is its body's ops'), as in
    ``trace_reduce``."""
    planes = trace_reduce._device_planes(data)
    line = trace_reduce._line(planes[0], trace_reduce.OPS_LINE) if planes else None
    by_scope: dict[str, float] = defaultdict(float)
    unscoped = 0.0
    if line is not None:
        for ev in line.events:
            if trace_reduce.CONTAINER.match(ev.name):
                continue
            path = scope_path(scopes.get(ev.name, ""))
            for scope in set(path):
                by_scope[scope] += ev.duration_ns
            if not path:
                unscoped += ev.duration_ns
    _, busy = first_chip_gaps(data)
    return {"busy_s": busy / 1e9, "unscoped_s": unscoped / 1e9,
            "by_scope": {k: v / 1e9 for k, v in sorted(by_scope.items(), key=lambda kv: -kv[1])}}


def read(xspace: bytes) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_serialized_xspace(xspace)
    return {"annotations": annotations(data), "idle": idle_by_phase(data),
            "scopes": scope_seconds(data, op_scopes(xspace))}


@lru_cache(maxsize=4)
def load(cell_name: str) -> dict | None:
    """Everything above for the cell's newest traced run, or None where this
    checkout holds no trace of it."""
    path = find_xplane(cell_name)
    if path is None:
        return None
    with open(path, "rb") as f:
        return read(f.read())


def of_run(run: dict) -> dict | None:
    """For a reader: the parsed trace of the run it is reading, only when that
    run was traced (``run["trace"]`` is the reducer's output)."""
    if not run.get("trace"):
        return None
    return load(run["cell"]["cell"]["name"])


def scope_share_pct(run: dict, scope: str) -> float | None:
    """Share of the first chip's busy time in ops under ``scope``; None where
    the run was not traced or its program has no such scope."""
    tr = of_run(run)
    if not tr or scope not in tr["scopes"]["by_scope"]:
        return None
    return 100.0 * tr["scopes"]["by_scope"][scope] / tr["scopes"]["busy_s"]


def phase_ms_per_step(run: dict, phase: str) -> float | None:
    """Mean host milliseconds one iteration of a loop spends in ``phase``
    (``"batcher.admit"``) over the traced window: the summed time of the
    phase's annotations over the number of iterations (each holds one
    ``<loop>.other`` annotation from begin to begin). The mean, because a
    phase that is nothing in most steps and 20 ms at a request's turnover
    costs its mean, and its median hides that. None where the run was not
    traced or its program holds no such annotation."""
    tr = of_run(run)
    if not tr:
        return None
    steps = sum(1 for *_, name, _ in tr["annotations"] if name == phase.split(".")[0] + ".other")
    spent = [t1 - t0 for t0, t1, name, _ in tr["annotations"] if name == phase]
    return sum(spent) / 1e6 / steps if steps and spent else None


def request_stage_ms(run: dict, stage: str) -> list[float]:
    """Milliseconds of the flight recorder's ``stage`` spans (``engine_queue``,
    ``prefill_wait``, ``prefill``, ``decode``: the children the fleet records
    under a request's span from the engine's own stamps) of the requests the
    harness counted for ``ttft_mean_ms``. An open-loop run sends its
    unmeasured lead-in first and nothing after its measured requests, and
    ``run["ttft_ms"]`` holds one entry for each of those, so they are the last
    that many requests to reach an engine."""
    from tpu_engine import tracing

    n = len(run.get("ttft_ms") or [])
    if run.get("loop") != "open" or not n:
        return []
    spans = [s for s in tracing.get_recorder().spans(kind="serving", limit=0) if s["t1"] is not None]
    reached = sorted((s for s in spans if s["name"] == "engine_queue"), key=lambda s: s["t0"])
    counted = {s["trace_id"] for s in reached[-n:]}
    return [(s["t1"] - s["t0"]) * 1e3 for s in spans if s["name"] == stage and s["trace_id"] in counted]
