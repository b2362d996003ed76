"""What both runners share: the device gate, memory readings, the output
directory, the profiler window, and the last line."""

from __future__ import annotations

import gc
import glob
import json
import os
import sys
import time

from .manifest import BENCH_DIR, load_reader

OUT_DIR = os.path.join(BENCH_DIR, "out")
GIB = 2**30


def rehearsal() -> bool:
    """The CPU rehearsal switch of the harness (never of a measurement): runs
    a cell's control flow at the configuration's ``rehearsal`` sizes."""
    return os.environ.get("ONCHIP_REHEARSAL") == "1"


def gate_devices(chips: int) -> dict:
    """Fail unless JAX holds at least ``chips`` TPU chips (the rehearsal takes
    CPU devices and says so in the line)."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if rehearsal():
        if platform != "cpu":
            raise SystemExit("ONCHIP_REHEARSAL=1 is for the CPU; unset it on the chip")
    elif platform != "tpu":
        raise SystemExit(f"the benchmark needs TPU devices; JAX found {platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"the cell asks {chips} chips; JAX found {len(devices)}")
    if platform == "tpu":
        from .peaks import peaks

        peaks(devices[0].device_kind)  # an unknown kind is an error
    return {"platform": platform, "kind": devices[0].device_kind, "count": len(devices)}


def memory_peak_bytes(chips: int) -> int:
    """Peak on the fullest chip: in-use plus reserved (XLA accounts its
    temporaries as reserved; ``peak_bytes_in_use`` alone misses them)."""
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        s = d.memory_stats() or {}
        peak = max(peak, int(s.get("peak_bytes_in_use", 0)) + int(s.get("peak_bytes_reserved", 0)))
    return peak


def out_path(*parts: str) -> str:
    p = os.path.join(OUT_DIR, *parts)
    os.makedirs(os.path.dirname(p), exist_ok=True)
    return p


class TraceWindow:
    """The profiler over the lead-in (or warm-up) and the first ``seconds`` of
    the measured window. Starting it stalls the calling thread for seconds, so
    it starts before the lead-in; stopping it does too, so a helper thread
    stops it and the load goes on."""

    def __init__(self, run_tag: str, seconds: float):
        self.dir = out_path("trace", run_tag, "x")[:-2]
        self.seconds = seconds
        self.t_start = self.t_stop = self.t_window = None
        self._stopper = None

    def start(self) -> None:
        import jax.profiler

        for old in glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True):
            os.remove(old)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # Python-level tracing slows the very host it watches
        opts.host_tracer_level = 2    # TraceAnnotation spans (onchip.*) stay
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t_start = time.perf_counter()

    def window_opened(self) -> None:
        self.t_window = time.perf_counter()

    def due(self) -> bool:
        return (self.t_window is not None and self.t_stop is None
                and time.perf_counter() - self.t_window >= self.seconds)

    def stop(self) -> None:
        import threading

        import jax.profiler

        if self.t_stop is None and self.t_start is not None:
            self.t_stop = time.perf_counter()
            self._stopper = threading.Thread(target=jax.profiler.stop_trace, name="onchip-trace-stop")
            self._stopper.start()

    def finish(self) -> None:
        self.stop()
        if self._stopper is not None:
            self._stopper.join()

    def xplane(self) -> str | None:
        found = sorted(glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True))
        return found[-1] if found else None


class Marks:
    """Seconds since the process started at each stage of set-up, printed on an
    earlier line so that ``setup_s`` can be split."""

    def __init__(self, t0: float):
        self.t0, self.at = t0, {}

    def __call__(self, name: str) -> None:
        self.at.setdefault(name, round(time.perf_counter() - self.t0, 3))


def quiet_collector() -> None:
    """Before the window: collect what set-up left, then freeze it and switch
    the collector off so that no collection pause falls into the window."""
    gc.collect()
    gc.freeze()
    gc.disable()


def read_layer_metrics(specs: list[dict], run: dict) -> dict:
    """Each per-layer metric's own reader over the run's spans, counters and
    reduced trace; a reader that finds nothing returns None and the metric is
    left out of the line."""
    out = {}
    for spec in specs:
        value = load_reader(spec["name"])(run, spec["name"])
        if value is not None:
            out[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    return out


def sized(cell: dict) -> dict:
    """The cell as it is run: at its files' sizes, or in the CPU rehearsal at
    the ``rehearsal`` sizes the configuration and traffic files carry."""
    if not rehearsal():
        return cell
    return {**cell, "config": {**cell["config"], **cell["config"]["rehearsal"]},
            "traffic": {**cell["traffic"], **cell["traffic"]["rehearsal"]}}


def run_tag(cell: dict, args) -> str:
    return f"{cell['cell']['name']}.seed{args.seed}.trace{args.trace}"


def trace_window(cell: dict, args):
    if not args.trace:
        return None
    return TraceWindow(run_tag(cell, args), min(float(cell["traffic"].get("trace_s", 6.0)), args.seconds))


def assemble(run: dict, args, trace, correct: bool, attempted: int, failed: int,
             end_to_end: dict, rehearsal_counts: dict) -> dict:
    """The result line: the cell's end-to-end metrics (``--trace 0``) or its
    per-layer metrics, read by their own readers from ``run`` and the reduced
    trace (``--trace 1``); the rehearsal reports counts and no device metric.
    ``rehearsal_counts["compared"]`` are the rows of ``check``'s comparison."""
    cell, chips = run["cell"], run["chips"]
    result = {"correct": bool(correct), "attempted": attempted, "failed": failed}
    device = dict(run["device"], memory_peak_bytes=run["peak_bytes"])
    if rehearsal():
        result["metrics"] = {}
        result["rehearsal"] = rehearsal_counts
    elif args.trace:
        from . import trace_reduce

        run["trace"] = red = trace_reduce.reduce(trace.xplane(), chips)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["metrics"] = read_layer_metrics(cell["per_layer"], run)
        result["breakdown"] = red["breakdown"]
    else:
        result["metrics"] = {m["name"]: {"value": end_to_end[m["name"]], "unit": m["unit"]}
                             for m in cell["end_to_end"]}
    result["device"] = device
    # Last in the line: every number compared, beside its limit (inf: nothing was there to compare).
    result["compared"] = {r["number"]: {"value": min(r["value"], sys.float_info.max), "limit": r["limit"]}
                          for r in rehearsal_counts["compared"]}
    return result


def print_result(result: dict) -> None:
    """The numbers compared as the last lines of standard error, then the
    contract's one line, last on standard output."""
    sys.stdout.flush()
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
