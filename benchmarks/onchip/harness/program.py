"""The seam between the benchmark and the program: everything the harness does
TO ``tpu_engine`` is here, so a reader can see that it is only the system
under test, its spans and its counters.

- a configuration file becomes a ``ModelConfig`` registered under the
  configuration's name, through its family's file (``families/<family>.py``,
  where the family is ``manifest.family_of``'s: the ``family`` the file states,
  else the ``model_type`` it publishes: published widths, the depth the file
  states);
- the worker's start-up sequence, as ``chip_smoke.start_up`` runs it, with the
  compile cache pointed inside the checkout;
- engine-side token and dispatch timestamps, after
  ``benchmarks/serving_latency.py::_TimestampingBatcher`` (there a subclass
  built by hand; here the same two overrides installed on the class, because
  the fleet builds its own engine and the benchmark goes through the fleet);
- a count of compilations, from ``jax.monitoring``.
"""

from __future__ import annotations

import contextlib
import os
import time

from .manifest import BENCH_DIR, family_of, load_by_name

CACHE_DIR = os.path.join(BENCH_DIR, ".cache")
MAX_SEED = 2**32 - 5


def seed32(seed: int) -> int:
    """``--seed`` may exceed what a PRNG key takes; fold it, keeping small
    seeds as they are."""
    return int(seed) % MAX_SEED


def prepare_environment() -> None:
    """Before jax is imported: the compile cache lives inside the checkout at
    a fixed path unless the machine names one; quiet TPU logs."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", CACHE_DIR)
    os.environ.setdefault("TPU_STDERR_LOG_LEVEL", "2")
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")


def model_config(config: dict, name: str):
    """The configuration file as a program ``ModelConfig``, registered under
    the configuration's name. Which published keys become which fields is the
    family's business: ``families/<family>.py``, found by ``family_of``.
    Nothing here knows a family."""
    from tpu_engine.models import transformer as tfm

    family = family_of(config)
    mc = load_by_name("families", family).model_config(config, name)
    if mc.name != name:
        raise ValueError(f"families/{family}.py named its ModelConfig {mc.name!r}, not {name!r}")
    tfm.MODEL_CONFIGS[name] = mc
    return mc


def start_up(train_cfg) -> dict:
    """The worker CLI's start-up calls in the worker's order; the persistent
    compile cache takes every program, however quickly it compiled, so that a
    second run of a cell compiles nothing."""
    import jax

    from tpu_engine import compile_cache
    from tpu_engine.comm import apply_comm_flags, comm_flags_status
    from tpu_engine.mesh_runtime import initialize_distributed

    apply_comm_flags(train_cfg)
    initialize_distributed()
    n_dev = len(jax.devices())
    cache = compile_cache.enable_compilation_cache()
    if cache:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return {"n_devices": n_dev, "comm_flags": comm_flags_status(train_cfg),
            "cache_dir": cache.dir, "cache_skipped": cache.skipped_reason}


class CompileCounter:
    """Counts what ``jax.monitoring`` reports: programs lowered (every new
    program, cached or not), backend compilations (cache misses) and
    persistent-cache hits. ``mark()`` then ``since_mark()`` give the window's."""

    EVENTS = {
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowered",
        "/jax/core/compile/backend_compile_duration": "backend_compiles",
        "/jax/compilation_cache/cache_retrieval_time_sec": "cache_hits",
    }

    def __init__(self):
        import jax.monitoring

        self.counts = {v: 0 for v in self.EVENTS.values()}
        self._mark = dict(self.counts)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        key = self.EVENTS.get(event)
        if key:
            self.counts[key] += 1

    def mark(self) -> None:
        self._mark = dict(self.counts)

    def since_mark(self) -> dict:
        return {k: self.counts[k] - self._mark[k] for k in self.counts}


class BatcherShim:
    """Engine-side timestamps without touching the product: wraps
    ``ContinuousBatcher._emit`` (one ``perf_counter`` stamp per emitted token,
    by engine request id) and ``ContinuousBatcher.step`` (one boundary per
    dispatch with the tokens it produced and the slots in use)."""

    def __init__(self):
        self.emit_times: dict[int, list[float]] = {}
        self.step_ends: list[float] = []
        self.step_tokens: list[int] = []
        self.step_active: list[int] = []
        self.step_context: list[int] = []
        self.tamper = None  # tests: a function token -> token, where tokens are produced
        self._orig = None

    def install(self) -> None:
        import jax.profiler

        from tpu_engine.serving import ContinuousBatcher

        shim = self
        orig_emit, orig_step = ContinuousBatcher._emit, ContinuousBatcher.step
        self._orig = (ContinuousBatcher, orig_emit, orig_step)

        def _emit(engine, req, slot, tok):
            shim.emit_times.setdefault(req.id, []).append(time.perf_counter())
            if shim.tamper is not None:
                tok = shim.tamper(tok)
            return orig_emit(engine, req, slot, tok)

        def step(engine):
            with jax.profiler.TraceAnnotation("onchip.batcher.step"):
                n = orig_step(engine)
            live = [r for r in engine._slots if r is not None]
            shim.step_ends.append(time.perf_counter())
            shim.step_tokens.append(int(n or 0))
            shim.step_active.append(len(live))
            shim.step_context.append(sum(len(r.prompt) + len(r.tokens) for r in live))
            return n

        ContinuousBatcher._emit, ContinuousBatcher.step = _emit, step

    def uninstall(self) -> None:
        if self._orig is not None:
            cls, emit, step = self._orig
            cls._emit, cls.step = emit, step
            self._orig = None


@contextlib.contextmanager
def host_span(name: str):
    """A host span on the profiler's own clock (``onchip.<name>``), so that the
    trace reducer can say what the host was doing in a device-idle gap."""
    import jax.profiler

    with jax.profiler.TraceAnnotation("onchip." + name):
        yield
