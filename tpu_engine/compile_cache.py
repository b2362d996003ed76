"""Persistent XLA compilation cache.

SURVEY.md §7 hard part (c): MTTR < 90 s auto-resume needs warm-start
compilation — a preempted worker that restarts must not pay the full
multi-minute XLA compile again. JAX's persistent compilation cache keys
compiled executables by (HLO, compile options, libtpu version) and reuses
them across processes, so the supervisor's resume path costs restore + one
*cache hit* instead of restore + cold compile.

Enabled by the worker CLI and by every supervised job
(``tpu_engine/supervisor.py``); idempotent and safe to call at any point —
JAX consults the cache per compilation, not at backend init. The fleet-level
warm/cold bookkeeping over this cache lives in
``tpu_engine/compile_index.py`` — enabling here attaches that index's JSON
sidecar to the cache dir.

The directory is placed from OUTSIDE the program, by one rule
(:func:`resolve_cache_dir`): ``JAX_COMPILATION_CACHE_DIR`` when set, else a
fixed directory inside the checkout. No argument, config field or code path
points the cache anywhere else — the path is part of how a deployment keeps
its cache across restarts, and a directory that moves never hits.
"""

from __future__ import annotations

import logging
import os
from dataclasses import dataclass
from typing import Optional

log = logging.getLogger(__name__)

# Fixed, inside the checkout (git-ignored): never under ~, never built from
# a temporary name, a pid or the time.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)

_enabled_dir: Optional[str] = None


def resolve_cache_dir() -> str:
    """The one cache directory: ``JAX_COMPILATION_CACHE_DIR`` if set (e.g.
    by infra/tpu-jobset.yaml onto a persistent volume), else the fixed
    in-checkout default."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


@dataclass(frozen=True)
class CacheEnableResult:
    """Structured outcome of :func:`enable_compilation_cache`.

    ``dir`` is the directory the cache is active with after this call (None
    when nothing is enabled); ``changed`` means this call touched JAX
    config; ``skipped_reason`` names why the call was a no-op (currently
    only ``"cpu-backend"``). Truthiness is "the cache is enabled".
    """

    dir: Optional[str]
    enabled: bool
    changed: bool = False
    skipped_reason: Optional[str] = None

    def __bool__(self) -> bool:
        return self.enabled


def enable_compilation_cache(force: bool = False) -> CacheEnableResult:
    """Point JAX's persistent compilation cache at :func:`resolve_cache_dir`
    (idempotent). The thresholds are lowered so the train step (which takes
    seconds to minutes to compile) always qualifies, while trivial
    sub-second compiles stay out of the cache.

    NOT enabled on the CPU backend unless ``force``: XLA:CPU AOT reloads
    are compiled with machine-feature sets that do not round-trip
    (``cpu_aot_loader`` warns of possible SIGILL, and hard interpreter
    crashes were observed in the CPU test mesh). The cache's purpose —
    warm TPU restarts — does not apply there anyway.
    """
    global _enabled_dir
    d = resolve_cache_dir()
    if _enabled_dir == d:
        return CacheEnableResult(dir=d, enabled=True)
    import jax

    if not force and jax.default_backend() == "cpu":
        log.info("CPU backend: persistent compilation cache not enabled")
        return CacheEnableResult(
            dir=_enabled_dir,
            enabled=_enabled_dir is not None,
            skipped_reason="cpu-backend",
        )

    os.makedirs(d, exist_ok=True)
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if prev != d:
        # Two latches make a plain config update insufficient: the cache
        # object binds to the directory it was first used with, and
        # ``is_cache_used`` memoizes a cache-OFF verdict at the process's
        # FIRST compile — so enabling after any earlier jit (telemetry
        # probe, eval_shape warm-up) would silently cache nothing.
        from jax._src import compilation_cache as _cc

        _cc.reset_cache()
    _enabled_dir = d
    log.info("persistent XLA compilation cache: %s", d)
    # The fleet compile index persists its layout-keyed sidecar next to the
    # executables it describes — warmth then survives the process.
    try:
        from tpu_engine.compile_index import get_index

        get_index().attach_dir(d)
    except Exception:  # the index must never break cache enablement
        log.debug("compile index sidecar attach failed", exc_info=True)
    return CacheEnableResult(dir=d, enabled=True, changed=True)


def cache_dir_in_use() -> Optional[str]:
    """The directory the cache was enabled with, or None."""
    return _enabled_dir
