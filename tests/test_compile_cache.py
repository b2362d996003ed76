"""Persistent XLA compilation cache (SURVEY.md §7 hard part c — warm-start
compiles bound resume MTTR): structured enable results, the one placement
rule (``JAX_COMPILATION_CACHE_DIR`` else a fixed in-checkout directory),
CPU-backend exclusion and the cache-unused latch."""

import os
import tempfile

import jax
import jax.numpy as jnp
import pytest

from tpu_engine import compile_cache, compile_index
from tpu_engine.compile_cache import CacheEnableResult


@pytest.fixture(autouse=True)
def _fresh_index(monkeypatch):
    """Each test gets a pristine process-wide compile index — the enable
    path attaches the index sidecar to the cache dir as a side effect —
    and a not-yet-enabled cache."""
    compile_index.reset_index()
    monkeypatch.setattr(compile_cache, "_enabled_dir", None)
    yield
    compile_index.reset_index()


def test_enable_populates_cache(tmp_path, monkeypatch):
    d = str(tmp_path / "xla-cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    # force=True: the CPU test backend is normally excluded (see below).
    res = compile_cache.enable_compilation_cache(force=True)
    assert res.dir == d and res.enabled and res.changed
    assert res.skipped_reason is None
    # Lower the threshold so this test's trivial compile qualifies.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    f(jnp.ones((64, 64))).block_until_ready()
    assert os.listdir(d), "no cache entries written"
    # Idempotent re-enable keeps the directory and reports changed=False.
    again = compile_cache.enable_compilation_cache(force=True)
    assert again.dir == d and again.enabled and not again.changed
    assert compile_cache.cache_dir_in_use() == d
    # Enabling attached the fleet index's sidecar next to the executables.
    assert compile_index.get_index().stats()["sidecar_path"] == os.path.join(
        d, compile_index.SIDECAR_NAME
    )


def test_env_places_the_cache_and_nothing_overrides_it(tmp_path, monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` set ⇒ that directory, and no argument
    or config field exists that could point the cache anywhere else."""
    import inspect

    from tpu_engine.sharding import TPUTrainConfig

    d = str(tmp_path / "from-env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    assert compile_cache.resolve_cache_dir() == d
    assert compile_cache.enable_compilation_cache(force=True).dir == d
    assert os.path.isdir(d)
    assert jax.config.jax_compilation_cache_dir == d
    assert list(
        inspect.signature(compile_cache.enable_compilation_cache).parameters
    ) == ["force"]
    assert not [f for f in TPUTrainConfig.model_fields if "cache" in f]


def test_default_is_a_fixed_path_inside_the_checkout(monkeypatch):
    """Env unset ⇒ ``<repo>/.jax_cache``: the same path in every process
    (the path is part of the cache key's locality — a directory that moves
    never hits), never under ~ or the temp dir, no pid, no timestamp."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    d = compile_cache.resolve_cache_dir()
    assert d == os.path.join(repo, ".jax_cache") == compile_cache.DEFAULT_CACHE_DIR
    assert os.path.dirname(d) == repo  # inside the checkout, not under ~
    assert not d.startswith(tempfile.gettempdir() + os.sep)
    assert str(os.getpid()) not in d
    assert not any(ch.isdigit() for ch in os.path.relpath(d, repo))
    # The git-ignore rule that keeps it out of commits exists.
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cpu_backend_is_excluded_by_default(tmp_path, monkeypatch):
    """XLA:CPU AOT reloads don't round-trip machine features (observed
    interpreter SIGILLs in the CPU test mesh) — the cache only enables on
    accelerator backends unless forced. The skip is a structured result,
    falsy and naming its reason."""
    d = str(tmp_path / "cpu-skip")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    res = compile_cache.enable_compilation_cache()
    assert isinstance(res, CacheEnableResult)
    assert not res  # nothing enabled → falsy
    assert res.dir is None
    assert res.skipped_reason == "cpu-backend"
    assert not os.path.exists(d)
    assert compile_cache.cache_dir_in_use() is None


def test_cpu_skip_preserves_prior_enable(tmp_path, monkeypatch):
    """A later un-forced call on CPU must not disturb an earlier forced
    enable: the result still reports the active dir and stays truthy."""
    d = str(tmp_path / "forced")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    assert compile_cache.enable_compilation_cache(force=True).dir == d
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "other"))
    res = compile_cache.enable_compilation_cache()
    assert res.skipped_reason == "cpu-backend"
    assert res and res.dir == d  # prior enable intact
    assert compile_cache.cache_dir_in_use() == d


def test_supervisor_enables_without_crashing(tmp_path, monkeypatch):
    """The supervised job's enable call is a safe no-op on the CPU backend
    (and points the cache at the resolved dir on TPU)."""
    from tpu_engine.mesh_runtime import MeshConfig
    from tpu_engine.sharding import Precision, ShardingStage, TPUTrainConfig
    from tpu_engine.supervisor import TrainingJob

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "job-cache"))
    cfg = TPUTrainConfig(
        model_name="gpt-tiny",
        sharding_stage=ShardingStage.DISABLED,
        mesh=MeshConfig(data=8),
        micro_batch_size=1,
        seq_len=16,
        precision=Precision.FP32,
        activation_checkpointing=False,
    )
    job = TrainingJob("cache-test", cfg, max_steps=1)
    job.start()
    job.join(timeout=300)
    assert job.status.value == "completed", job.error
    # CPU backend: skipped by design.
    assert compile_cache.cache_dir_in_use() is None


def test_enable_after_prior_compile_still_caches(tmp_path, monkeypatch):
    """JAX memoizes a cache-unused verdict at the process's FIRST compile
    (``is_cache_used``): a worker that jitted anything before calling
    ``enable_compilation_cache`` — telemetry probe, eval_shape warm-up —
    would silently get no cache. Enabling must clear the latch."""
    from jax._src import compilation_cache as _cc

    jax.config.update("jax_compilation_cache_dir", None)
    _cc.reset_cache()  # pristine: no verdict yet
    # First compile with no dir configured latches the cache-OFF verdict.
    jax.jit(lambda x: x * 2)(jnp.ones(4)).block_until_ready()

    d = str(tmp_path / "late-enable")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    assert compile_cache.enable_compilation_cache(force=True).dir == d
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.jit(lambda x: jnp.cos(x @ x).sum())(
        jnp.ones((32, 32))
    ).block_until_ready()
    assert os.listdir(d), "cache-unused latch survived enable"
