"""chip_smoke.py's phases on the 8-virtual-device CPU mesh with gpt-tiny —
the same functions, the same assertions (what only a chip can show, the
Mosaic kernel and memory_stats, is asserted on TPU devices only) — and the
script's refusal to run anywhere but on a TPU."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_phases_on_the_cpu_mesh():
    from tpu_engine.mesh_runtime import MeshConfig
    from tpu_engine.sharding import Precision, ShardingStage, TPUTrainConfig

    cfg = TPUTrainConfig(
        model_name="gpt-tiny",
        mesh=MeshConfig(data=2, fsdp=4),
        sharding_stage=ShardingStage.FULL_PARTITIONING,
        micro_batch_size=1, gradient_accumulation_steps=1, seq_len=64,
        precision=Precision.BF16, attention_impl="auto",
        learning_rate=1e-2, warmup_steps=1,
    )
    up = chip_smoke.start_up(cfg)
    assert up["n_devices"] == 8
    # A long-lived test process: the backend was up before the flags could
    # be delivered, and the start-up says so.
    assert up["comm_flags"]["in_force"] is False
    assert up["cache_skipped"] == "cpu-backend"

    launcher, job_id, train = chip_smoke.phase_train(cfg, steps=4)
    assert train["attention_impl"] == "xla" and train["steps"] == 4
    assert train["losses"][-1] < train["losses"][0]
    assert len(train["state_gib_per_device"]) == 8  # sharded over every device
    assert train["seconds_to_first_step"] > 0 and train["median_step_s_steady"] > 0

    released = chip_smoke.phase_release(launcher, job_id)
    assert released["reserved_hbm_gib"] == 0.0
    assert launcher.get_job(job_id) is None

    serve = chip_smoke.phase_serve(
        launcher, "gpt-tiny", max_slots=2, max_len=64,
        prompt_lens=[8, 8, 16, 16], max_new_tokens=8, timeout_s=300.0,
    )
    assert serve["completed"] == 4 and serve["failed"] == 0
    assert serve["tokens_total"] == 32
    launcher.scheduler.shutdown()


def test_script_refuses_to_run_off_tpu():
    """No CPU mode, no flag that enables one: on the CPU backend the
    script exits non-zero and prints no result line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert "needs TPU devices" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_script_takes_no_arguments_and_starts_no_children():
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        src = f.read()
    for needle in ("argparse", "sys.argv", "subprocess", "multiprocessing",
                   "os.fork", "os.system", "Popen", "os.environ.get", "getenv"):
        assert needle not in src, needle
    assert "except " not in src and "except:" not in src


def test_bench_parents_that_start_children_stay_off_jax():
    """One process per chip: a parent that has touched JAX holds the chip
    and its children then fail or hang. A script under ``benchmarks/`` (or
    ``bench.py``) that starts children must import neither jax nor
    tpu_engine (which imports jax) in the parent. None of those that are
    left starts any; one that comes to is held to it here."""
    import glob

    scripts = [os.path.join(REPO, "bench.py")] + sorted(
        glob.glob(os.path.join(REPO, "benchmarks", "*.py")))
    assert scripts[1:], "benchmarks/*.py not found"
    parents = []
    for path in scripts:
        with open(path) as f:
            src = f.read()
        if "subprocess" in src or "multiprocessing" in src:
            parents.append(os.path.splitext(os.path.basename(path))[0])
    code = (
        "import sys; sys.path[:0] = ['.', 'benchmarks']; "
        f"[__import__(m) for m in {parents!r}]; "
        "bad = [m for m in ('jax', 'jaxlib', 'tpu_engine') if m in sys.modules]; "
        "sys.exit(f'parent imported {bad}' if bad else 0)"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr
