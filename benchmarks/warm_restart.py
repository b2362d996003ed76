"""Cold vs warm resume-to-first-step on the real chip.

Measures the MTTR compile component the persistent XLA compilation cache
removes (SURVEY.md §7 hard part c): two fresh processes build the same
train program and run one step — the first with an empty cache (cold), the
second reusing it (warm). Prints one JSON line per phase and a summary.

Usage (on a TPU host):  python benchmarks/warm_restart.py [--model llama-1b]
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import json
import shutil
import subprocess

_CHILD = r"""
import json, os, time
t0 = time.perf_counter()
import jax
from tpu_engine.compile_cache import enable_compilation_cache
from tpu_engine.mesh_runtime import MeshConfig
from tpu_engine.sharding import ShardingStage, TPUTrainConfig
from tpu_engine.train import build_train_program

enable_compilation_cache()  # JAX_COMPILATION_CACHE_DIR, set by the parent
cfg = TPUTrainConfig(
    model_name=os.environ.get("WARM_RESTART_MODEL", "llama-1b"),
    sharding_stage=ShardingStage.FULL_PARTITIONING,
    mesh=MeshConfig(data=1, fsdp=jax.device_count()),
    micro_batch_size=int(os.environ.get("WARM_RESTART_BATCH", "4")),
    seq_len=int(os.environ.get("WARM_RESTART_SEQ", "2048")),
)
t_import = time.perf_counter()
prog = build_train_program(cfg)
state = prog.init(jax.random.PRNGKey(0))
jax.block_until_ready(state)
t_init = time.perf_counter()
batch = prog.synthetic_batch(0)
state, metrics = prog.step(state, batch)
jax.block_until_ready(metrics)
t_first_step = time.perf_counter()
print(json.dumps({
    "import_s": round(t_import - t0, 2),
    "init_s": round(t_init - t_import, 2),
    "first_step_s": round(t_first_step - t_init, 2),
    "resume_to_first_step_s": round(t_first_step - t0, 2),
}))
"""


def run_child(cache_dir: str, model: str, batch: int, seq: int) -> dict:
    env = dict(os.environ)
    env.update(
        JAX_COMPILATION_CACHE_DIR=cache_dir,
        WARM_RESTART_MODEL=model,
        WARM_RESTART_BATCH=str(batch),
        WARM_RESTART_SEQ=str(seq),
    )
    out = subprocess.run(
        [sys.executable, "-c", _CHILD], env=env, capture_output=True, text=True,
        timeout=1800,
    )
    if out.returncode != 0:
        raise RuntimeError(f"child failed:\n{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="llama-1b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--keep-cache", action="store_true")
    args = ap.parse_args()

    # A fixed sub-directory of wherever the cache is placed (the path is
    # part of what a cache entry is found by), emptied first so the first
    # child is cold. The parent resolves it by hand: importing tpu_engine
    # would initialise JAX here and take the chip from the children.
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cache = os.path.join(
        os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or os.path.join(repo, ".jax_cache"),
        "warm_restart",
    )
    shutil.rmtree(cache, ignore_errors=True)
    os.makedirs(cache)
    try:
        cold = run_child(cache, args.model, args.batch, args.seq)
        print(json.dumps({"phase": "cold", **cold}))
        warm = run_child(cache, args.model, args.batch, args.seq)
        print(json.dumps({"phase": "warm", **warm}))
        speedup = (
            cold["resume_to_first_step_s"] / warm["resume_to_first_step_s"]
            if warm["resume_to_first_step_s"] > 0
            else float("inf")
        )
        print(json.dumps({
            "metric": "warm_restart_resume_to_first_step",
            "model": args.model,
            "cold_s": cold["resume_to_first_step_s"],
            "warm_s": warm["resume_to_first_step_s"],
            "speedup": round(speedup, 2),
        }))
    finally:
        if not args.keep_cache:
            shutil.rmtree(cache, ignore_errors=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
