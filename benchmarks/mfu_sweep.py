"""Single-chip MFU sweep: remat policy × loss chunking × micro-batch for
the llama-1b headline config. Each variant runs in a fresh subprocess so
HBM fragmentation / leaked buffers from one config can't skew the next.

Usage: python benchmarks/mfu_sweep.py            # run all variants
       python benchmarks/mfu_sweep.py --one KEY  # child mode (internal)
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import json
import re
import subprocess
import time

VARIANTS: dict[str, dict] = {
    # round-1 headline (51.35% driver-captured)
    "mb8-nothing": dict(micro_batch_size=8, remat_policy="nothing_saveable"),
    "mb8-attn": dict(micro_batch_size=8, remat_policy="save_attn_out"),
    "mb8-qkv": dict(micro_batch_size=8, remat_policy="save_qkv_attn_out"),
    "mb8-dots": dict(micro_batch_size=8, remat_policy="dots_with_no_batch_dims_saveable"),
    "mb8-chunk512": dict(micro_batch_size=8, loss_chunk_size=512),
    "mb12-chunk512": dict(micro_batch_size=12, loss_chunk_size=512),
    "mb16-chunk512": dict(micro_batch_size=16, loss_chunk_size=512),
    "mb16-chunk512-qkv": dict(micro_batch_size=16, loss_chunk_size=512,
                              remat_policy="save_qkv_attn_out"),
    "mb4-noremat": dict(micro_batch_size=4, activation_checkpointing=False),
    "mb6-noremat-chunk512": dict(micro_batch_size=6,
                                 activation_checkpointing=False,
                                 loss_chunk_size=512),
    # bf16 Adam first moment frees ~2 GiB of state at 1B params — the
    # lever that brings the mb8 configs back inside the (tightened)
    # runtime memory envelope.
    "mb8-mubf16": dict(micro_batch_size=8, moment_dtype="bf16"),
    "mb8-mubf16-chunk512": dict(micro_batch_size=8, moment_dtype="bf16",
                                loss_chunk_size=512),
    "mb6-mubf16": dict(micro_batch_size=6, moment_dtype="bf16"),
    "mb4-plain": dict(micro_batch_size=4),
}


def run_one(key: str) -> None:
    import jax

    from tpu_engine.mesh_runtime import MeshConfig, MeshRuntime
    from tpu_engine.models import transformer as tfm
    from tpu_engine.profiler import peak_flops_per_chip
    from tpu_engine.sharding import ShardingStage, TPUTrainConfig
    from tpu_engine.train import build_train_program

    peak = peak_flops_per_chip(jax.devices()[0])
    if peak is None:
        raise SystemExit(f"mfu_sweep needs a TPU, found {jax.devices()[0].platform}")
    over = dict(VARIANTS[key])
    base = dict(
        model_name="llama-1b", sharding_stage=ShardingStage.DISABLED,
        mesh=MeshConfig(data=1), seq_len=2048, attention_impl="auto",
        precision="bf16", activation_checkpointing=True,
    )
    base.update(over)
    cfg = TPUTrainConfig(**base)
    program = build_train_program(cfg, runtime=MeshRuntime(cfg.mesh))
    state = program.init(jax.random.PRNGKey(0))
    batch = program.synthetic_batch(seed=0)
    for _ in range(2):
        state, metrics = program.step(state, batch)
    float(metrics["loss"])
    iters = 10
    t0 = time.perf_counter()
    for _ in range(iters):
        state, metrics = program.step(state, batch)
    float(metrics["loss"])
    dt = (time.perf_counter() - t0) / iters
    accum, gmicro, seq = program.global_batch_shape()
    tps = accum * gmicro * seq / dt
    fpt = tfm.train_flops_per_token(program.model_config, cfg.seq_len)
    print(json.dumps({
        "variant": key, "mfu_pct": round(100 * tps * fpt / peak, 2),
        "tokens_per_sec": round(tps, 1), "step_ms": round(dt * 1e3, 1),
    }))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--one")
    args = ap.parse_args()
    if args.one:
        run_one(args.one)
        return 0
    # The parent never imports jax: a process that has touched JAX holds the
    # chip, and each variant's child needs it.
    for key in VARIANTS:
        out = subprocess.run(
            [sys.executable, __file__, "--one", key],
            capture_output=True, text=True, timeout=900, env=os.environ,
        )
        if out.returncode == 0:
            print(out.stdout.strip().splitlines()[-1], flush=True)
            continue
        # A variant that does not fit ("Ran out of memory") is a sweep
        # result, reported as such; nothing is retried.
        err = out.stderr + out.stdout
        m = re.search(r"Ran out of memory[^\n]*", err)
        m2 = re.search(r"\w+Error: [^\n]*", err)
        short = (m.group(0) if m else m2.group(0) if m2 else err[-180:])[:180]
        print(json.dumps({"variant": key, "error": short}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
