"""Benchmark harness: steady-state training throughput + MFU on real hardware.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

The reference publishes no performance numbers (BASELINE.md), so
``vs_baseline`` is measured MFU divided by the BASELINE.json north-star
target of 45% MFU (>= 1.0 beats the target).

The 7B north-star model does not fit one chip for training (~84 GB of
master+optimizer state), so the bench trains the largest model that does —
llama-1b on a 16 GB-HBM chip — through the exact code path the 7B multi-chip
run uses (sharded pjit step, Pallas flash attention, bf16 compute, fp32
master, remat). One config is measured; a config that does not fit is an
error. The bench needs a TPU and fails without one.
"""

from __future__ import annotations

import json
import time

import jax

from tpu_engine.mesh_runtime import MeshConfig, MeshRuntime
from tpu_engine.models import transformer as tfm
from tpu_engine.profiler import peak_flops_per_chip
from tpu_engine.sharding import ShardingStage, TPUTrainConfig
from tpu_engine.train import build_train_program


def _config(n_dev: int) -> TPUTrainConfig:
    """The measured config. Best found on a v5e 16 GiB by a round-3 sweep
    of micro-batch x remat policy x moment dtype (pre-ledger, on code since
    rewritten; no ledger line bears it): micro-batch 6 with bf16 Adam
    first moments — the halved mu buffer (~2 GiB at 1B params) buys the
    activation headroom that lifts MFU past the micro-batch-4 plateau.
    micro_batch_size is per data-parallel shard (the program scales the
    global batch by the data×fsdp extent itself)."""
    mesh = MeshConfig(data=1, fsdp=n_dev) if n_dev > 1 else MeshConfig(data=1)
    stage = ShardingStage.FULL_PARTITIONING if n_dev > 1 else ShardingStage.DISABLED
    return TPUTrainConfig(
        model_name="llama-1b", micro_batch_size=6, moment_dtype="bf16",
        activation_checkpointing=True, sharding_stage=stage, mesh=mesh,
        seq_len=2048, attention_impl="auto", precision="bf16",
    )


def _run(cfg: TPUTrainConfig, iters: int) -> tuple[float, int, tfm.ModelConfig]:
    """Compile + warm up + time; returns (sec/step, tokens/step, model config).

    Timing is the MINIMUM over three measurement windows, not one long
    mean: a chip idle before the run ramps clocks over the first seconds
    (round-4 lesson — a single cold window read 52.9% where steady state
    is 53.4%), and min-of-windows reports the steady-state capability a
    long training run actually sees."""
    runtime = MeshRuntime(cfg.mesh)
    program = build_train_program(cfg, runtime=runtime)
    state = program.init(jax.random.PRNGKey(0))
    batch = program.synthetic_batch(seed=0)
    for _ in range(3):  # compile + clock ramp-up
        state, metrics = program.step(state, batch)
    float(metrics["loss"])  # host read: waits for the device
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            state, metrics = program.step(state, batch)
        float(metrics["loss"])
        best = min(best, (time.perf_counter() - t0) / iters)
    accum, global_micro, seq = program.global_batch_shape()
    tokens_per_step = accum * global_micro * seq
    return best, tokens_per_step, program.model_config


def main() -> None:
    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"bench.py measures the chip; found backend "
            f"{jax.default_backend()!r}, not 'tpu'"
        )
    n_dev = jax.device_count()
    cfg = _config(n_dev)
    dt, tokens_per_step, model_cfg = _run(cfg, iters=10)

    tokens_per_sec_chip = tokens_per_step / dt / n_dev
    flops_per_token = tfm.train_flops_per_token(model_cfg, cfg.seq_len)
    mfu = tokens_per_sec_chip * flops_per_token / peak_flops_per_chip(jax.devices()[0])
    print(json.dumps({
        "metric": f"mfu_{model_cfg.name}_{'fsdp' if n_dev > 1 else 'singlechip'}",
        "value": round(mfu * 100, 2),
        "unit": "% MFU",
        "vs_baseline": round(mfu / 0.45, 3),
        "tokens_per_sec_per_chip": round(tokens_per_sec_chip, 1),
        "step_time_ms": round(dt * 1e3, 2),
        "n_devices": n_dev,
        "device_kind": jax.devices()[0].device_kind,
    }))


if __name__ == "__main__":
    main()
