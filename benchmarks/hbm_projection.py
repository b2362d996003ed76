"""7B/13B/70B-scale memory + MFU projection without 7B-scale hardware.

Three independent measurement planes (VERDICT round-1 item 5):

1. **AOT compile against described TPU topologies** (``--aot``): libtpu
   compiles the REAL sharded train step for v5e meshes up to 16x16 (256
   chips — the BASELINE north-star hardware) without any chips attached,
   and ``compiled.memory_analysis()`` reports the per-device HBM the XLA
   compiler actually allocated (arguments + temporaries), while
   ``cost_analysis()`` reports per-device FLOPs per step. This is the
   strongest available evidence that a preset fits its target slice.

2. **eval_shape arithmetic** (``--table``): pure state accounting — bytes
   per device of params / grads / optimizer state at each ZeRO stage ×
   offload mode, from the sharding specs alone. No compile, runs anywhere.

3. **Single-layer microbenchmark on the real chip** (``--layer``): one
   llama-7b decoder block, seq 4096, fwd+bwd wall time on the attached TPU
   — anchors the 7B MFU projection with measured silicon numbers.

Each mode prints JSON lines; paste the summary into benchmarks/RESULTS.md.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import json
import time

import jax
import jax.numpy as jnp


# (name, model, topology, mesh axes, micro_batch, accum, seq, offload)
AOT_CONFIGS = [
    # The BASELINE north star: Llama-2-7B-scale FSDP on v5e-256.
    ("northstar-7b-v5e256", "llama-7b", "v5e:16x16",
     dict(data=16, fsdp=16), 2, 1, 4096, {}),
    # The shipped presets at their native mesh sizes.
    ("preset-7b-v5e4", "llama-7b", "v5e:2x2",
     dict(data=1, fsdp=4), 2, 1, 4096, {"optimizer_offload": "host"}),
    ("preset-13b-v5e8", "llama-13b", "v5e:2x4",
     dict(data=1, fsdp=8), 1, 1, 4096,
     {"optimizer_offload": "host", "param_offload": "host"}),
    ("preset-13b-v5e8-no-offload", "llama-13b", "v5e:2x4",
     dict(data=1, fsdp=8), 1, 1, 4096, {}),
    ("preset-70b-v5e16", "llama-70b", "v5e:4x4",
     dict(data=2, fsdp=8), 1, 1, 4096,
     {"optimizer_offload": "host", "param_offload": "host"}),
    ("70b-v5e256", "llama-70b", "v5e:16x16",
     dict(data=16, fsdp=16), 1, 1, 4096,
     {"optimizer_offload": "host", "param_offload": "host"}),
]


def _build(model, mesh_axes, micro, accum, seq, overrides, devices=None):
    from tpu_engine.mesh_runtime import MeshConfig, MeshRuntime
    from tpu_engine.sharding import ShardingStage, TPUTrainConfig
    from tpu_engine.train import build_train_program

    overrides = dict(overrides)
    stage = overrides.pop("sharding_stage", ShardingStage.FULL_PARTITIONING)
    cfg = TPUTrainConfig(
        model_name=model,
        sharding_stage=stage,
        mesh=MeshConfig(**mesh_axes),
        micro_batch_size=micro,
        gradient_accumulation_steps=accum,
        seq_len=seq,
        **overrides,
    )
    runtime = MeshRuntime(cfg.mesh, devices=devices) if devices else None
    return build_train_program(cfg, runtime=runtime)


def run_aot() -> None:
    from jax.experimental import topologies

    gib = 2**30
    for name, model, topo_name, mesh_axes, micro, accum, seq, overrides in AOT_CONFIGS:
        t0 = time.time()
        try:
            topo = topologies.get_topology_desc(topo_name, platform="tpu")
            prog = _build(model, mesh_axes, micro, accum, seq, overrides,
                          devices=topo.devices)
            state_shape = jax.eval_shape(prog.init, jax.random.PRNGKey(0))
            batch = jax.ShapeDtypeStruct(prog.global_batch_shape(), jnp.int32)
            comp = prog.step.lower(state_shape, batch).compile()
            ma = comp.memory_analysis()
            ca = comp.cost_analysis() or {}
            args_gib = ma.argument_size_in_bytes / gib
            temp_gib = ma.temp_size_in_bytes / gib
            peak_gib = args_gib + temp_gib  # outputs alias the donated args
            print(json.dumps({
                "config": name, "model": model, "topology": topo_name,
                "mesh": mesh_axes, "micro_batch": micro, "seq_len": seq,
                "offload": overrides,
                "device_args_gib": round(args_gib, 2),
                "device_temp_gib": round(temp_gib, 2),
                "device_peak_gib": round(peak_gib, 2),
                "fits_16gib_hbm": peak_gib < 16.0,
                "flops_per_step_per_device": ca.get("flops"),
                "compile_s": round(time.time() - t0, 1),
            }))
        except Exception as e:  # noqa: BLE001 — keep the sweep going
            print(json.dumps({
                "config": name, "error": f"{type(e).__name__}: {e}"[:300],
                "compile_s": round(time.time() - t0, 1),
            }))


def run_table() -> None:
    """Pure eval_shape accounting: per-device state bytes by stage/offload."""
    from tpu_engine.sharding import ShardingStage

    # The estimator lives in tpu_engine/hbm_estimate.py now (the fleet
    # scheduler's admission gate uses the analytic plane of the same module).
    from tpu_engine.hbm_estimate import per_device_bytes

    gib = 2**30

    from jax.experimental import topologies

    topo_for = {4: "v5e:2x2", 8: "v5e:2x4", 16: "v5e:4x4"}
    for model, fsdp in (("llama-7b", 4), ("llama-13b", 8), ("llama-70b", 16)):
        devices = topologies.get_topology_desc(
            topo_for[fsdp], platform="tpu"
        ).devices
        for stage in (0, 1, 2, 3):
            for offload in ({}, {"optimizer_offload": "host"},
                            {"optimizer_offload": "host", "param_offload": "host"}):
                if offload.get("param_offload") and stage < 3:
                    continue
                try:
                    cfg_over = dict(offload)
                    prog = _build(model, dict(data=1, fsdp=fsdp), 1, 1, 4096,
                                  {**cfg_over, "sharding_stage": ShardingStage(stage)},
                                  devices=devices)
                    state_shape = jax.eval_shape(prog.init, jax.random.PRNGKey(0))
                    sh = prog.state_shardings
                    p_dev = per_device_bytes(state_shape["params"], sh["params"], False)
                    p_host = per_device_bytes(state_shape["params"], sh["params"], True)
                    o_dev = per_device_bytes(state_shape["opt_state"], sh["opt_state"], False)
                    o_host = per_device_bytes(state_shape["opt_state"], sh["opt_state"], True)
                    print(json.dumps({
                        "model": model, "fsdp": fsdp, "stage": stage,
                        "offload": offload,
                        "params_dev_gib": round(p_dev / gib, 3),
                        "params_host_gib": round(p_host / gib, 3),
                        "opt_dev_gib": round(o_dev / gib, 3),
                        "opt_host_gib": round(o_host / gib, 3),
                    }))
                except Exception as e:  # noqa: BLE001
                    print(json.dumps({
                        "model": model, "stage": stage, "offload": offload,
                        "error": f"{type(e).__name__}: {e}"[:200],
                    }))


def run_layer() -> None:
    """One llama-7b decoder block fwd+bwd on the attached chip, seq 4096."""
    from tpu_engine.models import transformer as tfm

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU attached; --layer needs real silicon")
    cfg = tfm.MODEL_CONFIGS["llama-7b"]
    D, F = cfg.d_model, cfg.d_ff
    B, S = 1, 4096
    rng = jax.random.PRNGKey(0)
    layer = jax.eval_shape(lambda: tfm.init_params(rng, cfg, dtype=jnp.bfloat16))
    # Materialise ONE layer's params (full init would blow the single chip).
    one_layer = jax.tree.map(
        lambda s: jax.random.normal(rng, s.shape[1:], jnp.bfloat16) * 0.02
        if s.shape and s.shape[0] == cfg.n_layers
        else None,
        layer["layers"],
    )
    x = jax.random.normal(rng, (B, S, D), jnp.bfloat16)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))

    def block_loss(layer_params, x):
        out, _ = tfm._block(x, layer_params, cfg, positions, mesh=None,
                            tag_names=False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    grad_fn = jax.jit(jax.value_and_grad(block_loss))
    v, g = grad_fn(one_layer, x)
    jax.block_until_ready(g)
    n_iter = 20
    t0 = time.perf_counter()
    for _ in range(n_iter):
        v, g = grad_fn(one_layer, x)
    jax.block_until_ready(g)
    dt = (time.perf_counter() - t0) / n_iter
    # Per-layer train FLOPs: 6 × layer params × tokens + attention term.
    layer_params = sum(
        int(jnp.size(p)) for p in jax.tree.leaves(one_layer) if p is not None
    )
    attn_flops = 12 * S * S * D * B  # fwd+bwd causal attention (dense upper bound /2)
    flops = 6 * layer_params * B * S + attn_flops
    from tpu_engine.profiler import peak_flops_per_chip

    peak = peak_flops_per_chip()
    mfu = flops / dt / peak
    print(json.dumps({
        "metric": "llama7b_single_layer_fwd_bwd",
        "seq_len": S, "batch": B,
        "step_time_ms": round(dt * 1e3, 2),
        "layer_params": layer_params,
        "model_flops": flops,
        "mfu_anchor": round(mfu, 4),
        "device_kind": jax.devices()[0].device_kind,
    }))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--table", action="store_true")
    ap.add_argument("--layer", action="store_true")
    args = ap.parse_args()
    if not (args.aot or args.table or args.layer):
        args.table = True
    if args.table:
        run_table()
    if args.layer:
        run_layer()
    if args.aot:
        run_aot()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
