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
import sys
import time
import traceback

import jax

from tpu_engine.mesh_runtime import MeshConfig, MeshRuntime
from tpu_engine.models import transformer as tfm
from tpu_engine.profiler import peak_flops_per_chip
from tpu_engine.sharding import ShardingStage, TPUTrainConfig
from tpu_engine.train import build_train_program


def _config(n_dev: int) -> TPUTrainConfig:
    """The measured config. Best found on v5e 16 GiB (benchmarks/
    mfu_sweep.py + round-3 trace probes): micro-batch 6 with bf16 Adam
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


def _emit(fn, *args) -> bool:
    """Print one auxiliary JSON line. A line that raises is loud (traceback
    on stderr) and fails the run; the remaining lines still print."""
    try:
        line = fn(*args)
    except Exception:  # noqa: BLE001 — reported, and main() exits non-zero
        traceback.print_exc()
        return False
    if line is not None:
        print(json.dumps(line))
    return True


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
    auxiliary = [
        (_comm_compress_metric, n_dev),
        (_quant_train_metric,),
        (_scheduler_metric,),
        (_pipeline_schedule_metric, n_dev),
        (_chaos_metric,),
        (_goodput_metric,),
        (_compile_cache_metric,),
        (_serving_fleet_metric,),
        (_serving_disagg_metric,),
        (_placement_metric,),
        (_hetero_metric,),
        (_twin_metric,),
        (_historian_metric,),
        (_autopilot_metric,),
        (_ctl_scale_metric,),
        (_prefix_plane_metric,),
        (_reshard_metric,),
        (_spec_pool_metric,),
        (_ctl_crash_metric,),
    ]
    failed = [fn.__name__ for fn, *args in auxiliary if not _emit(fn, *args)]
    if failed:
        print(f"auxiliary lines failed: {failed}", file=sys.stderr)
        raise SystemExit(1)


def _comm_compress_metric(n_dev: int) -> dict | None:
    """Second JSON line: ZeRO++ comm-compression bytes-on-wire A/B.

    Compile-only (no training): builds the gpt-tiny step twice — GSPMD
    baseline vs qwZ+hpZ+qgZ — on an 8-device hybrid (dcn_data=2) mesh and
    byte-accounts the compiled HLO (comm_compress.collective_stats). On
    other device counts, reports the analytic per-element factor instead."""
    from tpu_engine import comm_compress as cc

    if n_dev != 8:
        return {
            "metric": "comm_compress_volume_factor",
            "value": cc.expected_volume_factors(256)["weight_gather"],
            "unit": "x fewer gather bytes (analytic, block=256)",
            "note": f"HLO A/B needs 8 devices (have {n_dev})",
        }

    def compiled_stats(extra: dict) -> dict:
        cfg = TPUTrainConfig(
            model_name="gpt-tiny",
            mesh=MeshConfig(data=4, fsdp=2, dcn_data=2),
            micro_batch_size=2, gradient_accumulation_steps=2,
            seq_len=64, precision="fp32", param_dtype="fp32",
            sharding_stage=ShardingStage.FULL_PARTITIONING,
            comm_quant_block_size=64, **extra,
        )
        runtime = MeshRuntime(
            cfg.mesh, slice_assignments=[0, 0, 0, 0, 1, 1, 1, 1]
        )
        prog = build_train_program(cfg, runtime=runtime)
        state = jax.eval_shape(prog.init, jax.random.PRNGKey(0))
        batch = jax.ShapeDtypeStruct(
            prog.global_batch_shape(), jax.numpy.int32
        )
        hlo = prog.step.lower(state, batch).compile().as_text()
        return cc.collective_stats(
            hlo,
            cc.slice_of_partition(dict(prog.mesh.shape), cfg.mesh.dcn_data),
        )

    base = compiled_stats({})
    full = compiled_stats(dict(
        comm_quant_weights=True, comm_secondary_weights=True,
        comm_quant_grads=True,
    ))
    return {
        "metric": "comm_compress_cross_slice_reduction",
        "value": round(
            base["cross_slice_bytes"] / max(full["cross_slice_bytes"], 1), 2
        ),
        "unit": "x fewer cross-slice bytes (qwz+hpz+qgz vs off)",
        "total_reduction": round(
            base["total_wire_bytes"] / max(full["total_wire_bytes"], 1), 2
        ),
        "n_devices": n_dev,
    }


def _quant_train_metric() -> dict | None:
    """Third JSON line: AQT-style int8 quantized-training A/B
    (tpu_engine/quant_train.py) — step-time ratio and loss parity of
    quant_training='int8' vs off on the gpt-tiny model, single device,
    same seed/batch, 8 timed steps (the benchmarks/quant_train.py
    protocol at bench scale)."""
    results = {}
    for quant in ("none", "int8"):
        cfg = TPUTrainConfig(
            model_name="gpt-tiny", mesh=MeshConfig(data=1),
            micro_batch_size=2, seq_len=128,
            sharding_stage=ShardingStage.DISABLED,
            learning_rate=1e-3, warmup_steps=2, total_steps=100,
            activation_checkpointing=False, attention_impl="auto",
            quant_training=quant,
        )
        program = build_train_program(cfg)
        state = program.init(jax.random.PRNGKey(0))
        batch = program.synthetic_batch(seed=0)
        losses = []
        t0 = None
        for i in range(9):
            state, metrics = program.step(state, batch)
            losses.append(float(metrics["loss"]))
            if i == 0:  # exclude compile
                jax.block_until_ready(state["params"])
                t0 = time.perf_counter()
        jax.block_until_ready(state["params"])
        results[quant] = {
            "dt_ms": (time.perf_counter() - t0) / 8 * 1e3,
            "losses": losses,
        }
        del program, state
        jax.clear_caches()
    base, q = results["none"], results["int8"]
    return {
        "metric": "quant_train_ab",
        "value": round(base["dt_ms"] / max(q["dt_ms"], 1e-9), 3),
        "unit": "x step-time vs bf16 (>1 = int8 faster)",
        "loss_delta_final": round(
            abs(base["losses"][-1] - q["losses"][-1]), 5
        ),
        "bf16_step_time_ms": round(base["dt_ms"], 2),
        "int8_step_time_ms": round(q["dt_ms"], 2),
        "backend": jax.default_backend(),
    }


def _scheduler_metric() -> dict | None:
    """Fourth JSON line: fleet-scheduler goodput on the 21-job mixed-priority
    mock-fleet trace (benchmarks/scheduler_sim.py phase A — FakeJobs, no
    device compute) vs the reference's serial FIFO launcher (= 1.0)."""
    from benchmarks.scheduler_sim import run_trace

    trace = run_trace()
    return {
        "metric": "scheduler_goodput_vs_serial_fifo",
        "value": trace["goodput_work_s_per_wall_s"],
        "unit": "work-seconds per wall-second (serial FIFO = 1.0)",
        "speedup_vs_serial": trace["speedup_vs_serial"],
        "mean_wait_s": trace["mean_wait_s"],
        "serial_mean_wait_s": trace["serial_mean_wait_s"],
        "preemptions": trace["preemptions"],
        "zero_lost_work": trace["zero_lost_work"],
    }


def _chaos_metric() -> dict | None:
    """Sixth JSON line: goodput under a seeded chip-fault trace — the
    self-healing detect->save->shrink->resume path vs the reference's
    die-and-restart (benchmarks/chaos.py, deterministic virtual clock)."""
    from benchmarks.chaos import run_trace

    trace = run_trace(seed=0)
    return {
        "metric": "chaos_goodput_self_heal_vs_die_restart",
        "value": trace["goodput_improvement"],
        "unit": "x goodput under faults (die-and-restart = 1.0)",
        "mttr_reduction": trace["mttr_reduction"],
        "mttr_mean_s": trace["self_heal"]["mttr_mean_s"],
        "baseline_mttr_mean_s": trace["die_and_restart"]["mttr_mean_s"],
        "steps_saved": trace["steps_saved"],
        "zero_lost_steps": trace["self_heal"]["lost_steps"] == 0,
    }


def _goodput_metric() -> dict | None:
    """JSON line after chaos: the goodput ledger's wall-clock decomposition
    of the same seeded chaos trace — per-category breakdown (percent of
    wall), the sum-to-wall invariant error, and the SLO burn-rate
    alerter's deterministic ok->warning->page progression."""
    from benchmarks.chaos import run_trace

    gp = run_trace(seed=0)["goodput"]
    return {
        "metric": "goodput_ledger_chaos_breakdown",
        "value": gp["goodput_fraction"],
        "unit": "productive fraction of self-heal wall clock",
        "breakdown_pct": gp["breakdown_pct"],
        "sum_error_pct": gp["sum_error_pct"],
        "slo_progression": gp["slo"]["progression"],
        "alert_count": gp["slo"]["alert_count"],
        "sum_to_wall_ok": gp["sum_error_pct"] < 1.0,
    }


def _compile_cache_metric() -> dict | None:
    """JSON line after goodput: the fleet compile cache's warm-start wins —
    chaos MTTR with the layout-keyed index on vs off, and the cache-aware
    admission lane's mean-wait reduction (both deterministic virtual-clock
    accounts, benchmarks/chaos.py + benchmarks/scheduler_sim.py phase C)."""
    from benchmarks.chaos import run_trace
    from benchmarks.scheduler_sim import run_warm_admission

    cc = run_trace(seed=0)["compile_cache"]
    warm = run_warm_admission(seed=0)
    return {
        "metric": "compile_cache_warm_start",
        "value": cc["mttr_warm_reduction_pct"],
        "unit": "% chaos MTTR reduction, compile index on vs off",
        "mttr_on_s": cc["mttr_on_s"],
        "mttr_off_s": cc["mttr_off_s"],
        "warm_resumes": cc["warm_resumes"],
        "cold_resumes": cc["cold_resumes"],
        "wall_saved_s": cc["wall_saved_s"],
        "mean_wait_fifo_s": warm["mean_wait_fifo_s"],
        "mean_wait_warm_s": warm["mean_wait_warm_s"],
        "wait_reduction_pct": warm["wait_reduction_pct"],
    }


def _pipeline_schedule_metric(n_dev: int) -> dict | None:
    """Fifth JSON line: the zero-bubble pipeline schedule's tick/busy-lane
    account vs 1F1B at the same M and P, plus a measured per-sample step
    time A/B on a tiny pipelined program when the visible devices allow a
    pipe=2 mesh."""
    from tpu_engine.parallel.pipeline_zb import schedule_account

    pipe, accum = 4, 16
    zb = schedule_account("zb", pipe, accum)
    f1b = schedule_account("1f1b", pipe, accum)
    line = {
        "metric": "pipeline_schedule_zb_vs_1f1b",
        "schedule": "zb",
        "pipe_stages": pipe,
        "microbatches": accum,
        "ticks": zb["ticks"],
        "busy_fraction": round(zb["busy_fraction"], 4),
        "1f1b_busy_fraction": round(f1b["busy_fraction"], 4),
        "burned_cost_vs_1f1b": round(
            zb["burned_cost"] / f1b["burned_cost"], 3
        ),
        "per_sample_ms": None,
        "1f1b_per_sample_ms": None,
    }
    if n_dev >= 2 and n_dev % 2 == 0:
        from tpu_engine.mesh_runtime import MeshConfig
        from tpu_engine.sharding import TPUTrainConfig
        from tpu_engine.train import build_train_program

        times = {}
        for sched in ("1f1b", "zb"):
            cfg = TPUTrainConfig(
                model_name="gpt-tiny",
                mesh=MeshConfig(data=-1, pipe=2),
                micro_batch_size=1,
                gradient_accumulation_steps=8,
                seq_len=64,
                precision="fp32",
                total_steps=4,
                pipeline_schedule=sched,
            )
            prog = build_train_program(cfg)
            state = prog.init(jax.random.PRNGKey(0))
            state, _ = prog.step(state, prog.synthetic_batch(seed=0))
            jax.block_until_ready(jax.tree.leaves(state)[0])
            t0 = time.perf_counter()
            for i in range(1, 3):
                state, m = prog.step(state, prog.synthetic_batch(seed=i))
            jax.block_until_ready(jax.tree.leaves(state)[0])
            samples = 2 * cfg.effective_batch_size
            times[sched] = (time.perf_counter() - t0) * 1e3 / samples
        line["per_sample_ms"] = round(times["zb"], 2)
        line["1f1b_per_sample_ms"] = round(times["1f1b"], 2)
        line["measured_pipe_stages"] = 2
        line["measured_microbatches"] = 8
    return line


def _serving_fleet_metric() -> dict | None:
    """Seventh JSON line: serving-fleet throughput on the seeded bursty
    open-loop trace — scheduler-managed autoscaled replicas (real router +
    autoscaler over the capacity sim, benchmarks/serving_fleet_sim.py) vs
    a static single replica."""
    from benchmarks.serving_fleet_sim import run_trace

    trace = run_trace(seed=0)
    auto = trace["autoscaled"]
    return {
        "metric": "serving_fleet_throughput_vs_static_1",
        "value": trace["throughput_improvement"],
        "unit": "x aggregate tokens/s (static single replica = 1.0)",
        "tokens_per_sec": round(auto["tokens_per_sec"], 1),
        "tokens_per_sec_per_chip": round(auto["tokens_per_sec_per_chip"], 1),
        "p50_ms": auto["p50_ms"],
        "p99_ms": auto["p99_ms"],
        "p99_within_slo": auto["p99_within_slo"],
        "p99_slo_ms": trace["p99_slo_ms"],
        "replica_trace": auto["replica_trace"],
        "max_replicas_used": auto["max_replicas_used"],
        "router_weights": auto["router"]["weights"],
        "prefix_hit_rate": auto["prefix_hit_rate"],
        "static_p99_ms": trace["static_1_replica"]["p99_ms"],
    }


def _serving_disagg_metric() -> dict | None:
    """JSON line: symmetric vs disaggregated prefill/decode serving at
    equal total chips on the long-prefill bursty trace
    (benchmarks/serving_fleet_sim.py §A/B, pool layouts chosen by
    tpu_engine.placement.plan_serving_pool)."""
    from benchmarks.serving_fleet_sim import run_disagg_ab

    ab = run_disagg_ab(seed=0)
    return {
        "metric": "serving_disagg_ttft_p99_vs_symmetric",
        "value": ab["ttft_p99_improvement"],
        "unit": "x p99 TTFT (symmetric fleet = 1.0, equal chips)",
        "total_chips": ab["total_chips"],
        "layouts": ab["layouts"],
        "symmetric_ttft_p99_ms": ab["symmetric"]["ttft_p99_ms"],
        "disagg_ttft_p99_ms": ab["disagg"]["ttft_p99_ms"],
        "symmetric_tokens_per_sec": ab["symmetric"]["tokens_per_sec"],
        "disagg_tokens_per_sec": ab["disagg"]["tokens_per_sec"],
        "gates_pass": ab["gates_pass"],
    }


def _placement_metric() -> dict | None:
    """Eighth JSON line: the placement planner's predicted-vs-measured
    rank correlation over the fast (gpt-tiny) layout sweep — the same
    global batch run through ≥6 mesh/schedule layouts on the 8-virtual-
    device CPU mesh, ranked against ``PlacementPlanner.predict``. The
    fuller compute-dominated table (gpt-mid) lives in
    ``benchmarks/placement_plan.py --sweep`` / RESULTS.md §PR 7."""
    from benchmarks.placement_plan import run_sweep

    sweep = run_sweep(size="tiny", iters=5)
    return {
        "metric": "placement_rank_correlation",
        "value": sweep["value"],
        "unit": sweep["unit"],
        "model": sweep["model"],
        "layouts": sweep["layouts"],
        "top_pick": sweep["top_pick"],
        "top_pick_within_5pct": sweep["top_pick_within_5pct"],
        "top_pick_measured_ms": sweep["top_pick_measured_ms"],
        "fastest_measured_ms": sweep["fastest_measured_ms"],
    }


def _hetero_metric() -> dict | None:
    """Ninth JSON line: throughput-weighted heterogeneous sharding — the
    steady-state goodput a rebalanced gang retains on a seeded 25%-
    degraded host vs the uniform gang (which gates every step on the slow
    host) and vs evicting the host (benchmarks/chaos.py hetero lane,
    deterministic virtual clock)."""
    from benchmarks.chaos import run_hetero_lane

    het = run_hetero_lane(seed=0)
    return {
        "metric": "hetero_rebalance_goodput",
        "value": het["steady_goodput_on"],
        "unit": "steady-state goodput fraction of heterogeneous ideal",
        "rebalance_off": het["steady_goodput_off"],
        "shrink": het["steady_goodput_shrink"],
        "goodput_recovered": het["goodput_recovered"],
        "rebalance_step": het["rebalance_on"]["rebalance_step"],
        "assignment": het["rebalance_on"]["assignment"],
        "global_batch_preserved": (
            sum(het["rebalance_on"]["assignment"])
            == het["params"]["global_micro"]
        ),
    }


def _twin_metric() -> dict | None:
    """Tenth JSON line: digital-twin replay fidelity + policy A/B — the
    twin records the seeded chaos run, re-ingests its JSONL, replays it
    against the real goodput ledger (per-category error must be <1%),
    and scores checkpoint-interval / compile-index policy variants over
    the same fault trace (tpu_engine/twin.py)."""
    from tpu_engine.twin import twin_bench_line

    return twin_bench_line(seed=0)


def _historian_metric() -> dict | None:
    """Eleventh JSON line: fleet-historian chaos-replay fidelity — the
    seeded chaos trace is replayed from its JSONL alone and the rebuilt
    metric history must match the live run within 1% per queried
    aggregate, with every injected fault stitched into exactly one
    resolved detect→action→resolution incident
    (tpu_engine/historian.py via twin.historian_bench_line)."""
    from tpu_engine.twin import historian_bench_line

    return historian_bench_line(seed=0)


def _autopilot_metric() -> dict | None:
    """Twelfth JSON line: autopilot chaos A/B — steady-state goodput on
    the seeded slow-host trace with the armed autopilot (drains the
    blamed host off historian trends + incident links) vs the loop off,
    plus the dry-run shadow stream (same decisions, zero actuations)
    (tpu_engine/twin.py autopilot lane, deterministic virtual clock)."""
    from tpu_engine.twin import autopilot_bench_line

    return autopilot_bench_line(seed=0)


def _reshard_metric() -> dict | None:
    """Fifteenth JSON line: reshard plane A/B — topology-changing resume
    MTTR vs the warm same-topology self-heal on the seeded chip-fault
    trace, gating the 1.5x budget with zero lost steps, byte-parity
    leaves across mesh factorizations on the real executor, 100% of held
    serving requests completing after the pool migration, and
    byte-identical repeats (tpu_engine/reshard.py via
    twin.reshard_bench_line)."""
    from tpu_engine.twin import reshard_bench_line

    return reshard_bench_line(seed=0)


def _spec_pool_metric() -> dict | None:
    """Sixteenth JSON line: fleet speculative decoding pools A/B —
    tokens/sec/chip on the seeded bursty multi-tenant trace with paired
    draft/verify pools vs plain chunked decode at equal chips, gating a
    >=1.2x win with p99 no worse, the sustained-low-acceptance tenant
    spilled back to plain decode by the audited historian rule (and no
    worse off than the baseline), the estimator's structured
    oversubscribed-draft rejection, a feasible propose-latency-ranked
    draft placement, and byte-identical repeats (tpu_engine/spec_pool.py
    via twin.spec_pool_bench_line)."""
    from tpu_engine.twin import spec_pool_bench_line

    return spec_pool_bench_line(seed=0)


def _ctl_crash_metric() -> dict | None:
    """Seventeenth JSON line: durable control plane A/B — crash-recovery
    MTTR vs the no-crash run of the same seeded storm, gating the 1.5x
    budget with zero lost or duplicated submissions, every held serving
    request answered, orphans re-adopted instead of re-launched, the
    vanished replica re-dispatched, byte-identical double recovery from
    the same journal bytes, and the torn journal tail skipped not raised
    (tpu_engine/journal.py via twin.ctl_crash_bench_line)."""
    from tpu_engine.twin import ctl_crash_bench_line

    return ctl_crash_bench_line(seed=0)


def _prefix_plane_metric() -> dict | None:
    """Fourteenth JSON line: fleet prefix plane A/B — p99 TTFT on the
    seeded many-tenant shared-prefix trace with the radix-index +
    host-RAM-tier plane vs per-replica LRU at equal chips, gating a
    >=2x improvement with tokens/sec no worse, byte-identical repeats,
    host-tier absorption of replica-cache overflow, and the estimator's
    structured host-budget rejection (tpu_engine/prefix_plane.py via
    twin.prefix_plane_bench_line)."""
    from tpu_engine.twin import prefix_plane_bench_line

    return prefix_plane_bench_line(seed=0)


def _ctl_scale_metric() -> dict | None:
    """Thirteenth JSON line: control-plane scale — 100k submissions and
    1M serving requests pushed through the real scheduler, router,
    historian and incident correlator under the virtual clock, gating
    that control overhead per simulated fleet-second stays flat (<=1.25x
    vs the 1k-job config) and every ring stays at its cap
    (tpu_engine/twin.py scale lane)."""
    from tpu_engine.twin import ctl_scale_bench_line

    return ctl_scale_bench_line(seed=0)


if __name__ == "__main__":
    main()
