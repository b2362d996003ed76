"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Run as ``python chip_smoke.py`` from the root of a checkout, on a machine
with a TPU. One process, no children, no arguments. It drives the system's
two main paths once, through the entry points users go through, at the
full width and depth of llama-1b with seeded random weights:

- phase A — train 8 steps through the worker's own start-up sequence
  (``apply_comm_flags`` → ``initialize_distributed`` →
  ``enable_compilation_cache`` → ``TPULauncher().launch(block=True)``) on a
  fixed batch, so the loss must fall;
- phase B — ``delete_job`` and check the chips come back;
- phase C — on the same launcher's scheduler, one ``ServingFleet`` replica
  answers 8 requests.

Every phase asserts its results by the repo's own means; nothing is caught
in order to continue, nothing is retried, no smaller model stands in. It
exits non-zero unless every assertion held, and on any device that is not
a TPU in the peak table. The last line of stdout is then
``{"ok": true, "device": {...}}``. The timings it prints are smoke
readings, not benchmark results.

The phases are plain functions of a config and sizes so that
``tests/test_chip_smoke.py`` can run them on the 8-virtual-device CPU mesh
with gpt-tiny; what only a chip can show (the Mosaic kernel in the lowered
step, ``memory_stats``) is asserted where the mesh's devices are TPUs.
"""

from __future__ import annotations

import gc
import json
import math
import os
import statistics
import sys
import time
from typing import Any

GIB = 2**30


def _say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _wait_for(what: str, done, timeout_s: float, tick=None, every_s: float = 0.05):
    """Poll ``done()`` (calling ``tick()`` between polls) until it returns a
    truthy value; a timeout is a failure, never a shrug."""
    deadline = time.time() + timeout_s
    while True:
        if tick is not None:
            tick()
        out = done()
        if out:
            return out
        if time.time() > deadline:
            raise TimeoutError(f"{what} not reached within {timeout_s:.0f}s")
        time.sleep(every_s)


# ---------------------------------------------------------------------------
# Start-up: the worker CLI's sequence (tpu_engine/launcher.py main()).
# ---------------------------------------------------------------------------


def start_up(cfg) -> dict[str, Any]:
    """The worker's start-up calls, in the worker's order. Returns what the
    program will run with: comm-flag delivery and the compile-cache dir."""
    import jax

    from tpu_engine import compile_cache
    from tpu_engine.comm import apply_comm_flags, comm_flags_status
    from tpu_engine.mesh_runtime import initialize_distributed

    apply_comm_flags(cfg)  # environment only — before the backend comes up
    initialize_distributed()
    n_dev = len(jax.devices())  # the backend initialises here
    cache = compile_cache.enable_compilation_cache()
    return {
        "n_devices": n_dev,
        "comm_flags": comm_flags_status(cfg),
        "cache_dir": cache.dir,
        "cache_skipped": cache.skipped_reason,
    }


# ---------------------------------------------------------------------------
# Phase A — train through the launcher
# ---------------------------------------------------------------------------


def fixed_batch(cfg, vocab_size: int, seed: int = 0):
    """The one batch every step sees: [accum, micro·dp, seq] int32 on the
    host, seeded; the jitted step places it by its own input sharding."""
    import numpy as np

    accum = cfg.gradient_accumulation_steps
    shape = (accum, cfg.effective_batch_size // accum, cfg.seq_len)
    return np.random.default_rng(seed).integers(0, vocab_size, shape, dtype=np.int32)


def fleet_rows(fleet) -> list[dict[str, Any]]:
    return [
        {
            "chip": d.index, "hbm_pct": d.hbm_utilization_pct,
            "hbm_used_gb": d.hbm_used_gb, "duty_pct": d.duty_cycle_pct,
            "health": d.health_status.value, "is_available": d.is_available,
            "jobs": [j.job_id for j in d.jobs], "alerts": d.alerts,
            "telemetry_sources": fleet.telemetry_sources,
        }
        for d in fleet.devices
    ]


def phase_train(cfg, steps: int) -> tuple[Any, str, dict[str, Any]]:
    """Launch ``cfg`` for ``steps`` steps on a fixed batch and check the
    run. Returns (launcher, job_id, readings)."""
    import jax

    from tpu_engine import tracing
    from tpu_engine.hbm_estimate import estimate_job_hbm
    from tpu_engine.launcher import TPULauncher
    from tpu_engine.models import transformer as tfm
    from tpu_engine.tpu_manager import TPUManager

    devices = jax.devices()
    on_tpu = all(d.platform == "tpu" for d in devices)
    n_dev = len(devices)
    batch = fixed_batch(cfg, tfm.MODEL_CONFIGS[cfg.model_name].vocab_size)
    marks: dict[int, float] = {}
    midrun: dict[str, Any] = {}

    def data_fn(step: int):
        marks[step] = time.time()
        if step == steps // 2:  # mid-run: what the fleet view makes of us
            midrun["rows"] = fleet_rows(TPUManager().get_fleet_status())
            midrun["memory_stats"] = [d.memory_stats() for d in devices]
        return batch

    launcher = TPULauncher()
    t_launch = time.time()
    res = launcher.launch(cfg, max_steps=steps, data_fn=data_fn, block=True)
    wall_s = time.time() - t_launch
    assert res.status == "launched", (res.status, res.error, res.queue_position)
    job = launcher.get_job(res.job_id)
    sub = launcher.scheduler.get(res.submission_id)
    desc = job.describe()
    assert desc["status"] == "completed", (desc["status"], desc["error"])
    assert desc["current_step"] == steps, desc["current_step"]

    # One attempt, no self-heal: the job's own HBM and duty cycle must not
    # have read as a fault in its own supervisor.
    assert sub.attempts == 1 and sub.preemptions == 0, (sub.attempts, sub.preemptions)
    assert desc["unhealthy_devices"] == [], desc["unhealthy_devices"]
    assert desc["recovery_events"] == [] and desc["preemption_reason"] is None, desc
    events = tracing.get_recorder().events(trace_id=sub.trace_id, limit=0)
    bad = [
        e["name"] for e in events
        if e["kind"] in ("recovery", "preempt_drain", "fault")
        or e["name"] in ("requeue", "shrink_admit")
    ]
    assert not bad, f"self-heal/requeue/preempt events in the job's trace: {bad}"

    curve = job.monitor.get_loss_curve()
    losses, gnorms = curve["losses"], curve["gradient_norms"]
    assert curve["steps"] == list(range(1, steps + 1)), curve["steps"]
    assert all(math.isfinite(x) for x in losses + gnorms), (losses, gnorms)
    assert losses[-1] < losses[0], f"loss did not fall on a fixed batch: {losses}"

    # What plan/describe() report is what the compiled program contains.
    prog = job.program
    impl = prog.model_config.attention_impl
    assert impl == desc["attention_impl"] == ("flash" if on_tpu else "xla"), impl
    mesh_ids = sorted(int(d.id) for d in prog.runtime.mesh.devices.flat)
    assert mesh_ids == sorted(int(d.id) for d in devices), mesh_ids
    mosaic_calls = None
    if on_tpu:
        # The kernel, not a stand-in: the lowered step holds the Mosaic
        # custom call, and nothing on a TPU mesh runs in interpret mode.
        state = jax.eval_shape(prog.init, jax.random.PRNGKey(cfg.seed))
        abstract_batch = jax.ShapeDtypeStruct(batch.shape, batch.dtype)
        text = prog.step.lower(state, abstract_batch).as_text()
        mosaic_calls = text.count("tpu_custom_call")
        assert mosaic_calls > 0, "no Mosaic custom call in the lowered train step"
        if n_dev > 1:  # under shard_map on every chip of the mesh
            assert "sdy.manual_computation" in text or "shard_map" in text

    # Sharding, from the job's own state: per-device bytes of params and
    # optimizer state, and which devices hold them.
    with job._state_lock:
        per_dev_state = _state_bytes_per_device(job._state)
    assert sorted(per_dev_state) == mesh_ids, (sorted(per_dev_state), mesh_ids)

    est = estimate_job_hbm(cfg, n_dev)
    stats = [d.memory_stats() for d in devices]
    peak_gib = None
    if on_tpu:
        assert all(s and s["peak_bytes_in_use"] > 0 for s in stats), stats
        peak_gib = [round(s["peak_bytes_in_use"] / GIB, 3) for s in stats]
        rows = midrun["rows"]
        assert all(r["health"] == "healthy" and r["is_available"] for r in rows), rows
        assert all(res.job_id in r["jobs"] for r in rows), rows

    tokens_per_step = batch.size
    step_s = [tokens_per_step / t for t in curve["throughputs"]]
    steady = step_s[2:] if steps >= 4 else step_s[1:]
    median_s = statistics.median(steady)
    readings = {
        "model": cfg.model_name,
        "mesh": cfg.mesh.model_dump(),
        "sharding_stage": int(cfg.sharding_stage),
        "micro_batch_size": cfg.micro_batch_size,
        "moment_dtype": cfg.moment_dtype.value if cfg.moment_dtype else None,
        "steps": steps,
        "attention_impl": impl,
        "mosaic_custom_calls_in_lowered_step": mosaic_calls,
        "comm_flags": desc["comm_flags"],
        "losses": [round(x, 4) for x in losses],
        "grad_norms": [round(x, 4) for x in gnorms],
        "wall_s": round(wall_s, 2),
        "seconds_to_first_step": round(marks[1] - t_launch, 2),
        "median_step_s_steady": round(median_s, 4),
        "tokens_per_s_per_chip": round(tokens_per_step / median_s / n_dev, 1),
        "peak_hbm_gib_per_device": peak_gib,
        "estimate_device_total_gib": est.device_total_gib,
        "state_gib_per_device": {
            k: round(v / GIB, 3) for k, v in sorted(per_dev_state.items())
        },
        "midrun_fleet_rows": midrun.get("rows"),
        "midrun_bytes_in_use_gib": [
            round(s["bytes_in_use"] / GIB, 3) if s else None
            for s in midrun.get("memory_stats", [])
        ],
        "memory_stats_after": stats,
    }
    return launcher, res.job_id, readings


def _state_bytes_per_device(state) -> dict[int, int]:
    """Bytes of the train state's params + optimizer state resident on each
    device, summed over addressable shards."""
    import jax

    out: dict[int, int] = {}
    for leaf in jax.tree.leaves((state["params"], state["opt_state"])):
        for shard in leaf.addressable_shards:
            did = int(shard.device.id)
            out[did] = out.get(did, 0) + shard.data.nbytes
    return out


# ---------------------------------------------------------------------------
# Phase B — release
# ---------------------------------------------------------------------------


def phase_release(launcher, job_id: str) -> dict[str, Any]:
    """delete_job gives the chips back: a fleet manager that runs jobs back
    to back in one process has to."""
    import jax

    assert launcher.delete_job(job_id)
    gc.collect()
    stats = launcher.scheduler.stats()
    assert stats["reserved_hbm_gib"] == 0.0 and stats["running"] == 0, stats
    in_use = [d.memory_stats() for d in jax.devices()]
    if all(d.platform == "tpu" for d in jax.devices()):
        assert all(s["bytes_in_use"] < GIB for s in in_use), in_use
    return {
        "bytes_in_use_gib_after_delete": [
            round(s["bytes_in_use"] / GIB, 4) if s else None for s in in_use
        ],
        "reserved_hbm_gib": stats["reserved_hbm_gib"],
    }


# ---------------------------------------------------------------------------
# Phase C — serve through the fleet
# ---------------------------------------------------------------------------


def phase_serve(
    launcher,
    model_name: str,
    max_slots: int,
    max_len: int,
    prompt_lens: list[int],
    max_new_tokens: int,
    timeout_s: float = 900.0,
) -> dict[str, Any]:
    """One replica on the launcher's own scheduler (the path
    ``POST /api/v1/serving/fleet/start`` takes) answers seeded token-id
    prompts; consecutive equal lengths share one prompt."""
    import numpy as np

    from tpu_engine.models import transformer as tfm
    from tpu_engine.scheduler import SubmissionState
    from tpu_engine.serving_fleet import (
        AutoscalerConfig,
        ReplicaAutoscaler,
        ServingFleet,
        ServingReplicaSpec,
    )

    vocab = tfm.MODEL_CONFIGS[model_name].vocab_size
    fleet = ServingFleet(
        launcher.scheduler,
        ServingReplicaSpec(model_name=model_name, max_slots=max_slots, max_len=max_len),
        autoscaler=ReplicaAutoscaler(AutoscalerConfig(min_replicas=1, max_replicas=1)),
    )
    t0 = time.time()
    assert fleet.scale_to(1) == 1
    (sid,) = fleet.status()["replicas"]
    sub = launcher.scheduler.get(sid)

    def replica_up():
        # Admitted, not left queued because the chip still reads busy from
        # phase A; a failed build is terminal and must not be waited out.
        assert sub.state not in (SubmissionState.FAILED, SubmissionState.CANCELLED), (
            sub.describe()
        )
        return fleet.running_replicas()

    _wait_for("replica RUNNING with engine_ready", replica_up, timeout_s, tick=fleet.tick)
    assert sub.state == SubmissionState.RUNNING and sub.attempts == 1, sub.describe()
    ready_s = time.time() - t0

    prompts: list[list[int]] = []
    for i, n in enumerate(prompt_lens):
        if i and n == prompt_lens[i - 1]:
            prompts.append(list(prompts[-1]))  # the pair shares one prompt
        else:
            rng = np.random.default_rng(1000 + n)
            prompts.append([int(t) for t in rng.integers(0, vocab, n)])
    t_submit = time.time()
    fids = [
        fleet.submit_request(p, max_new_tokens=max_new_tokens, temperature=0.0)
        for p in prompts
    ]

    finished: dict[str, dict[str, Any]] = {}

    def all_done():
        # The fleet stamps its own TTFT on the read that first sees a
        # request finished, so each result is kept from that read.
        for f in fids:
            if f not in finished:
                r = fleet.result(f)
                assert r["status"] != "failed", r
                if r["status"] == "done":
                    finished[f] = r
        return len(finished) == len(fids)

    _wait_for("all requests done", all_done, timeout_s, tick=fleet.tick)
    serve_s = time.time() - t_submit
    results = [finished[f] for f in fids]

    for r, p in zip(results, prompts):
        toks = r["tokens"]
        assert len(toks) == max_new_tokens, (len(toks), r)
        assert all(isinstance(t, int) and 0 <= t < vocab for t in toks), toks
        assert r["prompt_len"] == len(p)
        for key in ("ttft_ms", "fleet_ttft_ms"):
            assert math.isfinite(r[key]) and r[key] > 0, (key, r)
    for i in range(1, len(prompts)):
        if prompts[i] == prompts[i - 1]:
            assert results[i]["tokens"] == results[i - 1]["tokens"], (
                f"identical prompts ({len(prompts[i])} tokens) decoded differently",
                results[i - 1]["tokens"], results[i]["tokens"],
            )
    status = fleet.tick()
    assert status["completed_total"] == len(fids) == status["requests_total"], status
    assert status["pending_requests"] == 0 and status["running_replicas"] == 1, status
    assert status["tokens_total"] == len(fids) * max_new_tokens, status
    for key in ("p99_latency_ms", "ttft_p50_ms", "ttft_p99_ms"):
        assert status[key] is not None and math.isfinite(status[key]) and status[key] > 0, (
            key, status[key],
        )
    (engine,) = fleet.running_replicas().values()
    engine_stats = engine.stats()
    assert engine_stats["tokens_generated"] == len(fids) * max_new_tokens, engine_stats
    assert engine.last_error is None, engine.last_error
    del engine

    fleet.stop()
    _wait_for(
        "replica torn down",
        lambda: sub.state == SubmissionState.CANCELLED and not sub.job.is_alive,
        60.0, tick=launcher.scheduler.poll,
    )
    return {
        "model": model_name, "max_slots": max_slots, "max_len": max_len,
        "prompt_lens": prompt_lens, "max_new_tokens": max_new_tokens,
        "replica_ready_s": round(ready_s, 2),
        "serve_wall_s": round(serve_s, 2),
        "ttft_ms": [r["ttft_ms"] for r in results],
        "fleet_ttft_ms": [r["fleet_ttft_ms"] for r in results],
        "p99_latency_ms": round(status["p99_latency_ms"], 1),
        "completed": status["completed_total"], "failed": 0,
        "tokens_total": status["tokens_total"],
        "hbm_estimate_gib": sub.estimate.device_total_gib if sub.estimate else None,
    }


# ---------------------------------------------------------------------------
# The smoke itself: device gate + llama-1b sizes
# ---------------------------------------------------------------------------


def smoke_config(n_dev: int):
    """Preset "1b" on this machine's chips. The preset as written (fp32 Adam
    moments) is refused by admission on one 16 GB chip at every micro-batch
    — estimate_job_hbm says 16.65 GiB at micro-batch 2 against a 15.75 GiB
    limit — so the smoke takes the bench headline's bf16 first moments, and
    then the larger of {2, 1} that the estimate puts under the chip."""
    from tpu_engine.mesh_runtime import MeshConfig
    from tpu_engine.sharding import Precision, ShardingStage, presets

    return presets()["1b"].model_copy(update=dict(
        mesh=MeshConfig(data=1, fsdp=n_dev),
        sharding_stage=(
            ShardingStage.FULL_PARTITIONING if n_dev > 1 else ShardingStage.DISABLED
        ),
        seq_len=2048,
        precision=Precision.BF16,
        moment_dtype=Precision.BF16,
        attention_impl="auto",
        gradient_accumulation_steps=1,
        warmup_steps=2,
    ))


def pick_micro_batch(cfg, n_dev: int):
    """The largest of {2, 1} per data shard that admission will accept: the
    scheduler's own gate is estimate vs live free HBM on every chip."""
    from tpu_engine.hbm_estimate import estimate_job_hbm
    from tpu_engine.tpu_manager import TPUManager

    free = min(d.hbm_free_gb for d in TPUManager().get_fleet_status().devices)
    for mb in (2, 1):
        cand = cfg.model_copy(update={"micro_batch_size": mb})
        need = estimate_job_hbm(cand, n_dev).device_total_gib
        _say(f"admission: micro-batch {mb} needs {need:.2f} GiB/device, "
             f"{free:.2f} GiB free -> {'accepts' if need <= free else 'refuses'}")
        if need <= free:
            return cand
    raise SystemExit("admission accepts neither micro-batch 2 nor 1")


def main() -> None:
    import platform

    import jax
    import jaxlib
    import libtpu

    from tpu_engine import native
    from tpu_engine.profiler import peak_flops_per_chip
    from tpu_engine.sharding import presets

    # The comm flags depend on the preset only, the mesh on the device
    # count: start up (environment first), then look at the devices.
    up = start_up(presets()["1b"])
    devices = jax.devices()
    not_tpu = [d for d in devices if d.platform != "tpu"]
    if not_tpu:
        raise SystemExit(
            f"chip_smoke needs TPU devices; found {sorted({d.platform for d in devices})}"
        )
    for d in devices:
        peak_flops_per_chip(d)  # raises on a device_kind outside the peak table
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    _say(f"device {device}")
    _say(f"python {platform.python_version()} jax {jax.__version__} "
         f"jaxlib {jaxlib.__version__} libtpu {libtpu.__version__}")
    assert up["cache_dir"] and os.path.isdir(up["cache_dir"]), up
    entries_before = len(os.listdir(up["cache_dir"]))
    _say(f"compile cache {up['cache_dir']} ({entries_before} entries at start)")
    _say(f"comm flags: {up['comm_flags']}")
    assert up["comm_flags"]["in_force"], up["comm_flags"]
    _say(f"dataset reader: {native.status()} (this run feeds a synthetic batch)")

    n_dev = len(devices)
    cfg = pick_micro_batch(smoke_config(n_dev), n_dev)

    _say("phase A: train 8 steps through the launcher")
    launcher, job_id, train = phase_train(cfg, steps=8)
    _say("phase A readings (smoke, not benchmark): " + json.dumps(train))
    if n_dev > 1:
        # Nothing resident on chip 0 only: every chip holds ~1/n of the
        # params + optimizer state, and all of them were busy.
        per_dev = list(train["state_gib_per_device"].values())
        assert len(per_dev) == n_dev and max(per_dev) <= 1.05 * min(per_dev), per_dev
        assert min(train["midrun_bytes_in_use_gib"]) > 0.5 * max(
            train["midrun_bytes_in_use_gib"]
        ), train["midrun_bytes_in_use_gib"]

    _say("phase B: delete_job gives the chips back")
    released = phase_release(launcher, job_id)
    _say("phase B readings: " + json.dumps(released))

    _say("phase C: 8 requests through ServingFleet on the same scheduler")
    serve = phase_serve(
        launcher, "llama-1b", max_slots=8, max_len=1024,
        prompt_lens=[64, 64, 128, 128, 256, 256, 512, 512], max_new_tokens=32,
    )
    _say("phase C readings (smoke, not benchmark): " + json.dumps(serve))
    launcher.scheduler.shutdown()

    entries_after = len(os.listdir(up["cache_dir"]))
    assert entries_after > 0, "the compile cache directory is empty after the run"
    _say(f"compile cache {up['cache_dir']}: {entries_before} -> {entries_after} entries")
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"chip_smoke_{n_dev}chip.jsonl"), "a") as f:
        f.write(json.dumps({
            "device": device, "start_up": up, "cache_entries": [entries_before, entries_after],
            "train": train, "release": released, "serve": serve,
        }) + "\n")
    sys.stdout.flush()
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
