"""A training cell: the configuration through ``TPULauncher`` /
``FleetScheduler`` and the supervisor loop, fed by ``data_fn(step)``.

One job is built, driven from the seed through its first three steps (whose
loss, first gradient and parameter change the float32 reference later
follows), kept running through ``warmup_s`` seconds of continuous steps, and
the same job is then measured: the window opens on a step boundary, every
``data_fn`` call is a boundary, and ``train_tokens_per_s_chip`` is the tokens
of all whole steps that end inside the window over the seconds from the first
boundary to the last (``stats.window_rate``): no partial step, no division by
the nominal window, and a stall counts for what it took.
"""

from __future__ import annotations

import gc
import json
import statistics
import threading
import time

from . import check, common, counts, program, stats
from .manifest import load_by_name

CHECK_STEPS = 3


def _train_config(cell: dict, seed: int, on_tpu: bool):
    from tpu_engine.mesh_runtime import MeshConfig
    from tpu_engine.sharding import Precision, ShardingStage, TPUTrainConfig

    p = dict(cell["config"]["program"])
    t = cell["traffic"]
    mesh = MeshConfig(**p.pop("mesh"))
    stage = ShardingStage[p.pop("sharding_stage")]
    for key in ("precision", "param_dtype", "moment_dtype"):
        if p.get(key) is not None:
            p[key] = Precision[p[key]]
    if not on_tpu:
        p["attention_impl"] = "xla"  # the rehearsal has no Mosaic
    return TPUTrainConfig(
        model_name=cell["config_entry"]["name"], mesh=mesh, sharding_stage=stage,
        seq_len=t["seq_len"], micro_batch_size=t["micro_batch_per_shard"],
        gradient_accumulation_steps=t.get("accumulation", 1), seed=seed, **p,
    )


def _adam_mu(opt_state):
    import jax

    for node in jax.tree.leaves(opt_state, is_leaf=lambda n: hasattr(n, "mu")):
        if hasattr(node, "mu"):
            return node.mu
    raise RuntimeError("no Adam first moment in the optimizer state")


def run(cell: dict, args, t_process_start: float) -> dict:
    import jax

    from tpu_engine import tracing
    from tpu_engine.hbm_estimate import estimate_job_hbm
    from tpu_engine.launcher import TPULauncher

    marks = common.Marks(t_process_start)
    marks("imports")
    cell = common.sized(cell)
    config, traffic = cell["config"], cell["traffic"]
    chips = cell["cell"]["chips"]
    seed = program.seed32(args.seed)
    program.model_config(config, cell["config_entry"]["name"])
    tcfg = _train_config(cell, seed, on_tpu=not common.rehearsal())
    if args.control:
        tcfg = tcfg.model_copy(update={"quant_training": "int8"})
    up = program.start_up(tcfg)  # environment first: comm flags precede the backend
    device = common.gate_devices(chips)
    marks("backend_up")
    compiles = program.CompileCounter()
    ref = load_by_name("reference", config["reference"])
    gen = load_by_name("harness/generators", traffic["generator"])
    rows = tcfg.effective_batch_size // tcfg.gradient_accumulation_steps
    plan = gen.plan(traffic, config["vocab_size"], seed, args.seconds, rows=rows)
    run_tag, trace = common.run_tag(cell, args), common.trace_window(cell, args)

    leaf_norms = jax.jit(ref.leaf_norms)
    b1 = tcfg.beta1
    boundaries: list[float] = []
    state = {"job": None, "window_open": None, "warm_from": None,
             "prog": {"grad": None, "dparam": None}, "error": None, "first_step_s": None, "loop_span": None}
    job_ready = threading.Event()

    def to_lists(tree):
        return {k: [float(x) for x in v] for k, v in jax.device_get(tree).items()}

    def after_step(step: int) -> None:
        """Readings of the job's own state, between two steps."""
        job = state["job"]
        if step == 1:
            mu = _adam_mu(job._state["opt_state"])
            state["prog"]["grad"] = {
                k: [x / (1.0 - b1) for x in v] for k, v in to_lists(leaf_norms(mu)).items()}
        if step == CHECK_STEPS:
            flat, _ = jax.tree_util.tree_flatten_with_path(job._state["params"])
            state["prog"]["dparam"] = {
                "/".join(ref.path_keys(path)): ref.change_norms(config, seed, ref.path_keys(path), leaf)
                for path, leaf in flat}
            state["prog"]["loss"] = [float(x) for x in job.monitor.get_loss_curve()["losses"][:CHECK_STEPS]]
            if trace is not None:
                trace.start()
            state["warm_from"] = time.perf_counter()

    def data_fn(step: int):
        now = time.perf_counter()
        try:
            if state["loop_span"] is not None:
                state["loop_span"].__exit__(None, None, None)
                state["loop_span"] = None
            if step >= 1 and state["first_step_s"] is None:
                state["first_step_s"] = now - state["t_launch"]
                marks("first_step_done")
            if step in (1, CHECK_STEPS) and not state["prog"]["dparam"]:
                job_ready.wait()
                with program.host_span("check_readings"):
                    after_step(step)
                marks(f"readings_after_step{step}")
                now = time.perf_counter()
            if (state["window_open"] is None and state["warm_from"] is not None
                    and now - state["warm_from"] >= plan.warmup_s):
                common.quiet_collector()
                compiles.mark()
                if trace is not None:
                    trace.window_opened()
                now = time.perf_counter()
                state["window_open"] = len(boundaries)
            if trace is not None and trace.due():
                trace.stop()
            boundaries.append(now)
            with program.host_span("data_fn"):
                batch = plan.batch(step)
            # Until the next call the supervisor's loop body runs: dispatch, the
            # blocking metric read, health check, bookkeeping.
            state["loop_span"] = jax.profiler.TraceAnnotation("onchip.supervisor.step")
            state["loop_span"].__enter__()
            return batch
        except BaseException as e:  # surfaces in the supervisor thread otherwise
            state["error"] = repr(e)
            raise

    launcher = TPULauncher()
    marks("launcher_built")
    state["t_launch"] = time.perf_counter()
    res = launcher.launch(tcfg, max_steps=10**9, data_fn=data_fn, block=False)
    if res.status != "launched":
        raise SystemExit(f"launch: {res.status} {res.error} queue_position={res.queue_position}")
    job = launcher.get_job(res.job_id)
    sub = launcher.scheduler.get(res.submission_id)
    state["job"] = job
    job_ready.set()
    marks("launch_returned")

    # The window: from the boundary that opened it, for --seconds.
    while True:
        time.sleep(0.25)
        if state["error"] or not job.is_alive:
            break
        w0 = state["window_open"]
        if (w0 is not None and len(boundaries) > w0
                and time.perf_counter() >= boundaries[w0] + args.seconds + 0.05):
            break
    window_compiles = compiles.since_mark()
    if trace is not None:
        trace.finish()
    launcher.stop_job(res.job_id)
    job.join(timeout=120)
    gc.enable()
    desc = job.describe()
    if state["error"] or desc["status"] not in ("stopped", "completed"):
        raise SystemExit(f"train job ended {desc['status']}: {state['error'] or desc.get('error')}")

    w0 = state["window_open"]
    t_open = boundaries[w0]
    inside = [b for b in boundaries[w0:] if b <= t_open + args.seconds]
    n_steps = len(inside) - 1
    tokens = plan.tokens_per_step
    rate_chip = stats.window_rate(inside, [tokens] * n_steps) / chips
    seg = stats.segment_rates(inside, [tokens] * n_steps)  # printed, not reported
    intervals = [b - a for a, b in zip(inside, inside[1:])]
    with open(common.out_path("steps", run_tag + ".json"), "w") as f:
        json.dump({"intervals_s": intervals, "segments_tokens_per_s_chip": [s / chips for s in seg]}, f)
    timing = {} if common.rehearsal() else {
        "segments_tokens_per_s_chip": [s / chips for s in seg],
        "step_interval_ms": {"min": min(intervals) * 1e3, "median": statistics.median(intervals) * 1e3,
                             "max": max(intervals) * 1e3}}
    print(json.dumps({**timing, "whole_steps": n_steps,
                      "compilations_in_window": window_compiles, "compilations_total": compiles.counts,
                      "warmup_s": plan.warmup_s, "setup_marks_s": {**marks.at, "window_open": round(boundaries[w0] - t_process_start, 3)}}),
          flush=True)

    est = estimate_job_hbm(tcfg, chips)
    peak = common.memory_peak_bytes(chips)
    spans = tracing.get_recorder().spans(trace_id=sub.trace_id, limit=0)
    setup_s = t_open - t_process_start

    # Free the program's state, then the float32 reference follows the same
    # three steps on the same batches (its time is in no metric).
    if not launcher.delete_job(res.job_id):
        raise SystemExit("delete_job did not release the finished job")
    launcher.scheduler.shutdown()
    del job
    gc.collect()
    t_ref = time.perf_counter()
    hyper = {k: getattr(tcfg, k) for k in
             ("learning_rate", "warmup_steps", "beta1", "beta2", "weight_decay", "grad_clip_norm")}
    ref_out = ref.train_steps(config, hyper, seed,
                              [plan.batch(s)[0] for s in range(CHECK_STEPS)])
    ref_s = time.perf_counter() - t_ref
    correct, rows = check.compare_training(state["prog"], ref_out, config["check"])
    if window_compiles["lowered"]:
        correct = False
    rows.append({"number": "programs_lowered_in_window", "value": float(window_compiles["lowered"]),
                 "limit": 0.0, "ok": window_compiles["lowered"] == 0})
    print(json.dumps({"reference_s": ref_s, "reference_split_s": ref_out.get("split_s"),
                      "worst_leaf": {r["number"]: r["leaf"] for r in rows if "leaf" in r},
                      "loss_program_reference": [[r["program"], r["reference"]] for r in rows if "program" in r]}),
          flush=True)

    run = {
        "cell": cell, "chips": chips, "device": device, "kind": "train", "seconds": args.seconds,
        "setup_s": setup_s, "first_step_s": state["first_step_s"],
        "intervals_s": intervals, "tokens_per_step": tokens, "rate_chip": rate_chip,
        "profile": desc.get("profile"), "spans": spans, "estimate_gib": est.device_total_gib,
        "peak_bytes": peak, "flops_per_token": counts.train_flops_per_token(config, plan.seq_len),
        "seq_len": plan.seq_len, "rows_per_chip": plan.micro * plan.accum, "trace": None, "up": up,
    }
    return common.assemble(run, args, trace, correct, n_steps, 0,
                           {"train_tokens_per_s_chip": rate_chip, "setup_s": setup_s},
                           {"whole_steps": n_steps, "compared": rows})
