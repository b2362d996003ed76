"""A serving cell: one ``tensor_parallel=1`` replica of the configuration
behind ``ServingFleet`` over ``ContinuousBatcher``, on the launcher's own
scheduler; the traffic file's generator decides open or closed loop.

Set-up builds the replica, sends one request per prefill shape the traffic
will use, then runs the traffic's lead-in; the window opens on a server in its
steady state. Token and dispatch timestamps come from ``program.BatcherShim``.
After the window (and, open loop, the drain of what was due in it) the replica
is torn down and the float32 reference scores a seeded sample of the finished
requests, the longest among them.
"""

from __future__ import annotations

import gc
import json
import math
import statistics
import time
from functools import partial

from . import check, common, program, stats
from .manifest import load_by_name

POLL_S = 0.02


def _spec(cell: dict, seed: int, control: int):
    from tpu_engine.serving_fleet import ServingReplicaSpec
    from tpu_engine.sharding import Precision

    p = dict(cell["config"]["program"])
    p["compute_dtype"] = Precision[p.get("compute_dtype", "BF16")]
    if control:
        p["weight_quant"] = "int8"  # the control: the program's own lower-precision path
    return ServingReplicaSpec(model_name=cell["config_entry"]["name"], seed=seed, **p)


class Client:
    """Submits through the fleet and keeps, per request, what the metrics
    need. The engine's request id ties a fleet request to the shim's stamps."""

    def __init__(self, fleet, shim):
        self.fleet, self.shim = fleet, shim
        self.reqs: dict[str, dict] = {}

    def submit(self, prompt, max_new_tokens, due=None, measured=True) -> str:
        t_send = time.perf_counter()
        fid = self.fleet.submit_request(prompt, max_new_tokens=max_new_tokens, temperature=0.0)
        self.reqs[fid] = {"prompt": prompt, "want": max_new_tokens, "due": due if due is not None else t_send,
                          "sent": t_send, "measured": measured, "done": False, "failed": False,
                          "tokens": None, "rid": None}
        return fid

    def poll(self, fids) -> list[str]:
        """Read results of ``fids``; returns those that just finished."""
        finished = []
        for fid in fids:
            r = self.reqs[fid]
            if r["done"]:
                continue
            out = self.fleet.result(fid)
            if out["status"] in ("done", "failed"):
                r["done"], r["failed"] = True, out["status"] == "failed"
                r["tokens"] = list(out.get("tokens") or [])
                r["rid"] = self.fleet._requests[fid]["engine_rid"]
                finished.append(fid)
        return finished

    def open_fids(self):
        return [f for f, r in self.reqs.items() if not r["done"]]

    def wait_all(self, timeout_s: float) -> None:
        deadline = time.perf_counter() + timeout_s
        while self.open_fids() and time.perf_counter() < deadline:
            self.poll(self.open_fids())
            time.sleep(POLL_S)


def run(cell: dict, args, t_process_start: float) -> dict:
    import numpy as np

    from tpu_engine import tracing
    from tpu_engine.launcher import TPULauncher
    from tpu_engine.scheduler import SubmissionState
    from tpu_engine.serving_fleet import AutoscalerConfig, ReplicaAutoscaler, ServingFleet

    marks = common.Marks(t_process_start)
    marks("imports")
    cell = common.sized(cell)
    config, traffic = cell["config"], cell["traffic"]
    chips = cell["cell"]["chips"]
    seed = program.seed32(args.seed)
    program.model_config(config, cell["config_entry"]["name"])
    spec = _spec(cell, seed, args.control)
    up = program.start_up(spec.placement_config())  # environment first, then the backend
    device = common.gate_devices(chips)
    marks("backend_up")
    compiles = program.CompileCounter()
    shim = program.BatcherShim()
    shim.install()
    ref = load_by_name("reference", config["reference"])
    gen = load_by_name("harness/generators", traffic["generator"])
    plan = gen.plan(traffic, config["vocab_size"], seed, args.seconds)
    run_tag, trace = common.run_tag(cell, args), common.trace_window(cell, args)

    launcher = TPULauncher()
    fleet = ServingFleet(launcher.scheduler, spec,
                         autoscaler=ReplicaAutoscaler(AutoscalerConfig(min_replicas=1, max_replicas=1)))
    t_launch = time.perf_counter()
    if fleet.scale_to(1) != 1:
        raise SystemExit("the fleet did not take its one replica")
    (sid,) = fleet.status()["replicas"]
    sub = launcher.scheduler.get(sid)
    while not fleet.running_replicas():
        if sub.state in (SubmissionState.FAILED, SubmissionState.CANCELLED):
            raise SystemExit(f"replica did not start: {sub.describe()}")
        if time.perf_counter() - t_launch > 900:
            raise SystemExit("replica not ready within 900 s")
        fleet.tick()
        time.sleep(0.05)
    replica_ready_s = time.perf_counter() - t_launch
    marks("replica_ready")
    (engine,) = fleet.running_replicas().values()
    client = Client(fleet, shim)

    # Warm every prefill shape the traffic uses (one request per padded
    # prompt length), the decode chunk, insert and reset.
    pad = engine.prefill_pad_to
    seen, warm = set(), []
    rng = np.random.default_rng([seed, 11])
    for n_prompt, n_out in plan.warm_shapes():
        bucket = -(-n_prompt // pad) * pad
        if bucket not in seen:
            seen.add(bucket)
            warm.append(client.submit(rng.integers(0, config["vocab_size"], n_prompt).tolist(),
                                      n_out, measured=False))
    client.wait_all(900)
    marks("shapes_warm")
    first_dispatch_s = (shim.step_ends[0] - t_launch) if shim.step_ends else None
    warm_failed = [f for f in warm if client.reqs[f]["failed"] or not client.reqs[f]["done"]]
    if warm_failed:
        raise SystemExit(f"{len(warm_failed)} warm-up requests did not finish")

    lag: list[float] = []
    ticked = [time.perf_counter()]

    def housekeeping():
        now = time.perf_counter()
        if now - ticked[0] >= 0.5:  # the fleet's control tick, as its operator polls it
            ticked[0] = now
            with program.host_span("fleet.tick"):
                fleet.tick()

    if trace is not None:
        trace.start()
    if gen.LOOP == "open":
        reqs = plan.requests
        t_open = time.perf_counter() + plan.lead_in_s + 0.05
        i, opened = 0, False
        end = t_open + args.seconds
        measured: list[str] = []
        while True:
            now = time.perf_counter()
            if not opened and now >= t_open:
                opened = True
                common.quiet_collector()
                compiles.mark()
                if trace is not None:
                    trace.window_opened()
            while i < len(reqs) and t_open + reqs[i].due_s <= time.perf_counter():
                r = reqs[i]
                due = t_open + r.due_s
                with program.host_span("client.submit"):
                    fid = client.submit(r.prompt, r.max_new_tokens, due=due, measured=r.measured)
                if r.measured:
                    measured.append(fid)
                    lag.append(client.reqs[fid]["sent"] - due)
                i += 1
            if trace is not None and trace.due():
                trace.stop()
            if i >= len(reqs) and now >= end:
                break
            client.poll(client.open_fids())
            housekeeping()
            nxt = t_open + reqs[i].due_s if i < len(reqs) else end
            with program.host_span("client.wait"):
                time.sleep(max(0.0, min(POLL_S, nxt - time.perf_counter())))
        window_compiles = compiles.since_mark()
        t_close = end
        if trace is not None:
            trace.finish()
        client.wait_all(plan.drain_s)
    else:
        live: list[str] = []
        for _ in range(plan.clients):
            p, n = plan.next_request()
            live.append(client.submit(p, n, measured=False))
        # The window opens once every slot decodes and the lead-in has run.
        t_fill = time.perf_counter()
        while True:
            for fid in client.poll(live):
                live.remove(fid)
                p, n = plan.next_request()
                live.append(client.submit(p, n, measured=False))
            st = engine.stats()
            if (st["active_slots"] == plan.clients and st["prefilling"] == 0 and st["queued"] == 0
                    and time.perf_counter() - t_fill >= plan.lead_in_s):
                break
            if time.perf_counter() - t_fill > 600:
                raise SystemExit("slots did not fill within 600 s")
            time.sleep(POLL_S)
        common.quiet_collector()
        compiles.mark()
        if trace is not None:
            trace.window_opened()
        t_open = time.perf_counter()
        end = t_open + args.seconds
        measured = list(live)
        for fid in live:
            client.reqs[fid]["measured"] = True
        while time.perf_counter() < end:
            for fid in client.poll(live):
                live.remove(fid)
                p, n = plan.next_request()
                with program.host_span("client.submit"):
                    fid2 = client.submit(p, n)
                live.append(fid2)
                measured.append(fid2)
            if trace is not None and trace.due():
                trace.stop()
            housekeeping()
            with program.host_span("client.wait"):
                time.sleep(POLL_S)
        window_compiles = compiles.since_mark()
        t_close = end
        if trace is not None:
            trace.finish()
    gc.enable()

    # --- reduce the timestamps -------------------------------------------------
    idx = [k for k, t in enumerate(shim.step_ends) if t_open <= t <= t_close]
    ends = [shim.step_ends[k] for k in idx]
    toks = [shim.step_tokens[k] for k in idx]
    # Every token emitted by the dispatches that end inside the window, over
    # first dispatch end to last: all the work over all the time.
    serve_tokens_per_s = stats.window_rate(ends, toks[1:])
    seg = stats.segment_rates(ends, toks[1:])  # printed, not reported
    occupancy = [shim.step_active[k] for k in idx]
    ttft, tpot, failed, short = [], [], 0, 0
    for fid in measured:
        r = client.reqs[fid]
        stamps = shim.emit_times.get(r["rid"], []) if r["rid"] is not None else []
        if gen.LOOP == "closed" and not r["done"]:
            continue  # still decoding when the window closed: neither finished nor failed
        if r["failed"] or not r["done"] or not stamps:
            failed += 1
            ttft.append(math.inf)
            tpot.append(math.inf)
            continue
        if len(r["tokens"]) != r["want"]:
            short += 1
        ttft.append((stamps[0] - r["due"]) * 1e3)
        if len(stamps) > 1:
            tpot.append((stamps[-1] - stamps[0]) / (len(stamps) - 1) * 1e3)
    attempted = len(ttft)
    backlog = {}
    if gen.LOOP == "open":
        for share in (1 / 3, 2 / 3, 1.0):
            t = t_open + share * args.seconds
            n = 0
            for fid in measured:
                r = client.reqs[fid]
                first = (shim.emit_times.get(r["rid"]) or [math.inf])[0] if r["rid"] is not None else math.inf
                n += r["due"] <= t < first
            backlog[f"waiting_for_first_token_at_{share:.2f}"] = n
    with open(common.out_path("steps", run_tag + ".json"), "w") as f:
        json.dump({"dispatch_ends_s": [t - t_open for t in ends], "dispatch_tokens": toks,
                   "ttft_ms": [x if x != math.inf else None for x in ttft],
                   "tpot_ms": [x if x != math.inf else None for x in tpot]}, f)
    print(json.dumps({**({} if common.rehearsal() else {"segments_tokens_per_s": seg}), "dispatches": len(ends), "requests_measured": attempted, "backlog": backlog,
                      "ttft_ms_p50_mean_p90": [stats.percentile(ttft, 50), statistics.fmean(ttft), stats.percentile(ttft, 90)] if ttft and not common.rehearsal() else None,
                      "compilations_in_window": window_compiles, "compilations_total": compiles.counts,
                      "lead_in_s": plan.lead_in_s, "warm_requests": len(warm),
                      "setup_marks_s": {**marks.at, "window_open": round(t_open - t_process_start, 3)}}), flush=True)

    engine_stats = engine.stats()
    rec = tracing.get_recorder()
    route_wait_ms = []
    for fid in measured:
        evs = rec.events(trace_id=fleet._requests[fid]["trace_id"], limit=0)
        enq = [e["ts"] for e in evs if e["name"] == "enqueue"]
        routed = [e["ts"] for e in evs if e["name"] == "route"]
        if enq and routed:
            route_wait_ms.append((routed[0] - enq[0]) * 1e3)
    admission_spans = rec.spans(trace_id=sub.trace_id, limit=0)
    estimate_gib = sub.estimate.device_total_gib if sub.estimate else None
    peak = common.memory_peak_bytes(chips)
    setup_s = t_open - t_process_start

    # --- tear down, then let the reference score a sample -----------------------
    done = [f for f in measured if client.reqs[f]["done"] and not client.reqs[f]["failed"]
            and client.reqs[f]["tokens"]]
    del engine
    fleet.stop()
    deadline = time.time() + 60
    while (sub.state != SubmissionState.CANCELLED or sub.job.is_alive) and time.time() < deadline:
        launcher.scheduler.poll()
        time.sleep(0.05)
    launcher.scheduler.shutdown()
    shim.uninstall()
    gc.collect()

    t_ref = time.perf_counter()
    n_sample = int(config["check"].get("sample_requests", 4))
    by_len = sorted(done, key=lambda f: len(client.reqs[f]["prompt"]) + len(client.reqs[f]["tokens"]))
    sample = by_len[-1:] if by_len else []
    rest = [f for f in done if f not in sample]
    pick = np.random.default_rng([seed, 13]).permutation(len(rest))[:max(n_sample - 1, 0)]
    sample += [rest[k] for k in pick]
    gaps: list[float] = []
    margins: list[float] = []
    rows_fixed = -(-max((client.reqs[f]["want"] for f in measured), default=128) // 128) * 128
    score = partial(ref.served_logits, cfg=config, length=spec.max_len, rows=rows_fixed)
    if sample:
        params = ref.init_params(config, seed)
        for fid in sample:
            r = client.reqs[fid]
            lg, margin = score(params, r["prompt"], r["tokens"])
            gaps += check.served_gaps(np.asarray(lg), r["tokens"])
            margins += np.asarray(margin).tolist()
        del params
    ref_s = time.perf_counter() - t_ref
    # Where the reference's own routing is all but a tie, which experts a token
    # gets is decided by rounding; such positions are left out (dense: none).
    tie = float(config["check"].get("routing_margin_min", 0.0))
    decided = [g for g, m in zip(gaps, margins) if m >= tie]
    correct, rows = check.compare_serving(decided, config["check"], failed, short)
    rows[0]["positions_left_out_as_routing_ties"] = len(gaps) - len(decided)
    with open(common.out_path("steps", run_tag + ".check.json"), "w") as f:
        json.dump({"gaps": gaps, "margins": [m if m != math.inf else None for m in margins]}, f)
    lowered = window_compiles["lowered"]
    rows.append({"number": "programs_lowered_in_window", "value": float(lowered), "limit": 0.0,
                 "ok": lowered == 0})
    correct = correct and lowered == 0
    print(json.dumps({"reference_s": ref_s, "sampled_requests": len(sample), "tokens_compared": len(decided),
                      "positions_left_out_as_routing_ties": len(gaps) - len(decided)}), flush=True)

    run = {
        "cell": cell, "chips": chips, "device": device, "kind": "serve", "loop": gen.LOOP,
        "seconds": args.seconds, "setup_s": setup_s, "first_step_s": first_dispatch_s,
        "replica_ready_s": replica_ready_s, "ttft_ms": ttft, "tpot_ms": tpot, "lag_s": lag,
        "occupancy": occupancy, "slots": spec.max_slots, "engine_stats": engine_stats,
        "route_wait_ms": route_wait_ms, "spans": admission_spans, "estimate_gib": estimate_gib,
        "peak_bytes": peak, "dispatch_ends": ends, "dispatch_tokens": toks,
        "dispatch_context": [shim.step_context[k] for k in idx],
        "decode_chunk_steps": spec.decode_chunk_steps, "trace": None, "up": up,
    }
    values = {"serve_tokens_per_s": serve_tokens_per_s, "setup_s": setup_s}
    if ttft:
        values.update(ttft_p90_ms=stats.percentile(ttft, 90), tpot_p90_ms=stats.percentile(tpot, 90))
    return common.assemble(run, args, trace, correct, attempted, failed, values,
                           {"requests": attempted, "dispatches": len(ends), "compared": rows})
