"""The phase clock where it runs: a real ``TrainingJob``'s loop, a real
``ContinuousBatcher``'s ``step``, and a fleet request's lifecycle spans.
(The clock itself is in ``test_profiler.py``.)"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_engine import profiler, tracing
from tpu_engine.mesh_runtime import MeshConfig
from tpu_engine.models import transformer as tfm
from tpu_engine.serving import BATCHER_PHASES, ContinuousBatcher
from tpu_engine.serving_fleet import REQUEST_STAGES, build_replica_engine
from tpu_engine.supervisor import SUPERVISOR_PHASES, JobStatus, TrainingJob

from tests.test_serving_fleet import (  # noqa: F401 — sched_factory is a fixture
    make_fleet,
    mock_fleet_fn,
    sched_factory,
    small_spec,
    wait_until,
)
from tests.test_scheduler import cfg as train_cfg
from tests.test_tracing import tiny_config


def test_training_job_profile_covers_the_whole_iteration(tmp_path):
    """Every supervisor phase is reported, and ``total`` is the time between
    two ``data_fn`` calls — not the part of it up to the metric read."""
    calls = []
    cfg = tiny_config(tmp_path / "ckpt", total_steps=8, checkpoint_interval_steps=4)

    def data_fn(step):
        calls.append(time.perf_counter())
        time.sleep(0.01)  # a data phase that can be told from nothing
        return job.program.synthetic_batch(step)

    job = TrainingJob("phase-job", cfg, data_fn=data_fn)
    job.start()
    job.join(timeout=300)
    assert job.status == JobStatus.COMPLETED, job.error
    prof = job.describe()["profile"]
    assert prof["steps_seen"] == 8
    assert set(prof["phases"]) == set(SUPERVISOR_PHASES) | {"other"}
    assert prof["phases"]["data"]["p50_ms"] >= 10
    assert prof["phases"]["device"]["mean_ms"] > 0
    # A save ran at steps 4 and 8: the checkpoint phase saw it.
    assert prof["phases"]["checkpoint"]["mean_ms"] > 0
    # Between the first and the last data_fn call lie seven whole iterations;
    # the eighth ends where the loop leaves its body.
    intervals = np.diff(calls)
    assert len(intervals) == 7
    totals = list(job.profiler._totals)
    assert len(totals) == 8
    assert totals[:7] == pytest.approx(list(intervals), abs=2e-3)
    assert prof["total"]["mean_ms"] == pytest.approx(np.mean(totals) * 1e3)
    # The phases account for the total: the remainder is small and has a name.
    fractions = sum(p["fraction"] for p in prof["phases"].values())
    assert fractions == pytest.approx(1.0, abs=0.02)
    assert prof["phases"]["other"]["fraction"] < 0.2
    # What the operator reads is a whole-iteration time too.
    assert job.last_step_time_s >= 0.01
    # The attempt span's step_s (goodput's cap on productive time) counts
    # every iteration once: the wall time from the first data_fn call to the
    # last, plus the last iteration.
    (attempt,) = [sp for sp in tracing.get_recorder().spans(trace_id=job.trace_id, limit=0)
                  if sp["kind"] == "attempt"]
    assert attempt["attrs"]["step_s"] == pytest.approx(sum(totals), abs=1e-5)
    assert attempt["attrs"]["step_s"] == pytest.approx(
        calls[-1] - calls[0] + totals[-1], abs=5e-3)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = tfm.MODEL_CONFIGS["gpt-tiny"]
    return cfg, tfm.init_params(jax.random.PRNGKey(3), cfg, dtype=jnp.float32)


def test_batcher_stats_show_phases_counters_and_ordered_stamps(tiny_model):
    cfg, params = tiny_model
    srv = ContinuousBatcher(params, cfg, max_slots=2, max_len=96, chunk_steps=4,
                            compute_dtype=jnp.float32, prefill_pad_to=16,
                            prefill_chunk=16)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist() for n in (7, 40, 3)]
    rids = [srv.submit(p, max_new_tokens=n) for p, n in zip(prompts, (6, 9, 5))]
    for _ in range(60):
        if all(srv.result(r)["status"] == "done" for r in rids):
            break
        srv.step()
    st = srv.stats()
    assert "profile" not in st  # the router's per-request read stays counters
    prof = srv.profile()
    assert set(prof["phases"]) == set(BATCHER_PHASES) | {"other"}
    for phase in ("admit", "prefill", "first_token", "stage", "device", "emit"):
        assert prof["phases"][phase]["mean_ms"] > 0, phase
    assert prof["phases"]["idle"]["mean_ms"] == 0  # step() was driven by hand
    # Every first token comes from the prefill logits, the rest from decode
    # dispatches that compute chunk_steps tokens a slot and throw the
    # overshoot away.
    emitted = st["decode_tokens_emitted_total"]
    assert emitted == st["tokens_generated"] - len(rids) == 5 + 8 + 4
    assert emitted < st["decode_tokens_computed_total"]
    assert st["decode_tokens_computed_total"] % srv.chunk_steps == 0
    for rid in rids:
        out = srv.result(rid)
        stamps = [out[k] for k in ("submitted_at", "admitted_at", "prefill_started_at",
                                   "first_token_at", "finished_at")]
        assert stamps == sorted(stamps), out
    # The third request waited for a slot: its queue time is real.
    third = srv.result(rids[2])
    assert third["admitted_at"] > srv.result(rids[0])["first_token_at"]


def test_serve_forever_puts_its_sleep_in_the_idle_phase(tiny_model):
    cfg, params = tiny_model
    srv = ContinuousBatcher(params, cfg, max_slots=1, max_len=32,
                            compute_dtype=jnp.float32, prefill_pad_to=16)
    stop = threading.Event()
    t = threading.Thread(target=srv.serve_forever, args=(stop,), kwargs={"idle_sleep": 0.005})
    t.start()
    try:
        assert wait_until(lambda: srv.profile()["steps_seen"] >= 5, timeout=30)
    finally:
        stop.set()
        t.join(timeout=30)
    idle = srv.profile()["phases"]["idle"]
    assert idle["p50_ms"] >= 5 and idle["fraction"] > 0.5


def test_fleet_request_trace_holds_its_four_stages(sched_factory):
    """Closing a request's span records engine_queue, prefill_wait, prefill
    and decode under it, contiguous, from the engine's own stamps."""
    s = sched_factory(max_concurrent_jobs=2, fleet_fn=mock_fleet_fn)
    spec = small_spec(max_slots=2, max_len=64, prefill_chunk=16)
    fleet = make_fleet(s, spec=spec, engine_factory=build_replica_engine)
    fleet.scale_to(1)
    assert wait_until(lambda: len(fleet.running_replicas()) == 1, timeout=120)
    fid = fleet.submit_request(list(range(1, 21)), max_new_tokens=6)
    assert wait_until(lambda: fleet.result(fid)["status"] == "done", timeout=120)
    out = fleet.result(fid)
    rec = tracing.get_recorder()
    spans = rec.spans(trace_id=out["trace_id"], limit=0)
    (root,) = [sp for sp in spans if sp["parent_id"] is None]
    names = [n for n, _, _ in REQUEST_STAGES]
    stages = {sp["name"]: sp for sp in spans
              if sp["parent_id"] == root["span_id"] and sp["name"] in names}
    assert set(stages) == set(names)
    order = [stages[n] for n in names]
    assert order[0]["t0"] >= root["t0"] and order[-1]["t1"] <= root["t1"]
    for before, after in zip(order, order[1:]):
        assert before["t1"] == after["t0"]  # contiguous: one stamp ends one, begins the next
    for sp in order:
        assert sp["duration_s"] >= 0
        assert sp["attrs"]["prompt_tokens"] == 20 and sp["attrs"]["tokens"] == 6
        assert sp["attrs"]["replica"] == out["replica"]
        assert sp["attrs"]["engine_rid"] is not None
    assert order[0]["t0"] == out["submitted_at"] and order[2]["t1"] == out["first_token_at"]
    fleet.stop()


def test_a_phase_that_waits_for_the_engines_lock_books_the_wait_as_blocked(tiny_model, monkeypatch):
    """``handoff`` is a few microseconds of host code under ``engine._lock``;
    while another thread holds the lock it is 0.2 s of wall time, and the
    clock (whose phases read the thread's CPU time under a profiler session)
    says those were spent off the CPU."""
    monkeypatch.setattr(profiler, "_tracing", lambda: True)
    cfg, params = tiny_model
    srv = ContinuousBatcher(params, cfg, max_slots=1, max_len=32,
                            compute_dtype=jnp.float32, prefill_pad_to=16)
    srv.submit([3, 4, 5], max_new_tokens=8)
    for _ in range(3):
        srv.step()  # compiled and decoding
    held = threading.Event()

    def holder():
        with srv._lock:
            held.set()
            time.sleep(0.2)

    t = threading.Thread(target=holder)
    t.start()
    assert held.wait(10)
    srv.step()
    t.join()
    srv.step()  # closes the iteration that waited
    wall, blocked = srv._profiler._phases["handoff"][-1], srv._profiler._blocked["handoff"][-1]
    assert wall >= 0.1 and blocked == pytest.approx(wall, abs=5e-3)
    calm_wall = srv._profiler._phases["handoff"][-2]  # the iteration before it
    assert calm_wall < 0.05 and srv._profiler._blocked["handoff"][-2] <= calm_wall + 1e-4
    assert srv.profile()["phases"]["handoff"]["blocked_ms"]["mean"] >= 100 / len(srv._profiler._totals)


def test_the_replicas_wait_is_phase_idle_and_says_whether_work_was_pending(sched_factory, monkeypatch):
    """``ServingReplicaJob._run`` waits after every step that produced
    nothing, through ``ContinuousBatcher.idle_wait``: the wait is phase
    ``idle`` and not ``other``, and one taken while a prompt is still
    prefilling counts in ``idle_waits_with_work_total``."""
    monkeypatch.setattr(profiler, "_tracing", lambda: True)  # the phases read the thread's clock
    s = sched_factory(max_concurrent_jobs=2, fleet_fn=mock_fleet_fn)
    spec = small_spec(max_slots=2, max_len=256, prefill_chunk=64)
    fleet = make_fleet(s, spec=spec, engine_factory=build_replica_engine)
    fleet.scale_to(1)
    assert wait_until(lambda: len(fleet.running_replicas()) == 1, timeout=120)
    (engine,) = fleet.running_replicas().values()
    assert wait_until(lambda: engine.stats()["idle_waits_total"] >= 5, timeout=30)
    empty = engine.stats()
    assert empty["idle_waits_with_work_total"] == 0  # nothing was pending yet
    # 150 tokens in chunks of 64: the steps that only advance a chunk produce
    # no token, and the loop waits after each with the prompt still prefilling
    fid = fleet.submit_request(list(range(1, 151)), max_new_tokens=4)
    assert wait_until(lambda: fleet.result(fid)["status"] == "done", timeout=120)
    assert wait_until(lambda: engine.stats()["idle_waits_total"] >= empty["idle_waits_total"] + 5, timeout=30)
    st, prof = engine.stats(), engine.profile()
    assert 1 <= st["idle_waits_with_work_total"] <= 4
    assert st["idle_waits_total"] > st["idle_waits_with_work_total"]
    idle, other = prof["phases"]["idle"], prof["phases"]["other"]
    assert idle["p50_ms"] >= 4 and idle["blocked_ms"]["p50"] == pytest.approx(idle["p50_ms"], abs=2)
    assert other["p50_ms"] < 2  # the 5 ms wait is no longer the iteration's remainder
    assert fleet.status()["replicas"][next(iter(fleet.status()["replicas"]))]["engine"]["profile"][
        "phases"]["idle"]["blocked_ms"]["mean"] > 0
    fleet.stop()


def test_a_cpu_trace_shows_the_pump_on_its_own_thread(sched_factory, tmp_path):
    """In a ``jax.profiler`` trace ``tpu_ctl.scheduler.pass`` lies on the
    ``fleet-scheduler`` thread's line with ``thread=``, ``queued=``,
    ``running=`` and ``sampled=``: 1 on the pass that admits a job, whose
    fleet sample lies inside it, 0 on the passes beside the running job,
    which take none; a phase of a loop on another thread carries
    ``blocked_us=``, and no ``tpu_engine.*`` annotation shares the pump's
    line."""
    import glob

    import jax.profiler
    from jax.profiler import ProfileData

    s = sched_factory(max_concurrent_jobs=1, fleet_fn=mock_fleet_fn, poll_interval_s=0.01)
    before = s.stats()["poll_passes_total"]
    prof = profiler.StepProfiler(loop="batcher", phases=BATCHER_PHASES)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        sub = s.submit(train_cfg())  # starts the pump; its first pass admits
        for _ in range(3):
            prof.begin_step()
            with prof.phase("stage", with_prefill=0):
                time.sleep(0.02)
        prof.end_step()
    finally:
        jax.profiler.stop_trace()
    stats = s.stats()
    assert stats["poll_passes_total"] > before and stats["poll_pass_seconds_total"] > 0
    assert sub.state.value == "running"
    assert 1 <= stats["fleet_samples_total"] < stats["poll_passes_total"]
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lines = [[(ev.name, dict(ev.stats)) for ev in ln.events if ev.name.startswith(("tpu_ctl.", "tpu_engine."))]
             for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
             for ln in plane.lines]
    # (a pump some earlier test of this process left running has a line of its own)
    pumps = [evs for evs in lines if any(name == "tpu_ctl.scheduler.pass" for name, _ in evs)]
    assert pumps and sum(len(evs) for evs in pumps) >= 2
    for pump in pumps:
        # the mock fleet view goes through get_fleet_status, the samplers' one door, inside the pass
        assert {name for name, _ in pump} <= {"tpu_ctl.scheduler.pass", "tpu_ctl.manager.fleet_status"}
        for name, args in pump:
            assert args["thread"] == "fleet-scheduler"
            if name == "tpu_ctl.scheduler.pass":
                assert {"queued", "running", "sampled"} <= set(args)
        passes = [args for name, args in pump if name == "tpu_ctl.scheduler.pass"]
        assert {a["sampled"] for a in passes} <= {0, 1}
        # one fleet sample for each pass that says it took one, and for no other
        assert sum(a["sampled"] for a in passes) == sum(
            name == "tpu_ctl.manager.fleet_status" for name, _ in pump)
    ours = [a for pump in pumps for name, a in pump if name == "tpu_ctl.scheduler.pass"]
    assert any(a["sampled"] == 1 for a in ours) and any(
        a["sampled"] == 0 and a["running"] == 1 for a in ours)
    (loop,) = [evs for evs in lines if any(name == "tpu_engine.batcher.stage" for name, _ in evs)]
    stage = [args for name, args in loop if name == "tpu_engine.batcher.stage"]
    assert len(stage) == 3
    for args in stage:
        assert args["with_prefill"] == 0 and "blocked_us" in set(args)
        assert 15000 <= args["blocked_us"] <= 60000  # slept, not computed
    iteration = [args for name, args in loop if name == "tpu_engine.batcher.other"]
    assert len(iteration) == 3 and all(a["blocked_us"] >= 15000 for a in iteration)


def test_the_schedulers_passes_are_counted_exactly_under_threads(sched_factory):
    """``poll`` is safe to call from any thread: ``poll_passes_total`` loses
    no pass and ``poll_pass_seconds_total`` is no more than the callers saw,
    with no profiler session (``ctl_span`` is then nothing at all)."""
    s = sched_factory(max_concurrent_jobs=1, fleet_fn=mock_fleet_fn)
    before = s.stats()
    n_threads, n_each = 8, 25
    seen = [0.0] * n_threads

    def worker(k):
        for _ in range(n_each):
            t0 = time.perf_counter()
            s.poll()
            seen[k] += time.perf_counter() - t0

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    after = s.stats()
    assert after["poll_passes_total"] == before["poll_passes_total"] + n_threads * n_each
    assert 0 < after["poll_pass_seconds_total"] - before["poll_pass_seconds_total"] <= sum(seen)


def test_the_schedulers_samples_never_exceed_its_passes_under_threads(sched_factory):
    """``fleet_samples_total`` counts the passes that called ``fleet_fn``:
    under concurrent ``poll()`` it equals the calls, one a pass at most, and
    no lock-free reader of ``stats()`` ever sees it above
    ``poll_passes_total``, even when every pass samples (a queued head with
    a free slot that the fleet refuses is retried with a fresh sample)."""
    calls = []

    def fleet_fn():
        calls.append(threading.current_thread().name)
        return mock_fleet_fn()

    s = sched_factory(max_concurrent_jobs=1, fleet_fn=fleet_fn)
    s._ensure_thread = lambda: None  # the test's threads are the only pumps
    head = s.submit(train_cfg(mesh=MeshConfig(data=2, fsdp=4)))  # 8 > the mock's 7 healthy
    n_threads, n_each = 6, 20
    stop = threading.Event()
    torn = []

    def reader():
        while not stop.is_set():
            st = s.stats()
            if st["fleet_samples_total"] > st["poll_passes_total"]:
                torn.append(st)

    start = threading.Barrier(n_threads)

    def worker():
        start.wait(timeout=30)
        for _ in range(n_each):
            s.poll()

    watch = threading.Thread(target=reader)
    watch.start()
    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    stop.set()
    watch.join(timeout=10)
    assert not any(t.is_alive() for t in threads) and not watch.is_alive()
    st = s.stats()
    assert not torn
    assert head.state.value == "queued" and "healthy chip" in head.last_skip_reason
    assert st["poll_passes_total"] == n_threads * n_each
    assert st["fleet_samples_total"] == len(calls) == n_threads * n_each
    assert len(set(calls)) > 1  # more than one thread ran a sampling pass
