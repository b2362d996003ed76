"""Flight recorder: span/event invariants, bounded drops, Chrome-trace
export, step-time anomaly attribution (deterministic via the host-slow
fault seam), the auto-trace hook, and the full chaos lifecycle chain
(detect → emergency-save → requeue → shrink-admit → resume) recorded as
causally-linked spans under one job trace.
"""

import json

import pytest

from tpu_engine import faults, tracing
from tpu_engine.faults import FaultKind, FaultPlan, FaultSpec
from tpu_engine.mesh_runtime import MeshConfig
from tpu_engine.scheduler import FleetScheduler, SubmissionState
from tpu_engine.sharding import Precision, ShardingStage, TPUTrainConfig
from tpu_engine.supervisor import JobStatus, TrainingJob
from tpu_engine.tpu_manager import TPUManager
from tpu_engine.tracing import FlightRecorder, StepTimeAnomalyDetector


@pytest.fixture(autouse=True)
def _clean_process_state():
    """Fresh recorder per test (the integration paths write to the
    process-wide one) and no leaked fault plan."""
    faults.clear_active()
    prev = tracing.get_recorder()
    tracing.set_recorder(FlightRecorder())
    yield
    tracing.set_recorder(prev)
    faults.clear_active()


def tiny_config(tmp, **kw) -> TPUTrainConfig:
    base = dict(
        model_name="gpt-tiny",
        sharding_stage=ShardingStage.FULL_PARTITIONING,
        mesh=MeshConfig(data=2, fsdp=4),
        micro_batch_size=1,
        gradient_accumulation_steps=1,
        seq_len=32,
        precision=Precision.FP32,
        total_steps=10,
        activation_checkpointing=False,
        checkpoint_dir=str(tmp),
        checkpoint_interval_steps=100,
        log_every_steps=1,
    )
    base.update(kw)
    return TPUTrainConfig(**base)


# ---------------------------------------------------------------------------
# recorder invariants
# ---------------------------------------------------------------------------


def test_span_lifecycle_and_causal_links():
    rec = FlightRecorder()
    root = rec.start_span("job:x", kind="job", t0=1.0)
    tid = root.trace_id
    assert rec.trace_root(tid) == root.span_id
    # Children inherit the parent's trace; parent_id forms the causal link.
    child = rec.start_span("attempt", kind="attempt", parent=root, t0=2.0)
    assert child.trace_id == tid and child.parent_id == root.span_id
    child.end(t1=3.0, status="ok")
    root.end(t1=4.0)
    spans = rec.spans(trace_id=tid)
    assert [s["name"] for s in spans] == ["job:x", "attempt"]
    assert spans[1]["duration_s"] == 1.0
    assert spans[1]["attrs"]["status"] == "ok"
    traces = rec.traces()
    assert traces[0]["trace_id"] == tid
    assert traces[0]["root_name"] == "job:x" and traces[0]["spans"] == 2


def test_end_clamps_reversed_timestamps():
    rec = FlightRecorder()
    s = rec.record_span("x", t0=5.0, t1=4.0)  # virtual-clock skew
    assert s.t1 == 5.0 and s.duration_s == 0.0


def test_bounded_buffers_count_drops():
    rec = FlightRecorder(max_spans=4, max_events=4)
    for i in range(10):
        rec.record_span(f"s{i}", t0=float(i), t1=float(i))
        rec.event(f"e{i}", trace_id="t", ts=float(i))
    assert len(rec.spans(limit=0)) == 4
    assert len(rec.events(limit=0)) == 4
    st = rec.stats()
    # Nothing silent: totals keep counting, evictions are accounted for.
    assert st["spans_total"] == 10 and st["spans_dropped"] == 6
    assert st["events_total"] == 10 and st["events_dropped"] == 6


def test_cancel_drops_span_without_recording():
    rec = FlightRecorder()
    s = rec.start_span("retry-pass", t0=0.0)
    s.cancel()
    assert rec.spans(limit=0) == []


def test_jsonl_persistence_bounded_rotation(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    rec = FlightRecorder(persist_path=path, persist_max_bytes=400)
    for i in range(20):
        rec.record_span(f"span{i}", trace_id="t", t0=float(i), t1=float(i))
    st = rec.stats()["persist"]
    assert st["rotations"] >= 1 and st["errors"] == 0
    assert st["bytes"] <= 400
    # Both generations hold valid JSONL records.
    for p in (path, path + ".1"):
        with open(p) as f:
            recs = [json.loads(line) for line in f]
        assert all(r["record"] == "span" for r in recs)


def test_export_chrome_trace_format():
    rec = FlightRecorder()
    root = rec.start_span("job:x", kind="job", t0=1.0)
    child = rec.start_span("save", kind="checkpoint_save", parent=root, t0=2.0)
    child.end(t1=3.0)
    root.end(t1=4.0)
    rec.event("requeue", kind="scheduler", trace_id=root.trace_id, ts=2.5)
    doc = rec.export_chrome_trace(trace_id=root.trace_id)
    evs = doc["traceEvents"]
    assert all("ph" in e and "ts" in e and "pid" in e for e in evs)
    phases = {e["ph"] for e in evs}
    assert {"M", "X", "i"} <= phases
    # Causal parent link rides as a Chrome flow arrow (start + finish).
    assert "s" in phases and "f" in phases
    # Spans are complete events with a duration; instants carry scope.
    for e in evs:
        if e["ph"] == "X":
            assert e["dur"] >= 0
        if e["ph"] == "i":
            assert e["s"] == "t"
    # Non-metadata timestamps are sorted (Perfetto requirement).
    body = [e["ts"] for e in evs if e["ph"] != "M"]
    assert body == sorted(body)
    # pid lane is named after the trace via process_name metadata.
    meta = [e for e in evs if e["ph"] == "M" and e["name"] == "process_name"]
    assert meta and root.trace_id in meta[0]["args"]["name"]


# ---------------------------------------------------------------------------
# anomaly detection + attribution
# ---------------------------------------------------------------------------


def test_detector_warmup_baseline_and_sustained():
    det = StepTimeAnomalyDetector(warmup=3, ratio=1.5, min_excess_s=0.01,
                                  sustained_k=2)
    assert det.baseline_s is None
    for s in range(1, 4):
        assert det.observe(s, 0.1) is None  # warming up
    assert det.baseline_s == pytest.approx(0.1)
    a1 = det.observe(4, 0.5)
    assert a1 is not None and not a1["sustained"]
    assert a1["excess_s"] == pytest.approx(0.4)
    a2 = det.observe(5, 0.5)
    assert a2 is not None and a2["sustained"]
    # Outliers never entered the baseline — no normalising-away.
    assert det.baseline_s == pytest.approx(0.1)
    assert det.observe(6, 0.1) is None  # recovery resets the streak
    assert det.consecutive == 0
    assert det.summary()["flagged_total"] == 2


def test_attribution_priority_order():
    rec = FlightRecorder()
    tid = rec.new_trace_id()
    # Only a checkpoint save overlaps → checkpoint-save.
    rec.record_span("save", kind="checkpoint_save", trace_id=tid,
                    t0=10.0, t1=11.0)
    assert rec.attribute(tid, 9.5, 11.5) == "checkpoint-save"
    # A fault event in the same window outranks it.
    rec.event("host-slow", kind="fault", trace_id=tid, ts=10.5)
    assert rec.attribute(tid, 9.5, 11.5) == "host-slow"
    # Disjoint window → unknown.
    assert rec.attribute(tid, 100.0, 101.0) == "unknown"


def test_record_anomaly_counts_by_cause():
    rec = FlightRecorder()
    rec.record_anomaly("host-slow", trace_id="t", ts=1.0)
    rec.record_anomaly("host-slow", trace_id="t", ts=2.0)
    rec.record_anomaly("unknown", trace_id="t", ts=3.0)
    st = rec.stats()
    assert st["anomalies_total"] == 3
    assert st["anomalies_by_cause"] == {"host-slow": 2, "unknown": 1}
    evs = rec.events(trace_id="t", kind="anomaly", limit=0)
    assert [e["name"] for e in evs][:2] == ["step_anomaly:host-slow"] * 2


def test_host_slow_anomaly_attributed_deterministically(tmp_path):
    """The acceptance seam: an injected host-slow stall at a known step is
    flagged by the sliding baseline AND attributed to the injected cause
    (the supervisor records the fault event before the anomaly check)."""
    faults.activate(FaultPlan(seed=0, specs=[
        FaultSpec(kind=FaultKind.HOST_SLOW, at_step=8, slow_s=3.0, count=2),
    ]))
    det = StepTimeAnomalyDetector(warmup=3, ratio=1.5, min_excess_s=0.05)
    job = TrainingJob("anom-job", tiny_config(tmp_path / "ckpt"),
                      anomaly_detector=det)
    job.start()
    job.join(timeout=300)
    assert job.status == JobStatus.COMPLETED, job.error
    assert job.anomalies_total >= 1
    assert job.last_anomaly["cause"] == "host-slow"
    assert job.last_anomaly["step"] in (8, 9)
    d = job.describe()
    assert d["trace_id"] == job.trace_id
    assert d["last_anomaly"]["cause"] == "host-slow"
    rec = tracing.get_recorder()
    anoms = rec.events(trace_id=job.trace_id, kind="anomaly", limit=0)
    assert any(e["name"] == "step_anomaly:host-slow" for e in anoms)


class _FakeTraceSession:
    def __init__(self):
        self.calls = []

    def start(self, log_dir, duration_s=None):
        self.calls.append((log_dir, duration_s))
        return {"log_dir": log_dir}


def test_sustained_regression_auto_starts_trace(tmp_path):
    """Opt-in hook: sustained slow steps auto-start ONE bounded capture."""
    faults.activate(FaultPlan(seed=0, specs=[
        FaultSpec(kind=FaultKind.HOST_SLOW, at_step=6, slow_s=3.0, count=3),
    ]))
    det = StepTimeAnomalyDetector(warmup=3, ratio=1.5, min_excess_s=0.05,
                                  sustained_k=2)
    fake = _FakeTraceSession()
    job = TrainingJob(
        "auto-trace-job", tiny_config(tmp_path / "ckpt"),
        anomaly_detector=det, anomaly_trace_session=fake,
        anomaly_trace_dir=str(tmp_path / "anomtrace"),
    )
    job.start()
    job.join(timeout=300)
    assert job.status == JobStatus.COMPLETED, job.error
    # Three anomalous steps, one capture (no retry storm), bounded duration.
    assert fake.calls == [(str(tmp_path / "anomtrace"), 30.0)]
    evs = tracing.get_recorder().events(trace_id=job.trace_id, limit=0)
    assert any(e["name"] == "auto_trace_started" for e in evs)


# ---------------------------------------------------------------------------
# the chaos lifecycle chain, end to end through the real scheduler
# ---------------------------------------------------------------------------


def test_chaos_lifecycle_recorded_as_causal_chain(tmp_path):
    """Chip death at step 3 → the whole recovery lifecycle lands on ONE
    trace: submit → admission → attempt → detect/emergency-save → requeue
    → shrink-admit → resume, causally linked, exportable as Chrome JSON."""
    mgr = TPUManager()
    faults.activate(FaultPlan(seed=1, specs=[
        FaultSpec(kind=FaultKind.CHIP_UNHEALTHY, at_step=3, device_index=5),
    ]))
    cfg = tiny_config(
        tmp_path / "ckpt", mesh=MeshConfig(data=4, fsdp=2), total_steps=6,
        checkpoint_interval_steps=2, elastic_min_devices=2,
    )
    sched = FleetScheduler(
        max_concurrent_jobs=1, fleet_fn=mgr.get_fleet_status,
        poll_interval_s=0.05,
    )
    try:
        sub = sched.submit(cfg, job_kwargs={"auto_rollback": False})
        sub = sched.wait(sub.submission_id, timeout=600)
        assert sub.state == SubmissionState.COMPLETED
    finally:
        sched.shutdown()

    rec = tracing.get_recorder()
    spans = rec.spans(trace_id=sub.trace_id, limit=0)
    kinds = {s["kind"] for s in spans}
    assert {"job", "admission", "attempt", "compile", "emergency_save",
            "final_save"} <= kinds
    events = rec.events(trace_id=sub.trace_id, limit=0)
    ev_names = {e["name"] for e in events}
    assert {"submit", "requeue", "shrink_admit", "resume"} <= ev_names

    # Causality: both attempts hang off the job root; the root closed with
    # the terminal state.
    root_id = rec.trace_root(sub.trace_id)
    attempts = [s for s in spans if s["kind"] == "attempt"]
    assert len(attempts) == 2
    assert all(a["parent_id"] == root_id for a in attempts)
    (root,) = [s for s in spans if s["span_id"] == root_id]
    assert root["t1"] is not None and root["attrs"]["submission_id"]
    assert attempts[0]["attrs"]["preemption_reason"].startswith("self-heal")
    assert attempts[1]["attrs"]["resumed_from_step"] == 3

    # And it exports as a loadable Chrome trace.
    doc = rec.export_chrome_trace(trace_id=sub.trace_id)
    json.loads(json.dumps(doc))  # serialisable
    evs = doc["traceEvents"]
    assert all("ph" in e and "ts" in e and "pid" in e for e in evs)
    body = [e["ts"] for e in evs if e["ph"] != "M"]
    assert body == sorted(body)
    assert {e["ph"] for e in evs} >= {"X", "i", "s", "f"}


# ---------------------------------------------------------------------------
# FaultInjector event-log truncation is accounted, never silent
# ---------------------------------------------------------------------------


def test_fault_injector_counts_dropped_events():
    inj = faults.FaultInjector(FaultPlan(seed=0, specs=[]))
    inj.MAX_EVENTS = 5
    for i in range(12):
        inj.record("external", step=i, detail=f"obs {i}")
    assert len(inj.events) == 5
    assert inj.events_dropped == 7
    # Still monotonic after further drops, and surfaced in describe().
    inj.record("external", step=99)
    assert inj.events_dropped == 8
    d = inj.describe()
    assert d["events_dropped"] == 8
    assert inj.describe_full()["events_dropped"] == 8
    # The retained window is the newest events.
    assert [e.step for e in inj.events] == [8, 9, 10, 11, 99]


def test_fault_records_mirror_onto_recorder():
    rec = tracing.get_recorder()
    inj = faults.FaultInjector(FaultPlan(seed=0, specs=[]))
    inj.record("external", step=7, detail="mirror me")
    evs = rec.events(trace_id="fleet", kind="fault", limit=0)
    assert any(e["name"] == "external" and e["attrs"]["step"] == 7
               for e in evs)


# ---------------------------------------------------------------------------
# benchmark exports produce Perfetto-loadable trace files
# ---------------------------------------------------------------------------


def _assert_perfetto_loadable(path):
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    assert evs, "empty trace"
    assert all("ph" in e and "ts" in e and "pid" in e for e in evs)
    body = [e["ts"] for e in evs if e["ph"] != "M"]
    assert body == sorted(body), "timestamps must be monotonic"
    return doc


def test_chaos_benchmark_writes_perfetto_trace(tmp_path, monkeypatch, capsys):
    from benchmarks import chaos

    out = str(tmp_path / "chaos_trace.json")
    monkeypatch.setattr(
        "sys.argv",
        ["chaos", "--seed", "0", "--trace-out", out],
    )
    chaos.main()  # raises SystemExit(1) if the policy comparison regresses
    doc = _assert_perfetto_loadable(out)
    names = {e.get("name") for e in doc["traceEvents"]}
    # The recovery chain the benchmark simulates, span by span.
    assert {"detect", "emergency_save", "requeue", "shrink_admit",
            "resume", "grow_back"} <= names
    # Causal links exported as flow arrows.
    assert any(e["ph"] == "s" for e in doc["traceEvents"])
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["ok"]
