"""Digital-twin tests: trace ingestion hardening (rotation, torn tails,
schema versions), deterministic replay of recorder JSONL through the real
control-plane components, causal-chain preservation, replay fidelity vs
the source run's goodput decomposition, synthetic-generator parity with
the legacy sims, and the A/B policy scorecards."""

import json
import os

import pytest

from tpu_engine import twin
from tpu_engine.tracing import SCHEMA_VERSION, FlightRecorder
from tpu_engine.twin import (
    ReplayWorkload,
    TrainTwinParams,
    TwinEngine,
    VirtualClock,
    bursty_arrivals,
    chip_fault_timeline,
    decomposition_diff,
    default_policy_scorecard,
    deterministic_ids,
    diurnal_arrivals,
    goodput_lane,
    heavy_tail_prefill_arrivals,
    read_recorder_jsonl,
    replay_fidelity,
    replay_self_heal,
    twin_replay_gates,
)


# -- virtual clock + deterministic ids ---------------------------------------


def test_virtual_clock_advances_and_sets():
    clock = VirtualClock(0.0)
    assert clock() == 0.0
    assert clock.now() == 0.0
    assert clock.advance(2.5) == 2.5
    assert clock.set(10.0) == 10.0
    assert clock() == 10.0


def test_deterministic_ids_reproduce_across_factories():
    a, b = deterministic_ids("x"), deterministic_ids("x")
    seq_a = [a() for _ in range(5)]
    seq_b = [b() for _ in range(5)]
    assert seq_a == seq_b
    assert len(set(seq_a)) == 5


# -- schema versioning --------------------------------------------------------


def test_recorder_jsonl_lines_carry_schema_version(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    rec = FlightRecorder(clock=lambda: 0.0, persist_path=path)
    tid = rec.new_trace_id()
    rec.record_span("root", kind="job", trace_id=tid, t0=0.0, t1=1.0)
    rec.event("submit", kind="scheduler", trace_id=tid, ts=0.0)
    lines = [
        json.loads(x)
        for x in open(path, encoding="utf-8").read().splitlines()
        if x.strip()
    ]
    assert lines
    for rec_line in lines:
        assert rec_line["schema_version"] == SCHEMA_VERSION


def test_ingester_rejects_unknown_schema_accepts_legacy(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    good = {"record": "span", "name": "s", "kind": "job", "span_id": "a",
            "trace_id": "t", "parent_id": None, "t0": 0.0, "t1": 1.0,
            "schema_version": SCHEMA_VERSION}
    legacy = dict(good, span_id="b")
    legacy.pop("schema_version")
    future = dict(good, span_id="c", schema_version=99)
    bad_type = dict(good, span_id="d", schema_version="one")
    with open(path, "w", encoding="utf-8") as f:
        for rec_line in (good, legacy, future, bad_type):
            f.write(json.dumps(rec_line) + "\n")
    records, stats = read_recorder_jsonl(path)
    assert stats["accepted"] == 2  # good + legacy
    assert stats["legacy_lines"] == 1
    assert stats["skipped_by_reason"] == {"unknown_schema": 2}
    assert [r["span_id"] for r in records] == ["a", "b"]


# -- ingestion hardening: rotation + torn tails -------------------------------


def _span_line(i, t0=0.0, t1=1.0):
    return json.dumps({
        "record": "span", "name": f"s{i}", "kind": "job",
        "span_id": f"sp-{i}", "trace_id": "t", "parent_id": None,
        "t0": t0, "t1": t1, "schema_version": SCHEMA_VERSION,
    })


def test_rotated_files_read_oldest_first(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    with open(path + ".1", "w", encoding="utf-8") as f:
        f.write(_span_line(1) + "\n" + _span_line(2) + "\n")
    with open(path, "w", encoding="utf-8") as f:
        f.write(_span_line(3) + "\n")
    records, stats = read_recorder_jsonl(path)
    assert stats["files"] == 2
    assert [r["span_id"] for r in records] == ["sp-1", "sp-2", "sp-3"]


def test_torn_tail_and_parse_errors_skipped_not_raised(tmp_path):
    twin._reset_stats_for_tests()
    path = str(tmp_path / "trace.jsonl")
    with open(path + ".1", "w", encoding="utf-8") as f:
        f.write(_span_line(1) + "\n")
        f.write("{corrupt mid-file}\n")  # parse_error: not the live tail
    with open(path, "w", encoding="utf-8") as f:
        f.write(_span_line(2) + "\n")
        f.write(json.dumps({"record": "gc", "schema_version": 1}) + "\n")
        # Mid-append capture: the final line of the live file is truncated.
        f.write(_span_line(3)[: len(_span_line(3)) // 2])
    records, stats = read_recorder_jsonl(path)
    assert [r["span_id"] for r in records] == ["sp-1", "sp-2"]
    assert stats["skipped"] == 3
    assert stats["skipped_by_reason"] == {
        "parse_error": 1, "unknown_record": 1, "torn_tail": 1,
    }
    st = twin.twin_stats()
    assert st["ingest_files_total"] == 2
    assert st["ingest_skipped_lines_total"] == 3
    assert st["ingest_skipped_by_reason"]["torn_tail"] == 1
    assert st["ingest_skipped_by_reason"]["parse_error"] == 1


def test_torn_tail_only_applies_to_live_file_final_line(tmp_path):
    # A truncated final line of the *rotated* file is a parse error — only
    # the live file can be captured mid-append.
    path = str(tmp_path / "trace.jsonl")
    with open(path + ".1", "w", encoding="utf-8") as f:
        f.write(_span_line(1)[:20])  # no trailing newline
    with open(path, "w", encoding="utf-8") as f:
        f.write(_span_line(2) + "\n")
    _, stats = read_recorder_jsonl(path)
    assert stats["skipped_by_reason"] == {"parse_error": 1}


def test_missing_file_is_empty_workload(tmp_path):
    records, stats = read_recorder_jsonl(str(tmp_path / "absent.jsonl"))
    assert records == [] and stats["files"] == 0
    w = ReplayWorkload(records, stats)
    assert w.t_range == (0.0, 0.0)
    out = TwinEngine().replay(w)
    assert out["spans_replayed"] == 0 and out["traces"] == {}


# -- recorded chaos trace fixture --------------------------------------------


@pytest.fixture(scope="module")
def chaos_jsonl(tmp_path_factory):
    """A seeded self-heal run recorded to JSONL — the replay fixture."""
    path = str(tmp_path_factory.mktemp("twin") / "chaos.jsonl")
    params = TrainTwinParams()
    rec = FlightRecorder(
        max_spans=16384, max_events=16384, clock=lambda: 0.0,
        id_factory=deterministic_ids("src"), persist_path=path,
        persist_max_bytes=64 * 1024 * 1024,
    )
    tid = rec.new_trace_id()
    events = chip_fault_timeline(0, 12, params)
    heal = replay_self_heal(events, params, recorder=rec, trace_id=tid)
    source = goodput_lane(rec, tid, heal["wall_s"], full_gang=params.n_chips)
    return {"path": path, "trace_id": tid, "heal": heal, "source": source,
            "params": params}


def test_replay_reconstructs_workload_views(chaos_jsonl):
    w = ReplayWorkload.from_jsonl(chaos_jsonl["path"])
    assert w.ingest["skipped"] == 0
    assert len(w.jobs) == 1
    job = w.jobs[0]
    assert job["trace_id"] == chaos_jsonl["trace_id"]
    assert job["name"] == "job:chaos-self-heal"
    assert int(job["gang"]) == chaos_jsonl["params"].n_chips
    assert len(w.faults) == chaos_jsonl["heal"]["faults"]
    lo, hi = w.t_range
    # The goodput lane's counter-track events land on bucket boundaries,
    # so the trace horizon rounds up past the job's own wall clock.
    assert lo == 0.0 and hi >= chaos_jsonl["heal"]["wall_s"]


def test_replay_is_deterministic_byte_identical(chaos_jsonl):
    """Satellite 3: the same trace replayed twice produces byte-identical
    event orderings and identical goodput decompositions."""
    w = ReplayWorkload.from_jsonl(chaos_jsonl["path"])
    e1, e2 = TwinEngine(), TwinEngine()
    out1, out2 = e1.replay(w), e2.replay(w)
    s1 = json.dumps(e1.recorder.spans(limit=0), sort_keys=True)
    s2 = json.dumps(e2.recorder.spans(limit=0), sort_keys=True)
    assert s1 == s2
    ev1 = json.dumps(e1.recorder.events(limit=0), sort_keys=True)
    ev2 = json.dumps(e2.recorder.events(limit=0), sort_keys=True)
    assert ev1 == ev2
    assert out1["traces"] == out2["traces"]
    assert out1["spans_replayed"] == out2["spans_replayed"]


def test_replayed_self_heal_chain_causally_intact(chaos_jsonl):
    """Satellite 3: after ingest + replay, every fault's recovery chain
    detect → emergency_save → requeue → shrink_admit → compile → resume
    still links parent-to-child on the replayed recorder."""
    w = ReplayWorkload.from_jsonl(chaos_jsonl["path"])
    engine = TwinEngine()
    engine.replay(w)
    spans = engine.recorder.spans(limit=0)
    by_id = {s["span_id"]: s for s in spans}
    roots = [s for s in spans if s["name"] == "job:chaos-self-heal"]
    assert len(roots) == 1
    root = roots[0]
    detects = sorted(
        (s for s in spans if s["name"] == "detect"), key=lambda s: s["t0"]
    )
    assert len(detects) == chaos_jsonl["heal"]["faults"] > 0
    chain = ("emergency_save", "requeue", "shrink_admit", "compile", "resume")
    for detect in detects:
        assert by_id[detect["parent_id"]] is root
        tail = detect
        for name in chain:
            children = [
                s for s in spans
                if s["parent_id"] == tail["span_id"] and s["name"] == name
            ]
            assert len(children) == 1, (name, tail["name"])
            child = children[0]
            assert child["t0"] >= tail["t0"]
            tail = child
        assert tail["kind"] == "supervisor"
    # Grow-backs chain off a resume (or the root before the first fault).
    for grow in (s for s in spans if s["name"] == "grow_back"):
        parent = by_id[grow["parent_id"]]
        assert parent["name"] in ("resume", "job:chaos-self-heal")


def test_replay_fidelity_within_one_percent_and_fast(chaos_jsonl):
    """Acceptance gates: replayed decomposition within 1% of the source
    per category; >= 1000 simulated fleet-seconds per CPU-second."""
    w = ReplayWorkload.from_jsonl(chaos_jsonl["path"])
    engine = TwinEngine()
    out = engine.replay(w)
    side = out["traces"][chaos_jsonl["trace_id"]]
    source = chaos_jsonl["source"]
    # The source lane reports the fraction rounded to 4 decimals.
    assert side["goodput_fraction"] == pytest.approx(
        source["goodput_fraction"], abs=1e-4
    )
    diff = decomposition_diff(
        source["breakdown_s"], side["categories"], source["wall_s"]
    )
    assert diff["max_error_pct"] < 1.0
    assert out["fleet_seconds_per_cpu_second"] >= 1000.0


def test_replay_fidelity_end_to_end():
    fid = replay_fidelity(seed=0)
    assert fid["max_error_pct"] < 1.0
    assert fid["fleet_seconds_per_cpu_second"] >= 1000.0
    assert fid["ingest"]["skipped"] == 0
    assert fid["replay_goodput_fraction"] == pytest.approx(
        fid["source_goodput_fraction"], abs=1e-3
    )


def test_replay_bumps_health_counters(chaos_jsonl):
    twin._reset_stats_for_tests()
    w = ReplayWorkload.from_jsonl(chaos_jsonl["path"])
    TwinEngine().replay(w)
    st = twin.twin_stats()
    assert st["replays_total"] == 1
    assert st["replayed_spans_total"] == len(w.spans)
    assert st["replayed_events_total"] == len(w.events)
    assert st["fleet_seconds_total"] > 0.0
    assert st["last_fleet_seconds_per_cpu_second"] > 0.0


# -- synthetic traffic generators --------------------------------------------


def test_bursty_generator_matches_legacy_serving_sim():
    """The sims' seeded request traces must reproduce byte-for-byte
    through the shared generator (rng draw order is the contract)."""
    from benchmarks import serving_fleet_sim as sim

    assert sim.request_trace(3) == bursty_arrivals(
        3,
        duration_s=sim.SIM_DURATION_S,
        base_rps=sim.BASE_RATE_RPS,
        burst_rps=sim.BURST_RATE_RPS,
        burst_every_s=sim.BURST_EVERY_S,
        burst_len_s=sim.BURST_LEN_S,
        n_prefixes=sim.N_PREFIXES,
        prefix_len=sim.PREFIX_LEN,
        mean_new_tokens=sim.MEAN_NEW_TOKENS,
    )
    # The long-prefill trace draws from an offset seed stream so the two
    # legacy generators stay independent for the same seed.
    long_trace = sim.long_prefill_trace(5)
    assert long_trace and all("prefill_units" in r for r in long_trace)
    assert long_trace != sim.long_prefill_trace(6)
    assert sim.long_prefill_trace(5) == long_trace  # deterministic


def test_generators_are_seeded_and_shaped():
    bursty = bursty_arrivals(1, duration_s=120.0)
    assert bursty == bursty_arrivals(1, duration_s=120.0)
    assert bursty != bursty_arrivals(2, duration_s=120.0)
    assert all(r["n_new"] >= 8 and r["prompt"] for r in bursty)
    diurnal = diurnal_arrivals(1, duration_s=300.0)
    assert all(0.0 <= r["t"] < 300.0 for r in diurnal)
    heavy = heavy_tail_prefill_arrivals(1, duration_s=300.0)
    assert all(r["prefill_units"] >= 0.3 for r in heavy)
    # Pareto tail: the max prefill dwarfs the median.
    units = sorted(r["prefill_units"] for r in heavy)
    assert units[-1] > 4.0 * units[len(units) // 2]


# -- A/B scorecards -----------------------------------------------------------


def test_policy_scorecard_measures_real_deltas():
    card = default_policy_scorecard(seed=0)
    v = card["variants"]
    assert card["baseline"] == "ckpt100_index_on"
    assert set(v) == {"ckpt100_index_on", "ckpt50_index_on",
                      "ckpt200_index_on", "ckpt100_index_off"}
    # Checkpoint interval trades checkpoint time against... nothing here
    # (no lost steps), so the 200-step variant wins goodput.
    assert v["ckpt200_index_on"]["goodput_fraction"] > (
        v["ckpt50_index_on"]["goodput_fraction"]
    )
    # Warm compile index beats cold resumes on both goodput and MTTR.
    assert v["ckpt100_index_on"]["goodput_fraction"] > (
        v["ckpt100_index_off"]["goodput_fraction"]
    )
    assert v["ckpt100_index_on"]["mttr_mean_s"] < (
        v["ckpt100_index_off"]["mttr_mean_s"]
    )
    assert v["ckpt100_index_off"]["cold_resumes"] > 0
    assert v["ckpt100_index_on"]["warm_resumes"] > 0
    deltas = card["deltas_vs_baseline"]
    assert deltas["ckpt100_index_off"]["goodput_fraction"] < 0.0
    # Scorecards are deterministic run-to-run (cpu_s is wall time).
    again = default_policy_scorecard(seed=0)
    assert again["variants"] == card["variants"]
    assert again["deltas_vs_baseline"] == card["deltas_vs_baseline"]


def test_twin_replay_gates_all_pass():
    assert twin_replay_gates(seed=0) == {
        "replay_within_1pct": True,
        "replay_fast_enough": True,
        "policy_delta_measured": True,
        "warm_beats_fifo": True,
    }


# -- control-plane scale lane ---------------------------------------------------


def test_scale_lane_small_is_deterministic_complete_and_bounded():
    """The 1k-job / 10k-request configuration through the real scheduler,
    router, historian and correlator: same counts on a repeat, every job
    completes, every ring at or under its cap. (What the clock says of it
    is not asserted here: see the slow test below.)"""
    small = twin.ScaleLaneParams.small()
    a = twin.scale_lane(seed=0, params=small)
    b = twin.scale_lane(seed=0, params=small)
    assert a["deterministic"] == b["deterministic"]
    assert a["deterministic"]["jobs"]["completed"] == small.n_jobs
    assert a["deterministic"]["serving"]["routed"] >= 0.98 * small.n_requests
    assert a["rings_bounded"]


@pytest.mark.slow
def test_ctl_scale_profile_gates():
    """100x the jobs and requests cost 100x the control work, not more
    (about a minute; its flatness gate reads this host's CPU clock)."""
    prof = twin.ctl_scale_profile(seed=0)
    assert prof["ok"], (prof["gates"], prof["overhead_ratio"])


# -- HTTP surface -------------------------------------------------------------


def test_twin_router_replay_endpoint(chaos_jsonl):
    from aiohttp.test_utils import TestClient, TestServer, loop_context

    from backend.main import create_app

    with loop_context() as loop:
        async def go():
            client = TestClient(TestServer(create_app()))
            await client.start_server()
            try:
                r = await client.get("/api/v1/twin")
                assert r.status == 200
                doc = await r.json()
                assert doc["schema_version"] == SCHEMA_VERSION
                r = await client.post(
                    "/api/v1/twin/replay",
                    json={"path": chaos_jsonl["path"]},
                )
                assert r.status == 200
                out = await r.json()
                assert out["dry_run"] is True
                assert out["spans_replayed"] > 0
                assert chaos_jsonl["trace_id"] in out["traces"]
                assert out["jobs"] == 1
                assert out["traces_truncated"] == 0
                r = await client.post(
                    "/api/v1/twin/replay",
                    json={"path": chaos_jsonl["path"] + ".nope"},
                )
                assert r.status == 404
                r = await client.post(
                    "/api/v1/twin/replay",
                    json={"path": chaos_jsonl["path"], "bucket_s": -1},
                )
                assert r.status == 400
            finally:
                await client.close()

        loop.run_until_complete(go())


def test_rotation_produces_readable_generations(tmp_path):
    """The recorder's own size-based rotation yields the path+'.1' layout
    the ingester reads — record enough spans to force at least one roll."""
    path = str(tmp_path / "rot.jsonl")
    rec = FlightRecorder(
        clock=lambda: 0.0, persist_path=path, persist_max_bytes=4096,
    )
    tid = rec.new_trace_id()
    for i in range(200):
        rec.record_span(
            f"s{i}", kind="step", trace_id=tid, t0=float(i), t1=float(i) + 0.5,
        )
    assert os.path.exists(path + ".1")
    records, stats = read_recorder_jsonl(path)
    assert stats["files"] == 2
    assert stats["skipped"] == 0
    # Oldest-first ordering across generations by construction time.
    t0s = [r["t0"] for r in records if r.get("record") == "span"]
    assert t0s == sorted(t0s)
