"""Durable control plane: write-ahead journal + crash recovery units.

Covers the :class:`~tpu_engine.journal.ControlPlaneJournal` itself
(bounded rotation, torn-tail-tolerant ingest, O(1) stats, never-raising
appends), ``FleetScheduler.restore`` (deterministic rebuild, orphan
re-adoption, vanished-training requeue, the HBM double-grant audit),
``ServingFleet.re_adopt`` (roster + held-request recovery) and the
component export/load hooks behind ``journal.collect_sections``. The
full kill-mid-storm A/B with exit gates is ``twin.ctl_crash_ab``
(``test_ctl_crash_ab_gates`` below).
"""

import json
import threading
from types import SimpleNamespace

import pytest

from tests.test_scheduler import StubJob, cfg
from tpu_engine import journal as journal_mod
from tpu_engine.autopilot import AutopilotConfig, FleetAutopilot
from tpu_engine.hbm_estimate import estimate_job_hbm
from tpu_engine.journal import ControlPlaneJournal, collect_sections
from tpu_engine.prefix_plane import HOST_HOLDER, PrefixPlane
from tpu_engine.scheduler import FleetScheduler, SubmissionState
from tpu_engine.serving_fleet import ServingFleet, ServingReplicaSpec
from tpu_engine.spec_pool import SpecSpillController
from tpu_engine.tpu_manager import TPUDevice, TPUFleetStatus


@pytest.fixture(autouse=True)
def _fresh_journal_stats():
    journal_mod._reset_stats_for_tests()
    journal_mod.clear_active_journal()
    yield
    journal_mod._reset_stats_for_tests()
    journal_mod.clear_active_journal()


def _make_sched(**kw):
    """Pump-thread-free scheduler: tests drive poll() by hand."""
    kw.setdefault("job_factory", StubJob)
    kw.setdefault("poll_interval_s", 3600.0)
    kw.setdefault("grow_back", False)
    kw.setdefault("hetero_rebalance", False)
    s = FleetScheduler(**kw)
    s._ensure_thread = lambda: None
    return s


# ---------------------------------------------------------------------------
# the journal itself
# ---------------------------------------------------------------------------


def test_snapshot_resets_replay_suffix(tmp_path):
    clk = iter(range(1000))
    j = ControlPlaneJournal(
        str(tmp_path / "j.jsonl"), clock=lambda: float(next(clk))
    )
    j.append("sched.submit", {"sid": "a"})
    j.append("sched.submit", {"sid": "b"})
    j.snapshot({"scheduler": {"seq": 2}})
    j.append("sched.admit", {"sid": "a"})
    got = j.read()
    # Replay starts at the newest snapshot: only the suffix survives.
    assert got["snapshot"]["sections"]["scheduler"] == {"seq": 2}
    assert [e["kind"] for e in got["events"]] == ["sched.admit"]
    assert got["stats"]["accepted"] == 4 and got["stats"]["skipped"] == 0
    st = j.stats()
    assert st["appends_total"] == 3 and st["snapshots_total"] == 1


def test_read_skips_torn_and_unknown_lines(tmp_path):
    path = tmp_path / "j.jsonl"
    j = ControlPlaneJournal(str(path))
    j.append("sched.submit", {"sid": "a"})
    with open(path, "a", encoding="utf-8") as f:
        # Legacy line (pre-versioning): accepted.
        f.write(json.dumps({"record": "event", "kind": "legacy.ev",
                            "ts": 0.0, "payload": {}}) + "\n")
        # Future schema: skipped, never guessed at.
        f.write(json.dumps({"record": "event", "kind": "x",
                            "schema_version": 99, "payload": {}}) + "\n")
        # Unrecognized record kind.
        f.write(json.dumps({"record": "weird", "schema_version": 1}) + "\n")
        # Mid-file garbage is a parse error...
        f.write("{{{ not json\n")
        # ...but an undecodable FINAL line is the torn tail of the write
        # the crash interrupted.
        f.write('{"record":"event","kind":"sched.su')
    got = j.read()
    assert [e["kind"] for e in got["events"]] == ["sched.submit", "legacy.ev"]
    assert got["stats"]["legacy_lines"] == 1
    assert got["stats"]["skipped_by_reason"] == {
        "unknown_schema": 1, "unknown_record": 1,
        "parse_error": 1, "torn_tail": 1,
    }
    # Module-level read counters (the scrape surface) saw the same ingest.
    js = journal_mod.journal_stats()
    assert js["reads_total"] == 1
    assert js["read_skipped_lines_total"] == 4
    assert js["read_skipped_by_reason"]["torn_tail"] == 1


def test_append_never_raises(tmp_path):
    # Parent directory missing: every write fails — and is absorbed.
    j = ControlPlaneJournal(str(tmp_path / "no" / "such" / "dir" / "j.jsonl"))
    j.append("sched.submit", {"sid": "a"})
    j.snapshot({"scheduler": {}})
    st = j.stats()
    assert st["append_errors_total"] == 2
    got = j.read()
    assert got["snapshot"] is None and got["events"] == []


# ---------------------------------------------------------------------------
# scheduler restore
# ---------------------------------------------------------------------------


def test_restore_readopts_orphans_and_requeues_vanished(tmp_path):
    j = ControlPlaneJournal(str(tmp_path / "j.jsonl"))
    s1 = _make_sched(max_concurrent_jobs=2)
    s1.attach_journal(j)
    sub_a = s1.submit(cfg())
    sub_b = s1.submit(cfg())
    sub_c = s1.submit(cfg())
    s1.poll()
    assert sub_a.state == SubmissionState.RUNNING
    assert sub_b.state == SubmissionState.RUNNING
    assert sub_c.state == SubmissionState.QUEUED
    seq_b = sub_b.seq
    job_a = sub_a.job

    # Crash. Job A kept running (orphan); job B died with the host.
    appends_before = j.stats()["appends_total"]
    s2 = _make_sched(max_concurrent_jobs=2)
    r = s2.restore(j, live_jobs={sub_a.submission_id: job_a}, now=123.0)
    assert r["had_snapshot"] is False
    assert r["restored_submissions"] == 3
    assert r["events_replayed"] == 5  # 3 submits + 2 admits
    assert r["readopted"] == 1 and r["requeued_vanished"] == 1
    got_a = s2.get(sub_a.submission_id)
    assert got_a.state == SubmissionState.RUNNING and got_a.job is job_a
    got_b = s2.get(sub_b.submission_id)
    assert got_b.state == SubmissionState.QUEUED
    assert got_b.seq == seq_b  # requeued at its ORIGINAL position
    assert got_b.last_skip_reason == "requeued_at_recovery"
    assert s2.get(sub_c.submission_id).state == SubmissionState.QUEUED
    # restore() never writes — double recovery is byte-identical.
    assert j.stats()["appends_total"] == appends_before
    s3 = _make_sched(max_concurrent_jobs=2)
    s3.restore(j, live_jobs={sub_a.submission_id: job_a}, now=123.0)
    d2 = json.dumps(s2.snapshot_state(), sort_keys=True)
    d3 = json.dumps(s3.snapshot_state(), sort_keys=True)
    assert d2 == d3
    # Recovery counters landed on the module surface.
    cr = journal_mod.recovery_stats()
    assert cr["restores_total"] == 2 and cr["jobs_readopted_total"] == 2
    for job in (job_a, sub_b.job):
        if job is not None:
            job.finish()


def test_restore_detects_double_grants(tmp_path):
    est = estimate_job_hbm(cfg())
    cap = est.device_total_gib * 1.5  # fits one claimant, not two
    fleet = TPUFleetStatus(devices=[TPUDevice(index=0, hbm_total_gb=cap)])

    j = ControlPlaneJournal(str(tmp_path / "j.jsonl"))
    s1 = _make_sched(max_concurrent_jobs=2)
    sub_a = s1.submit(cfg())
    sub_b = s1.submit(cfg())
    # Doctor the snapshot into the inconsistent state a crash-interrupted
    # release leaves behind: both submissions journaled RUNNING with a
    # grant on device 0, which cannot hold both.
    snap = s1.snapshot_state()
    for e in snap["submissions"]:
        e["state"] = "running"
        e["attempts"] = 1
        e["placement"] = [0]
        e["hbm_estimate"] = est.model_dump(mode="json")
    j.snapshot({"scheduler": snap})

    live = {
        sub_a.submission_id: SimpleNamespace(_stop=threading.Event()),
        sub_b.submission_id: SimpleNamespace(_stop=threading.Event()),
    }
    s2 = _make_sched(max_concurrent_jobs=2, fleet_fn=lambda: fleet)
    r = s2.restore(j, live_jobs=live, now=99.0)
    assert r["readopted"] == 2 and r["double_grants"] == 1
    # The YOUNGEST claimant's grant is the bogus one: demoted, its job
    # stopped, the device quarantined with a structured reason.
    victim = s2.get(sub_b.submission_id)
    assert victim.state == SubmissionState.QUEUED
    assert victim.last_skip_reason == "double_grant_at_recovery"
    assert live[sub_b.submission_id]._stop.is_set()
    assert s2.get(sub_a.submission_id).state == SubmissionState.RUNNING
    q = s2._hetero_quarantined[0]
    assert q["source"] == "ctl_recovery:double_grant"
    assert s2._reserved[0] <= cap + 1e-9
    assert journal_mod.recovery_stats()["double_grants_total"] == 1


# ---------------------------------------------------------------------------
# serving fleet re-adoption
# ---------------------------------------------------------------------------


def test_re_adopt_recovers_roster_and_held_requests(tmp_path):
    j = ControlPlaneJournal(str(tmp_path / "j.jsonl"))
    s = _make_sched(max_concurrent_jobs=4)
    replica_sub = s.submit(cfg(), workload="serving")  # survived, still queued
    j.append("fleet.desired", {"n": 2})
    j.append("fleet.replica", {"sid": replica_sub.submission_id})
    j.append("fleet.replica", {"sid": "sub_gone"})  # vanished with the host
    j.append("fleet.request", {
        "fid": "r_1", "prompt": [1, 2, 3], "max_new_tokens": 8,
        "temperature": 0.0, "submitted_at": 1.0,
    })
    j.append("fleet.request", {
        "fid": "r_2", "prompt": [4, 5], "max_new_tokens": 4,
        "temperature": 0.5, "submitted_at": 2.0,
    })
    j.append("fleet.request_done", {"fid": "r_1"})

    spec = ServingReplicaSpec(model_name="gpt-tiny", max_slots=4, max_len=64)
    fleet = ServingFleet(s, spec)
    r = fleet.re_adopt(j, redispatch=False)
    assert r["replicas_readopted"] == 1
    assert r["replicas_redispatched"] == 0  # redispatch=False mints no ids
    assert r["requests_recovered"] == 1 and r["held_fids"] == ["r_2"]
    assert replica_sub.submission_id in fleet._replicas
    assert fleet.desired_replicas == 2
    assert fleet.requests_total == 2 and fleet.completed_total == 1
    assert fleet._req_seq == 2  # the next fid cannot collide with r_1/r_2
    held = fleet._requests["r_2"]
    assert held["prompt"] == [4, 5] and held["done"] is False
    # The journal is attached for subsequent write-ahead.
    before = j.stats()["appends_total"]
    fleet.submit_request([7, 8], max_new_tokens=2)
    assert j.stats()["appends_total"] == before + 1


# ---------------------------------------------------------------------------
# component export/load hooks + section assembly
# ---------------------------------------------------------------------------


def test_export_load_hooks_round_trip():
    # Spec-spill: spilled set, streaks and cooldown clocks survive.
    ctl = SpecSpillController(historian=None)
    ctl.load_state({"spilled": ["t1"], "streak": {"t1": 2, "t2": 1},
                    "last_fired": {"t1": 10.0}})
    assert ctl.is_spilled("t1") and not ctl.is_spilled("t2")
    ctl2 = SpecSpillController(historian=None)
    ctl2.load_state(ctl.export_state())
    assert ctl2.export_state() == ctl.export_state()

    # Autopilot: tuple-keyed hysteresis flattens to JSON and back.
    ap = FleetAutopilot(config=AutopilotConfig(), clock=lambda: 0.0)
    ap._streak = {("replan", "q"): 2}
    ap._last_action = {("rescale", "fleet"): 5.0}
    state = json.loads(json.dumps(ap.export_state()))  # must be JSON-safe
    ap2 = FleetAutopilot(config=AutopilotConfig(), clock=lambda: 0.0)
    ap2.load_state(state)
    assert ap2._streak == ap._streak
    assert ap2._last_action == ap._last_action

    # Prefix plane: the host-tier index re-parks as capacity entries.
    plane = PrefixPlane(prefix_tokens=4)
    assert plane.host.put((1, 2, 3, 4), nbytes=128)
    plane.index.insert((1, 2, 3, 4), HOST_HOLDER)
    state = plane.export_host_index()
    assert state["entries"] == [{"prefix": [1, 2, 3, 4], "nbytes": 128}]
    plane2 = PrefixPlane(prefix_tokens=4)
    assert plane2.load_host_index(json.loads(json.dumps(state))) == 1
    assert plane2.host.contains((1, 2, 3, 4))
    # Garbage tolerated: not-a-dict and half-shaped entries are skipped.
    assert plane2.load_host_index("nope") == 0
    assert plane2.load_host_index({"entries": [{"nbytes": 4}]}) == 0


def test_collect_sections_and_active_journal(tmp_path):
    s = _make_sched()
    sections = collect_sections(scheduler=s)
    assert set(sections) == {"scheduler"}
    sections = collect_sections(
        scheduler=s,
        autopilot=FleetAutopilot(config=AutopilotConfig(), clock=lambda: 0.0),
        spec_spill=SpecSpillController(historian=None),
        prefix_plane=PrefixPlane(prefix_tokens=4),
    )
    assert set(sections) == {
        "scheduler", "autopilot", "spec_spill", "prefix_host",
    }

    # No active journal: the scrape surface renders zeros, attached=False.
    js = journal_mod.journal_stats()
    assert js["attached"] is False and js["appends_total"] == 0
    j = ControlPlaneJournal(str(tmp_path / "j.jsonl"))
    journal_mod.set_active_journal(j)
    j.append("sched.submit", {"sid": "a"})
    js = journal_mod.journal_stats()
    assert js["attached"] is True and js["appends_total"] == 1
    journal_mod.note_mttr(3.5)
    assert journal_mod.recovery_stats()["last_mttr_seconds"] == 3.5


def test_ctl_crash_ab_gates():
    """The kill-mid-storm lane: the seeded storm through the real scheduler
    and fleet, journaled, the control plane dropped mid-storm (torn journal
    line included) and restored, against the same storm with no crash."""
    from tpu_engine.twin import ctl_crash_ab

    res = ctl_crash_ab(seed=0)
    assert res["ok"], res["gates"]
    assert set(res["gates"]) == {
        "zero_lost_submissions", "zero_duplicated_submissions",
        "held_requests_complete", "orphans_readopted",
        "vanished_training_requeued", "vanished_replica_redispatched",
        "no_phantom_double_grants", "double_recovery_identical",
        "torn_tail_skipped_not_raised", "mttr_within_budget",
    }
    crashed = res["crashed"]
    assert crashed["recovery"]["readopted"] > 0
    assert crashed["recovery"]["requeued_vanished"] > 0
    assert crashed["recovery"]["double_grants"] == 0
    assert crashed["re_adopt"]["replicas_redispatched"] > 0
    assert crashed["journal"]["appends_total"] > 0
    assert crashed["mttr_s"] <= res["mttr_budget_s"]
