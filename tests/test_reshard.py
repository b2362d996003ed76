"""Reshard plane: topology-changing resume and pool migration.

Four tiers in one file:

- **Topology/plan units** — manifest round trip, the compatibility rule
  (pipe extent changes refused, everything else bridgeable), plan byte
  accounting, the cost model, and the host abstract form.
- **Real-executor round trips** — a sharded pytree saved under
  ``data4×fsdp2`` through the real Orbax manager restores byte-parity
  onto ``data2×fsdp4`` and a shrunk ``3×2`` mesh; the parity gate
  quarantines and raises on a corrupted re-placement; injected restore
  corruption rides the manager's existing fall-back path untouched.
- **Real-engine migration** — held ``hold_kv`` requests drain onto a
  pool of different chunk/lane geometry and int8 storage and complete;
  prefix payloads cross the replica→replica and host-tier legs.
- **Scheduler/planner wiring** — the structured
  ``no_topology_compatible_checkpoint:<model>`` skip on both the auto
  and fixed-config admission paths, and the planner's reshard ranking
  term (same-topology band, remap pricing, inert without a manifest).
"""

import json
from types import SimpleNamespace

import numpy as np
import pytest

from tests.test_scheduler import StubJob, cfg, wait_until
from tpu_engine import reshard
from tpu_engine.checkpoint import TrainCheckpointManager
from tpu_engine.faults import FaultInjector, FaultKind, FaultPlan, FaultSpec
from tpu_engine.mesh_runtime import MeshConfig
from tpu_engine.placement import PlacementPlanner
from tpu_engine.scheduler import FleetScheduler, SubmissionState
from tpu_engine.tpu_manager import TPUManager


@pytest.fixture(autouse=True)
def _fresh_stats():
    reshard._reset_stats_for_tests()
    yield


@pytest.fixture
def sched_factory():
    created = []

    def make(**kw):
        jobs = []

        def factory(sub):
            job = StubJob(sub)
            jobs.append(job)
            return job

        kw.setdefault("job_factory", factory)
        kw.setdefault("poll_interval_s", 0.01)
        kw.setdefault("grow_back_cooldown_s", 0.0)
        s = FleetScheduler(**kw)
        s._stub_jobs = jobs
        created.append(s)
        return s

    yield make
    for s in created:
        for j in getattr(s, "_stub_jobs", []):
            j.finish()
        s.shutdown()


# ---------------------------------------------------------------------------
# Topology manifest + compatibility rule
# ---------------------------------------------------------------------------


def test_normalize_and_same_topology():
    assert reshard.normalize_topology({"data": 4, "fsdp": 2}) == {
        "data": 4, "fsdp": 2, "pipe": 1, "sequence": 1, "model": 1,
    }
    assert reshard.same_topology({"data": 4, "fsdp": 2},
                                 {"data": 4, "fsdp": 2, "pipe": 1})
    assert not reshard.same_topology({"data": 4, "fsdp": 2},
                                     {"data": 2, "fsdp": 4})


def test_topology_compatible_rules():
    ok, why = reshard.topology_compatible(
        {"data": 4, "fsdp": 2}, {"data": 2, "fsdp": 4}
    )
    assert ok and why == ""
    # Shrink + model-axis change: still bridgeable.
    ok, _ = reshard.topology_compatible(
        {"data": 4, "fsdp": 2}, {"data": 3, "fsdp": 2}
    )
    assert ok
    # Pipe extent change: stage-stacked state, refused with the reason.
    ok, why = reshard.topology_compatible(
        {"data": 4, "fsdp": 2}, {"data": 2, "fsdp": 2, "pipe": 2}
    )
    assert not ok and "pipe extent" in why


def test_topology_manifest_round_trip(tmp_path):
    assert reshard.read_topology(str(tmp_path)) is None
    reshard.write_topology(str(tmp_path), {"data": 4, "fsdp": 2},
                           extra={"job_id": "j1"})
    got = reshard.read_topology(str(tmp_path))
    assert got == {"data": 4, "fsdp": 2, "pipe": 1, "sequence": 1, "model": 1}
    doc = json.loads((tmp_path / reshard.TOPOLOGY_FILE).read_text())
    assert doc["job_id"] == "j1"
    # Unreadable manifest → None, never a raise.
    (tmp_path / reshard.TOPOLOGY_FILE).write_text("{torn")
    assert reshard.read_topology(str(tmp_path)) is None


def test_write_topology_never_raises(tmp_path):
    reshard.write_topology(str(tmp_path / "nope" / "deeper"), {"data": 2})


# ---------------------------------------------------------------------------
# Plan + cost model
# ---------------------------------------------------------------------------


def _abstract_tree():
    import jax

    return {
        "w": jax.ShapeDtypeStruct((16, 8), np.float32),
        "b": jax.ShapeDtypeStruct((8,), np.float32),
    }


def test_build_reshard_plan_accounts_bytes():
    plan = reshard.build_reshard_plan(
        _abstract_tree(), {"data": 4, "fsdp": 2}, {"data": 2, "fsdp": 4}
    )
    assert plan.compatible and not plan.is_same_topology
    assert plan.leaves == 2
    assert plan.total_bytes == (16 * 8 + 8) * 4
    assert plan.bytes_to_remap == plan.total_bytes
    assert plan.summary()["predicted_reshard_s"] > 0
    st = reshard.reshard_stats()
    assert st["plans_built_total"] == 1
    assert st["last_plan_bytes"] == plan.total_bytes
    assert st["last_plan_leaves"] == 2


def test_same_topology_plan_remaps_nothing():
    plan = reshard.build_reshard_plan(
        _abstract_tree(), {"data": 4, "fsdp": 2}, {"fsdp": 2, "data": 4}
    )
    assert plan.is_same_topology and plan.bytes_to_remap == 0
    assert plan.summary()["predicted_reshard_s"] == 0.0


def test_incompatible_plan_carries_reason():
    plan = reshard.build_reshard_plan(
        _abstract_tree(), {"pipe": 2}, {"pipe": 1}
    )
    assert not plan.compatible and "pipe extent" in plan.reason


def test_reshard_cost_model():
    assert reshard.reshard_cost_s(0) == 0.0
    assert reshard.reshard_cost_s(-5) == 0.0
    cost = reshard.reshard_cost_s(reshard.RESHARD_BANDWIDTH_BYTES_S)
    assert cost == pytest.approx(reshard.RESHARD_FIXED_OVERHEAD_S + 1.0)
    # The planner's pricing input: params + fp32 master + two moments.
    from tpu_engine.models import transformer as tfm

    bytes_ = reshard.state_bytes_for_model("gpt-tiny")
    assert bytes_ == tfm.param_count(tfm.MODEL_CONFIGS["gpt-tiny"]) * 12
    assert reshard.state_bytes_for_model("nope-9b") is None


def test_host_abstract_like_strips_shardings():
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("fsdp",))
    sharded = {
        "w": jax.ShapeDtypeStruct(
            (16, 8), np.float32,
            sharding=NamedSharding(mesh, PartitionSpec("fsdp")),
        )
    }
    host = reshard.host_abstract_like(sharded)
    assert host["w"].shape == (16, 8) and host["w"].dtype == np.float32
    assert getattr(host["w"], "sharding", None) is None


# ---------------------------------------------------------------------------
# Real-executor restore round trips
# ---------------------------------------------------------------------------


def _mesh(data, fsdp):
    import jax
    from jax.sharding import Mesh

    return Mesh(
        np.array(jax.devices()[: data * fsdp]).reshape(data, fsdp),
        ("data", "fsdp"),
    )


def _host_state(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "params": {"w": rng.standard_normal((16, 8)).astype(np.float32)},
        "opt": {"mu": rng.standard_normal((16, 8)).astype(np.float32)},
    }


def _specs():
    from jax.sharding import PartitionSpec

    return {"params": {"w": PartitionSpec("fsdp")},
            "opt": {"mu": PartitionSpec("fsdp")}}


def _placed(state, mesh):
    import jax
    from jax.sharding import NamedSharding

    return jax.tree.map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh, spec)),
        state, _specs(),
    )


def _abstract(state, mesh):
    import jax
    from jax.sharding import NamedSharding

    return jax.tree.map(
        lambda leaf, spec: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, spec)
        ),
        state, _specs(),
    )


def test_restore_resharded_across_factorizations(tmp_path):
    """The tentpole round trip: saved on data4×fsdp2, resumed byte-parity
    on data2×fsdp4 AND a shrunk 6-device 3×2 mesh."""
    host = _host_state()
    want = reshard.leaf_checksums(host)
    mgr = TrainCheckpointManager(str(tmp_path), async_save=False)
    assert mgr.save(100, _placed(host, _mesh(4, 2)), wait=True)
    reshard.write_topology(str(tmp_path),
                           reshard.mesh_topology(_mesh(4, 2)))
    for d, f in ((2, 4), (3, 2)):
        step, state, report = reshard.restore_resharded(
            mgr, _abstract(host, _mesh(d, f))
        )
        assert step == 100 and report["parity_ok"] is True
        assert report["plan"]["src_topology"]["data"] == 4
        assert report["plan"]["dst_topology"]["data"] == d
        assert report["bytes_remapped"] == report["plan"]["total_bytes"] > 0
        assert reshard.leaf_checksums(state) == want
        # The restored leaves actually live on the target factorization.
        mesh = state["params"]["w"].sharding.mesh
        assert dict(mesh.shape) == {"data": d, "fsdp": f}
    st = reshard.reshard_stats()
    assert st["plans_applied_total"] == 2
    assert st["parity_checks_total"] == 2 and st["parity_failures_total"] == 0


def test_restore_resharded_manager_method(tmp_path):
    """checkpoint.TrainCheckpointManager grows the seam directly."""
    host = _host_state(1)
    mgr = TrainCheckpointManager(str(tmp_path), async_save=False)
    assert mgr.save(7, _placed(host, _mesh(4, 2)), wait=True)
    reshard.write_topology(str(tmp_path), {"data": 4, "fsdp": 2})
    step, state = mgr.restore_resharded(_abstract(host, _mesh(2, 4)))
    assert step == 7
    assert reshard.leaf_checksums(state) == reshard.leaf_checksums(host)


def test_restore_resharded_refuses_pipe_change(tmp_path):
    mgr = TrainCheckpointManager(str(tmp_path), async_save=False)
    step, state, report = reshard.restore_resharded(
        mgr, _abstract(_host_state(), _mesh(2, 4)),
        saved_topology={"data": 2, "fsdp": 2, "pipe": 2},
    )
    assert step is None and state is None
    assert "incompatible topology" in report["error"]


def test_restore_resharded_no_checkpoint(tmp_path):
    mgr = TrainCheckpointManager(str(tmp_path), async_save=False)
    step, state, report = reshard.restore_resharded(
        mgr, _abstract(_host_state(), _mesh(2, 4)),
        saved_topology={"data": 4, "fsdp": 2},
    )
    assert step is None and state is None
    assert report["error"] == "no restorable checkpoint"


def test_parity_gate_quarantines_and_raises(tmp_path, monkeypatch):
    """A re-placement that changes any leaf's bytes must never resume
    silently: the step is quarantined and ReshardParityError raised."""
    import jax

    host = _host_state(2)
    mgr = TrainCheckpointManager(str(tmp_path), async_save=False)
    assert mgr.save(5, _placed(host, _mesh(4, 2)), wait=True)
    real_put = jax.device_put

    def corrupting_put(x, *a, **kw):
        out = real_put(x, *a, **kw)
        if getattr(x, "shape", None) == (16, 8):
            return real_put(np.zeros_like(np.asarray(out)), *a, **kw)
        return out

    monkeypatch.setattr(jax, "device_put", corrupting_put)
    with pytest.raises(reshard.ReshardParityError, match="parity failure"):
        reshard.restore_resharded(
            mgr, _abstract(host, _mesh(2, 4)),
            saved_topology={"data": 4, "fsdp": 2},
        )
    assert 5 in mgr.quarantined_steps()
    st = reshard.reshard_stats()
    assert st["parity_failures_total"] == 1
    assert st["plans_applied_total"] == 0


def test_injected_restore_corruption_falls_back_through_reshard(tmp_path):
    """The faults.py restore-corruption seam rides the manager's existing
    quarantine-and-fall-back path inside a resharded restore too."""
    mgr = TrainCheckpointManager(str(tmp_path), async_save=False)
    old = _host_state(3)
    new = _host_state(4)
    assert mgr.save(1, _placed(old, _mesh(4, 2)), wait=True)
    assert mgr.save(2, _placed(new, _mesh(4, 2)), wait=True)
    inj = FaultInjector(FaultPlan(specs=[
        FaultSpec(kind=FaultKind.CHECKPOINT_RESTORE_CORRUPTION, at_step=2),
    ]))
    inj.arm()
    mgr._fault_injector = inj
    step, state, report = reshard.restore_resharded(
        mgr, _abstract(old, _mesh(2, 4)),
        saved_topology={"data": 4, "fsdp": 2},
    )
    # Step 2 "corrupted" → quarantined → step 1 resharded instead.
    assert step == 1 and report["parity_ok"] is True
    assert reshard.leaf_checksums(state) == reshard.leaf_checksums(old)
    assert 2 in mgr.quarantined_steps()


# ---------------------------------------------------------------------------
# Real-engine migration (held KV + prefix payloads)
# ---------------------------------------------------------------------------


def _engine(**kw):
    from tpu_engine.serving_fleet import ServingReplicaSpec, build_replica_engine

    base = dict(model_name="gpt-tiny", max_slots=2, max_len=96,
                prefill_chunk=16)
    base.update(kw)
    return build_replica_engine(ServingReplicaSpec(**base))


def _drive(engine, rid, steps=400):
    for _ in range(steps):
        if engine.result(rid)["status"] == "done":
            break
        engine.step()
    out = engine.result(rid)
    assert out["status"] == "done", out
    return out


def test_migrate_held_requests_across_pool_geometries():
    """Held hold_kv requests drain onto a pool of different chunk/lane
    geometry AND int8 storage; all complete, none left behind."""
    src = _engine()
    dst = _engine(max_slots=4, max_len=128, prefill_chunk=32, kv_quant=True)
    prompts = [[11, 7, 23, 42, 5], [3, 1, 4, 15, 9, 2]]
    for p in prompts:
        _drive(src, src.submit(p, max_new_tokens=1, hold_kv=True))
    assert src.held_requests() == [0, 1]

    res = reshard.migrate_held_requests(src, dst, max_new_tokens=4,
                                        now_s=2.5)
    assert res["migrated"] == 2 and res["wire_bytes"] > 0
    assert res["mttr_s"] == 2.5
    assert src.held_requests() == []
    for dst_rid in res["mapping"].values():
        out = _drive(dst, dst_rid)
        assert len(out["tokens"]) == 4
    reshard.note_migrated_completions(len(res["mapping"]))
    st = reshard.reshard_stats()
    assert st["migrations_total"] == 1
    assert st["held_requests_migrated_total"] == 2
    assert st["held_requests_completed_total"] == 2
    assert st["last_migration_mttr_s"] == 2.5


def test_migrate_prefix_and_host_rehydration():
    src = _engine(max_slots=2, prefix_cache_tokens=256)
    dst = _engine(max_slots=2, prefix_cache_tokens=256, kv_quant=True,
                  prefill_chunk=32, max_len=128)
    system = np.random.default_rng(7).integers(1, 250, 64).tolist()
    for tail in ([9, 9], [8, 8]):
        _drive(src, src.submit(system + tail, max_new_tokens=2))
    key = max(src._prefix_cache._entries, key=len)

    assert reshard.migrate_prefix(src, dst, list(key))
    assert dst.stats()["prefix_cache"]["entries"] == 1
    assert not reshard.migrate_prefix(src, dst, [1, 2, 3])  # not resident

    from tpu_engine.prefix_plane import HostKVTier

    tier = HostKVTier(budget_bytes=64 << 20, clock=lambda: 0.0)
    assert tier.put(key, handoff=src.export_prefix(list(key)), now=0.0)
    assert reshard.rehydrate_from_host(tier, list(key), dst, now=1.0)
    assert not reshard.rehydrate_from_host(tier, [4, 5, 6], dst, now=1.0)
    assert reshard.reshard_stats()["prefix_payloads_migrated_total"] == 2


def test_rebucket_for_pool_counts():
    from tests.test_disagg import _fake_handoff

    h, k, _v = _fake_handoff(T=5)
    out = reshard.rebucket_for_pool(h, chunk=8, max_lanes=16, kv_quant=False)
    assert out.length == 5
    np.testing.assert_allclose(out.k, k, rtol=1e-6)
    st = reshard.reshard_stats()
    assert st["kv_rebuckets_total"] == 1
    assert st["kv_rebucket_bytes_total"] == out.wire_bytes()


# ---------------------------------------------------------------------------
# Planner ranking term
# ---------------------------------------------------------------------------


def _chips(n, free=12.0, total=16.0):
    return [
        SimpleNamespace(index=i, hbm_free_gb=free, hbm_total_gb=total)
        for i in range(n)
    ]


def pcfg(**kw):
    from tpu_engine.sharding import TPUTrainConfig

    base = dict(
        model_name="gpt-tiny",
        mesh=MeshConfig(data=2, fsdp=4),
        micro_batch_size=2,
        gradient_accumulation_steps=2,
        seq_len=64,
    )
    base.update(kw)
    return TPUTrainConfig(**base)


def test_planner_inert_without_saved_topology():
    result = PlacementPlanner().plan(pcfg(), devices=_chips(8), gang=8)
    assert result.plans
    assert all(p.reshard_same_topology is None for p in result.plans)
    assert all(p.predicted_reshard_s == 0.0 for p in result.plans)


def test_planner_prefers_same_topology_within_band():
    planner = PlacementPlanner()
    # Widen the band so the ranking term (not the step-time estimator's
    # layout preference) is what this test exercises.
    planner.prefer_same_topology_max_slowdown_pct = 1000.0
    saved = {"data": 2, "fsdp": 4}
    result = planner.plan(pcfg(), devices=_chips(8), gang=8,
                          saved_topology=saved)
    assert result.plans
    head = result.best
    assert head.reshard_same_topology is True
    assert head.predicted_reshard_s == 0.0
    assert planner.stats()["reshard_tiebreaks_total"] >= 1
    # Topology-changing alternatives got priced, not rejected.
    changed = [p for p in result.plans if p.reshard_same_topology is False]
    assert changed and all(p.predicted_reshard_s > 0 for p in changed)
    assert "predicted_reshard_s" in result.table()[0]


def test_planner_rejects_pipe_extent_change():
    planner = PlacementPlanner()
    saved = {"data": 2, "fsdp": 2, "pipe": 2}
    result = planner.plan(pcfg(), devices=_chips(8), gang=8,
                          saved_topology=saved)
    # gpt-tiny enumerates pipe ∈ {1, 2}: pipe=1 layouts are refused with
    # the structured reason, pipe=2 layouts stay feasible.
    refused = [p for p in result.infeasible
               if (p.skip_reason or "").startswith(
                   "no_topology_compatible_checkpoint")]
    assert refused
    assert all(p.mesh["pipe"] == 2 for p in result.plans)
    assert planner.stats()["topology_rejected_total"] == len(refused)


# ---------------------------------------------------------------------------
# Scheduler: the structured skip on both admission paths
# ---------------------------------------------------------------------------


def test_fixed_config_skip_no_topology_compatible_checkpoint(
    sched_factory, tmp_path
):
    reshard.write_topology(str(tmp_path), {"data": 1, "fsdp": 2, "pipe": 2})
    s = sched_factory(max_concurrent_jobs=2, fleet_fn=TPUManager.get_mock_fleet)
    sub = s.submit(cfg(checkpoint_dir=str(tmp_path)))
    assert wait_until(
        lambda: sub.last_skip_reason == "no_topology_compatible_checkpoint:gpt-tiny"
    )
    assert sub.state == SubmissionState.QUEUED
    (entry,) = s.queue_state()["queued"]
    assert entry["last_skip_reason"] == \
        "no_topology_compatible_checkpoint:gpt-tiny"


def test_fixed_config_compatible_manifest_admits(sched_factory, tmp_path):
    # Different data/fsdp factorization but same pipe extent: bridgeable,
    # admission proceeds.
    reshard.write_topology(str(tmp_path), {"data": 2, "fsdp": 1})
    s = sched_factory(max_concurrent_jobs=2, fleet_fn=TPUManager.get_mock_fleet)
    sub = s.submit(cfg(checkpoint_dir=str(tmp_path)))
    assert wait_until(lambda: sub.state == SubmissionState.RUNNING)


def test_auto_placement_skip_no_topology_compatible_checkpoint(
    sched_factory, tmp_path
):
    # pipe=5 divides nothing the planner can stage for gpt-tiny (2
    # layers), so every enumerated layout is refused on topology.
    reshard.write_topology(str(tmp_path), {"data": 1, "fsdp": 1, "pipe": 5})
    s = sched_factory(max_concurrent_jobs=2, fleet_fn=TPUManager.get_mock_fleet)
    sub = s.submit(cfg(
        mesh=MeshConfig(data=-1, fsdp=1),
        checkpoint_dir=str(tmp_path),
        auto_place=True,
    ))
    assert wait_until(
        lambda: sub.last_skip_reason == "no_topology_compatible_checkpoint:gpt-tiny"
    )
    assert sub.state == SubmissionState.QUEUED


# ---------------------------------------------------------------------------
# Twin lane: deterministic replay + gates at reduced size
# ---------------------------------------------------------------------------


def test_replay_reshard_resume_zero_lost_steps_and_deterministic():
    from tpu_engine.compile_index import CompileCacheIndex
    from tpu_engine.twin import (
        TrainTwinParams,
        chip_fault_timeline,
        replay_reshard_resume,
        replay_self_heal,
        seed_initial_compile,
    )

    params = TrainTwinParams(layout_prefix="reshard")
    events = chip_fault_timeline(0, n_faults=12, params=params)
    assert events

    def run(fn):
        idx = CompileCacheIndex()
        seed_initial_compile(idx, params)
        return fn(events, params, compile_index=idx) if fn is replay_self_heal \
            else fn(events, params, state_bytes=12_000_000_000,
                    compile_index=idx)

    rs = run(replay_reshard_resume)
    assert rs == run(replay_reshard_resume)  # byte-identical repeat
    assert rs["lost_steps"] == 0
    assert rs["topology_changes"] >= rs["faults"] > 0
    assert rs["reshard_s_total"] > 0
    same = run(replay_self_heal)
    # Topology freedom costs the remap leg but stays within the exit
    # gate's 1.5× budget of the warm same-topology mean.
    assert same["mttr_mean_s"] < rs["mttr_mean_s"] <= 1.5 * same["mttr_mean_s"]


def test_reshard_roundtrip_report_gates():
    from tpu_engine.twin import reshard_roundtrip_report

    rep = reshard_roundtrip_report(seed=0)
    assert rep["ok"], rep
    assert len(rep["targets"]) == 2
    assert all(t["byte_parity_vs_source"] for t in rep["targets"])


def test_reshard_ab_gates():
    """The whole reshard lane: topology-changing resume against the warm
    same-topology recovery and the topology-locked restart on the seeded
    chip-fault trace, the checkpoint round trip, and held serving requests
    migrating to the destination pool."""
    from tpu_engine.twin import reshard_ab

    res = reshard_ab(seed=0)
    assert res["ok"], res["gates"]
    assert set(res["gates"]) == {
        "zero_lost_steps", "mttr_within_budget", "beats_topology_locked",
        "roundtrip_byte_parity", "held_requests_complete",
        "int8_parity_within_bound", "prefix_migrates_both_paths",
        "deterministic_repeat",
    }
    assert res["reshard"]["lost_steps"] == 0
    assert res["topology_locked"]["lost_steps"] > 0
    assert res["reshard"]["mttr_mean_s"] <= res["mttr_budget_s"]
    mig = res["migration"]
    assert mig["migrated"] > 0 and mig["completed"] == mig["migrated"]
    assert mig["parity_mismatches"] <= mig["migrated"]
