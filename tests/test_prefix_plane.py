"""Fleet prefix plane: radix index, host-RAM KV tier, cache-aware routing.

Three tiers in one file, mirroring ``test_disagg.py``:

- **Unit properties** — trie insert/remove/prune and the longest-holder
  walk; the host tier's byte ledger and its reuse-scored (NOT least-
  recently-used) eviction; plane routing hints, admission bookkeeping,
  spill-to-host and replica teardown; the HBM estimator's host-tier term
  and its structured over-budget rejection.
- **Real-engine round trips** — ``_PrefixCache`` reuse telemetry;
  ``export_prefix``/``install_prefix``; and all four KVHandoff wire x
  pool conversions round-tripping store -> host tier -> rehydrate within
  the documented one-token int8 bound.
- **Twin lane** — the seeded many-tenant lane is deterministic and the
  A/B gates (p99 TTFT >= 2x, throughput no worse, host tier absorbs
  overflow) hold at reduced duration.
"""

import numpy as np
import pytest

from tests.test_disagg import MAX_NEW, PROMPT, drive, extract, tiny_spec
from tpu_engine.hbm_estimate import HostBudgetExceeded, estimate_serving_hbm
from tpu_engine.historian import MetricHistorian
from tpu_engine.prefix_plane import (
    HIT_TOKENS_SERIES,
    HOST_HOLDER,
    HostKVTier,
    PrefixPlane,
    PrefixTrieIndex,
    plane_stats,
    quantize_handoff,
)

# ---------------------------------------------------------------------------
# PrefixTrieIndex
# ---------------------------------------------------------------------------


def test_trie_longest_holder_walk():
    idx = PrefixTrieIndex()
    idx.insert([1, 2, 3, 4], "a")
    idx.insert([1, 2], "b")
    idx.insert([7, 8], "c")
    # Deepest marked node wins; shallower holders are shadowed.
    matched, holders = idx.longest_holders([1, 2, 3, 4, 99])
    assert (matched, holders) == (4, {"a"})
    # A prompt diverging after 2 tokens falls back to the shallower mark.
    matched, holders = idx.longest_holders([1, 2, 9])
    assert (matched, holders) == (2, {"b"})
    assert idx.longest_holders([5, 5]) == (0, set())
    # exclude filters holders without disturbing depth preference.
    matched, holders = idx.longest_holders([1, 2, 3, 4], exclude={"a"})
    assert (matched, holders) == (2, {"b"})


def test_trie_remove_prunes_empty_tail():
    idx = PrefixTrieIndex()
    idx.insert([1, 2, 3], "a")
    idx.insert([1, 2], "b")
    n_full = idx.nodes
    assert n_full == 4  # root + 3
    idx.remove([1, 2, 3], "a")
    # The [.., 3] tail node is unreachable garbage — it must be pruned —
    # while the shared [1, 2] spine survives for "b".
    assert idx.nodes == 3
    assert idx.longest_holders([1, 2, 3]) == (2, {"b"})
    assert idx.n_prefixes == 1
    # Removing an unknown (prefix, holder) pair is a no-op.
    idx.remove([1, 2, 3], "a")
    assert idx.nodes == 3


def test_trie_drop_holder_forgets_everything():
    idx = PrefixTrieIndex()
    idx.insert([1, 2], "a")
    idx.insert([3, 4], "a")
    idx.insert([1, 2], "b")
    idx.drop_holder("a")
    assert idx.prefixes("a") == set()
    assert idx.longest_holders([3, 4]) == (0, set())
    assert idx.longest_holders([1, 2]) == (2, {"b"})


# ---------------------------------------------------------------------------
# HostKVTier
# ---------------------------------------------------------------------------


def _tier(budget, **kw):
    kw.setdefault("historian", MetricHistorian())
    return HostKVTier(budget_bytes=budget, **kw)


def test_host_tier_byte_ledger_and_refresh():
    tier = _tier(250, clock=lambda: 0.0)
    assert tier.put([1, 1], nbytes=100)
    assert tier.put([2, 2], nbytes=100)
    assert tier.total_bytes == 200
    # Refreshing an entry re-charges, not double-charges.
    assert tier.put([1, 1], nbytes=120)
    assert tier.total_bytes == 220
    assert tier.contains([1, 1]) and tier.contains([2, 2])
    # A payload larger than the whole budget is refused outright.
    assert not tier.put([3, 3], nbytes=251)
    assert tier.stats()["occupancy"] == round(220 / 250, 4)
    tier.pop([1, 1])
    assert tier.total_bytes == 100


def test_host_tier_evicts_by_reuse_not_recency():
    """The eviction victim is the LOWEST historian-scored prefix: a
    frequently re-hit entry survives even when another entry was touched
    more recently (plain LRU would evict the old hot entry)."""
    now = [0.0]
    tier = _tier(250, clock=lambda: now[0], reuse_window_s=600.0)
    hot, cold = (1, 2, 3), (4, 5, 6)
    assert tier.put(hot, nbytes=100, now=0.0)
    assert tier.put(cold, nbytes=100, now=1.0)
    for t in (2.0, 3.0, 4.0):
        assert tier.get(hot, now=t) is None  # capacity entry, hit counted
    tier.get(cold, now=5.0)  # cold touched LAST -> LRU would keep it
    assert tier.put((7, 8, 9), nbytes=100, now=6.0)
    assert tier.contains(hot)
    assert not tier.contains(cold)
    assert tier.evictions == 1
    st = tier.stats()
    assert st["entries"] == 2 and st["hits"] == 4


def test_host_tier_reuse_score_falls_back_without_series():
    """With no historian coverage the tier's own lifetime hit counters
    drive the same decision (telemetry loss must not randomize
    eviction)."""

    class _Deaf:
        def record(self, *a, **kw):
            raise RuntimeError("down")

        def query(self, *a, **kw):
            raise RuntimeError("down")

    tier = HostKVTier(budget_bytes=250, historian=_Deaf(),
                      clock=lambda: 0.0)
    assert tier.put((1,), nbytes=100, now=0.0)
    assert tier.put((2,), nbytes=100, now=1.0)
    tier.get((1,), now=2.0)
    tier.get((1,), now=3.0)
    tier.get((2,), now=4.0)
    assert tier.put((3,), nbytes=100, now=5.0)
    assert tier.contains((1,)) and not tier.contains((2,))


def test_host_tier_hits_feed_historian_series():
    hist = MetricHistorian()
    tier = HostKVTier(budget_bytes=1000, historian=hist, clock=lambda: 0.0)
    prefix = (9, 9, 9)
    tier.put(prefix, nbytes=10, now=0.0)
    tier.get(prefix, now=1.0)
    q = hist.query(
        HIT_TOKENS_SERIES, t0=0.0, t1=10.0, agg="sum",
        labels={"prefix": HostKVTier.prefix_label(prefix)},
    )
    assert q["count"] == 1 and q["value"] == len(prefix)


# ---------------------------------------------------------------------------
# PrefixPlane
# ---------------------------------------------------------------------------


def _plane(**kw):
    kw.setdefault("historian", MetricHistorian())
    kw.setdefault("clock", lambda: 0.0)
    kw.setdefault("host", HostKVTier(
        budget_bytes=1 << 20, historian=kw["historian"], clock=kw["clock"]
    ))
    return PrefixPlane(**kw)


def test_plane_route_hint_prefers_longest_then_free():
    plane = _plane(prefix_tokens=8)
    plane.index.insert([1, 2], "r_short")
    plane.index.insert([1, 2, 3, 4], "r_long")
    plane.index.insert([1, 2, 3, 4], HOST_HOLDER)
    rid, matched = plane.route_hint([1, 2, 3, 4, 5], {"r_short": 4,
                                                      "r_long": 4})
    assert (rid, matched) == ("r_long", 4)  # host sentinel never routed to
    # The longest holder being slot-full yields (None, matched): the
    # caller falls through to WRR but knows the host tier may still help.
    rid, matched = plane.route_hint([1, 2, 3, 4, 5], {"r_long": 0})
    assert (rid, matched) == (None, 4)
    # Free-slot count breaks ties between equal-depth holders.
    plane.index.insert([1, 2, 3, 4], "r_other")
    rid, _ = plane.route_hint([1, 2, 3, 4], {"r_long": 1, "r_other": 3})
    assert rid == "r_other"


def test_plane_admission_lifecycle_and_spill():
    """cold -> replica hit -> mirror overflow spills to the host tier ->
    a different replica's admission rehydrates from it."""
    spilled = []

    def spill(prefix, rid):
        spilled.append((prefix, rid))
        return 64  # capacity model: 64 bytes per prefix

    plane = _plane(prefix_tokens=2, replica_prefix_budget=1, spill=spill)
    assert plane.observe_admit([1, 1, 9], "r0", now=0.0)["kind"] == "cold"
    assert plane.observe_admit([1, 1, 8], "r0", now=1.0)["kind"] == "replica"
    # A second prefix overflows r0's single-entry mirror: (1, 1) must
    # spill to the host tier, not vanish.
    obs = plane.observe_admit([2, 2, 9], "r0", now=2.0)
    assert obs["kind"] == "cold" and obs["evicted"] == [(1, 1)]
    assert spilled == [((1, 1), "r0")]
    assert plane.host.contains((1, 1))
    assert HOST_HOLDER in plane.index.longest_holders([1, 1])[1]
    # Another replica admitting the spilled prefix is a host rehydration.
    obs = plane.observe_admit([1, 1, 7], "r1", now=3.0)
    assert obs["kind"] == "host" and obs["payload"] is None
    st = plane.stats()
    assert st["host_rehydrations"] == 1
    assert st["host"]["stores"] == 1
    # The rehydrated replica now serves route hints for the prefix.
    assert plane.route_hint([1, 1, 5], {"r0": 4, "r1": 4})[0] == "r1"


def test_plane_spill_skipped_while_another_replica_holds():
    plane = _plane(prefix_tokens=2, replica_prefix_budget=1,
                   spill=lambda p, r: 64)
    plane.observe_admit([1, 1, 9], "r0", now=0.0)
    plane.observe_admit([1, 1, 9], "r1", now=1.0)  # r1 holds it too
    plane.observe_admit([2, 2, 9], "r0", now=2.0)  # evicts r0's copy
    # r1 still holds the prefix on-device: no host bytes spent on it.
    assert not plane.host.contains((1, 1))
    assert plane.route_hint([1, 1, 5], {"r0": 4, "r1": 4})[0] == "r1"


def test_plane_drop_replica_keeps_host_copy():
    plane = _plane(prefix_tokens=2, replica_prefix_budget=4)
    plane.observe_admit([3, 3, 1], "r0", now=0.0)
    plane.store_host([3, 3], nbytes=64, now=1.0)
    plane.drop_replica("r0")
    # No replica holds it any more (matched counts replica holders only)
    # but the host copy survives the teardown and stays discoverable.
    assert plane.route_hint([3, 3, 1], {"r1": 4}) == (None, 0)
    assert plane.host_prefix_for([3, 3, 1]) == (3, 3)
    assert plane.stats()["replicas_tracked"] == 0


def test_plane_module_counters_track_activity():
    from tpu_engine.prefix_plane import _reset_stats_for_tests

    _reset_stats_for_tests()
    try:
        plane = _plane(prefix_tokens=2, replica_prefix_budget=1,
                       spill=lambda p, r: 64)
        plane.observe_admit([1, 1, 9], "r0", now=0.0)
        plane.observe_admit([2, 2, 9], "r0", now=1.0)  # spills (1, 1)
        plane.observe_admit([1, 1, 7], "r1", now=2.0)  # host rehydration
        plane.route_hint([2, 2, 5], {"r0": 4})
        st = plane_stats()
        assert st["lookups_total"] == 1
        assert st["index_hits_total"] == 1
        assert st["host_stores_total"] == 1
        assert st["rehydrations_total"] == 1
        assert st["host_hits_total"] == 1
        assert st["index_prefixes"] >= 1
    finally:
        _reset_stats_for_tests()


# ---------------------------------------------------------------------------
# HBM estimator: host-tier term + structured rejection
# ---------------------------------------------------------------------------


def test_estimate_host_tier_term_and_budget():
    base = estimate_serving_hbm("llama-1b", 8, 2048)
    assert base.host_gib == 0.0
    est = estimate_serving_hbm(
        "llama-1b", 8, 2048, host_prefix_tokens=100_000, host_budget_gib=8.0
    )
    assert est.host_gib > 0
    # The host tier lives in host RAM: the device-side totals are
    # untouched by promising host-resident prefix tokens.
    assert est.device_total_gib == base.device_total_gib
    assert any("host" in n for n in est.notes)


def test_estimate_rejects_oversubscribed_host_budget():
    with pytest.raises(HostBudgetExceeded) as ei:
        estimate_serving_hbm(
            "llama-1b", 8, 2048,
            host_prefix_tokens=1 << 30, host_budget_gib=1.0,
        )
    reason = ei.value.reason
    assert reason["kind"] == "host_budget_exceeded"
    assert reason["model_name"] == "llama-1b"
    assert reason["required_gib"] > reason["budget_gib"] == 1.0


def test_plan_host_tier_sizes_through_estimator():
    tier = PrefixPlane.plan_host_tier("llama-1b", 8, 2048,
                                      host_prefix_tokens=10_000,
                                      host_budget_gib=2.0)
    assert tier.budget_bytes == int(2.0 * (1 << 30))
    with pytest.raises(HostBudgetExceeded):
        PrefixPlane.plan_host_tier("llama-1b", 8, 2048,
                                   host_prefix_tokens=1 << 30,
                                   host_budget_gib=1.0)


# ---------------------------------------------------------------------------
# Real-engine round trips (gpt-tiny, like test_disagg)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engines():
    from tpu_engine.serving_fleet import build_replica_engine

    return {
        "prefill": build_replica_engine(tiny_spec()),
        "decode": build_replica_engine(tiny_spec()),
        "decode_kvq": build_replica_engine(tiny_spec(kv_quant=True)),
    }


@pytest.fixture(scope="module")
def baseline_tokens(engines):
    out = drive(engines["decode"], engines["decode"].submit(PROMPT, MAX_NEW))
    assert len(out["tokens"]) == MAX_NEW
    return list(out["tokens"])


@pytest.mark.parametrize("pool", ["decode", "decode_kvq"])
@pytest.mark.parametrize("wire_quant", [False, True])
def test_host_tier_roundtrip_all_wire_pool_pairs(
    engines, baseline_tokens, wire_quant, pool
):
    """All four wire x pool conversions survive the host tier: extract
    (fp or int8 wire) -> HostKVTier.put (always stores int8) -> get ->
    submit_prefilled into an fp or int8 slot pool, within the documented
    one-token bound of the single-replica baseline."""
    pre, dec = engines["prefill"], engines[pool]
    out = drive(pre, pre.submit(PROMPT, max_new_tokens=1, hold_kv=True))
    h = extract(pre, out["id"], quantize=wire_quant)
    assert h.quantized == wire_quant

    tier = HostKVTier(budget_bytes=1 << 20, historian=MetricHistorian(),
                      clock=lambda: 0.0)
    key = tuple(h.prompt)
    assert tier.put(key, handoff=h, now=0.0)
    stored = tier.get(key, now=1.0)
    assert stored is not None and stored.quantized  # host form is int8
    if wire_quant:
        assert stored is h  # already-int8 payloads pass through untouched

    got = drive(dec, dec.submit_prefilled(stored,
                                          max_new_tokens=MAX_NEW - 1))
    stitched = [out["tokens"][0], *got["tokens"]]
    assert len(stitched) == len(baseline_tokens)
    mismatches = sum(a != b for a, b in zip(stitched, baseline_tokens))
    assert mismatches <= 1


def test_quantize_handoff_matches_wire_quantizer(engines):
    pre = engines["prefill"]
    out = drive(pre, pre.submit(PROMPT, max_new_tokens=1, hold_kv=True))
    fp = extract(pre, out["id"])
    q = quantize_handoff(fp)
    assert q.quantized and q.dtype == "int8"
    assert q.k.dtype == np.int8 and q.k_scale.shape == (*q.k.shape[:-1], 1)
    assert q.wire_bytes() < fp.wire_bytes()
    # Round-trip bound: absmax int8 error is half a code step.
    deq = q.k.astype(np.float32) * q.k_scale
    assert np.all(np.abs(deq - fp.k) <= q.k_scale / 2 + 1e-6)


def _drain(engine, prompts, max_new=4, steps=200):
    rids = [engine.submit(p, max_new_tokens=max_new) for p in prompts]
    for _ in range(steps):
        if all(engine.result(r)["status"] == "done" for r in rids):
            break
        engine.step()
    return [engine.result(r)["tokens"] for r in rids]


def test_prefix_cache_reuse_telemetry():
    """Satellite: ``_PrefixCache`` reports hit-token totals and per-entry
    hit counts through the batcher's stats surface."""
    eng = _fresh_cached_engine()
    rng = np.random.default_rng(5)
    # Longer than one prefill chunk (64 on the replica build) so the
    # shared prefix crosses a cacheable boundary.
    system = rng.integers(1, 250, 80).tolist()
    tails = [[1, 2], [3, 4], [5, 6]]
    _drain(eng, [system + t for t in tails])
    st = eng.stats()["prefix_cache"]
    assert st["hits"] >= 2
    # Every hit pasted >= one full chunk of the shared system prompt.
    assert st["hit_tokens_total"] >= 64 * st["hits"]
    assert isinstance(st["entry_hits"], list) and st["entry_hits"]
    assert sum(e["hits"] for e in st["entry_hits"]) == st["hits"]
    assert all(e["prefix_tokens"] > 0 for e in st["entry_hits"])


def _fresh_cached_engine(**kw):
    from tpu_engine.serving_fleet import build_replica_engine

    return build_replica_engine(
        tiny_spec(prefix_cache_tokens=512, **kw)
    )


def test_export_install_prefix_cross_replica():
    """A prefix exported from one replica's cache installs into another
    replica and serves its first warm admission without re-prefilling the
    shared tokens — the live rehydration path ``_observe_plane`` uses."""
    src, dst = _fresh_cached_engine(), _fresh_cached_engine()
    rng = np.random.default_rng(7)
    system = rng.integers(1, 250, 80).tolist()
    ref = _drain(src, [system + [9, 9], system + [8, 8]])
    assert src.stats()["prefix_cache"]["entries"] >= 1

    key = max(src._prefix_cache._entries, key=len)
    h = src.export_prefix(list(key))
    assert h is not None
    assert h.length == len(key) and list(h.prompt) == list(key)
    assert h.emitted == []
    # Prefix-export payloads are deliberately NOT decodable — they lack
    # the emitted token submit_prefilled needs to resume decoding from.
    with pytest.raises(ValueError):
        dst.submit_prefilled(h)

    assert dst.install_prefix(list(key), h)
    st = dst.stats()["prefix_cache"]
    assert st["entries"] == 1 and st["tokens"] >= len(key)
    # Warm admissions on the installed prefix hit AND stream identically.
    got = _drain(dst, [system + [9, 9], system + [8, 8]])
    assert got == ref
    st = dst.stats()["prefix_cache"]
    assert st["hits"] >= 1 and st["hit_tokens_total"] >= len(key)
    # Re-installing a resident prefix is an idempotent no-op.
    assert dst.install_prefix(list(key), h)


def test_export_prefix_unknown_key_is_none():
    eng = _fresh_cached_engine()
    assert eng.export_prefix([1, 2, 3]) is None


# ---------------------------------------------------------------------------
# Twin lane: determinism + the measured A/B gates
# ---------------------------------------------------------------------------


def _short_params(**kw):
    from tpu_engine.twin import PrefixPlaneLaneParams

    base = dict(duration_s=200.0, warmup_s=30.0, n_replicas=3,
                n_prefixes=24, replica_cache_prefixes=4,
                host_budget_entries=48, burst_every_s=60.0)
    base.update(kw)
    return PrefixPlaneLaneParams(**base)


def test_twin_lane_deterministic():
    from tpu_engine.twin import prefix_plane_lane

    p = _short_params(duration_s=90.0)
    a = prefix_plane_lane(seed=3, plane=True, params=p)
    b = prefix_plane_lane(seed=3, plane=True, params=p)
    assert a == b
    c = prefix_plane_lane(seed=4, plane=True, params=p)
    assert c != a


def test_twin_ab_gates_hold():
    from tpu_engine.twin import prefix_plane_ab

    res = prefix_plane_ab(seed=0, params=_short_params())
    assert res["gates"]["plane_beats_baseline_p99_ttft_2x"], res["gates"]
    assert res["gates"]["tokens_per_sec_no_worse"]
    assert res["gates"]["deterministic_repeat"]
    assert res["gates"]["host_tier_absorbs_overflow"]
    assert res["gates"]["host_budget_rejected"]
    assert res["ok"]
    assert res["host_budget_rejection"]["kind"] == "host_budget_exceeded"
    assert res["ttft_p99_improvement"] >= 2.0
    plane = res["plane"]["plane"]
    assert plane["host"]["stores"] > 0 and plane["host_rehydrations"] > 0
