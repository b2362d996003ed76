"""Fleet-historian invariants: rollup-tier conservation against the raw
ring, the range-query engine (aggs, tier selection, approx degradation),
bounded memory under a 10k-tick scrape sim, virtual-clock determinism
(explicit timestamps never consult the wall clock), incident stitching
across every chaos fault kind, and the twin chaos-replay fidelity gate.

Everything runs on a virtual clock — no sleeps, no wall-clock reads."""

import pytest

from tpu_engine.faults import FaultKind
from tpu_engine.historian import (
    DEFAULT_TIERS,
    IncidentCorrelator,
    MetricHistorian,
    percentile,
)


def _forbidden_clock() -> float:
    raise AssertionError("historian consulted the wall clock")


def _fill(hist, name, pairs, labels=None):
    for ts, v in pairs:
        hist.record(name, v, ts=ts, labels=labels)


# ---------------------------------------------------------------------------
# Rollup conservation: every tier is an exact fold of the raw samples.
# ---------------------------------------------------------------------------


def test_rollup_tiers_conserve_raw_samples():
    hist = MetricHistorian(raw_capacity=4096, clock=_forbidden_clock)
    samples = [(i * 0.7, float((i * 37) % 101) - 50.0) for i in range(500)]
    _fill(hist, "m", samples)
    for width, _max_buckets in DEFAULT_TIERS:
        buckets = hist.buckets("m", width)
        assert buckets, f"tier {width} retained nothing"
        assert sum(b["count"] for b in buckets) == len(samples)
        assert sum(b["sum"] for b in buckets) == pytest.approx(
            sum(v for _, v in samples)
        )
        assert min(b["min"] for b in buckets) == min(v for _, v in samples)
        assert max(b["max"] for b in buckets) == max(v for _, v in samples)
        for b in buckets:
            inside = [
                v for ts, v in samples
                if b["t0"] <= ts < b["t0"] + b["width_s"]
            ]
            assert b["count"] == len(inside)
            assert b["sum"] == pytest.approx(sum(inside))
            assert b["min"] == min(inside)
            assert b["max"] == max(inside)
            assert b["first"] == inside[0]
            assert b["last"] == inside[-1]


def test_coarser_tier_is_fold_of_finer_tier():
    hist = MetricHistorian(clock=_forbidden_clock)
    _fill(hist, "m", [(i * 1.3, float(i % 17)) for i in range(400)])
    fine = hist.buckets("m", 10.0)
    coarse = hist.buckets("m", 60.0)
    for cb in coarse:
        members = [
            fb for fb in fine
            if cb["t0"] <= fb["t0"] < cb["t0"] + 60.0
        ]
        assert cb["count"] == sum(fb["count"] for fb in members)
        assert cb["sum"] == pytest.approx(sum(fb["sum"] for fb in members))
        assert cb["min"] == min(fb["min"] for fb in members)
        assert cb["max"] == max(fb["max"] for fb in members)


# ---------------------------------------------------------------------------
# Query engine
# ---------------------------------------------------------------------------


def test_query_raw_aggregates():
    hist = MetricHistorian(clock=_forbidden_clock)
    _fill(hist, "m", [(float(i), float(i)) for i in range(10)])
    q = hist.query("m", t0=2.0, t1=7.0, agg="avg", tier="raw")
    assert q["tier"] == "raw" and not q["approx"]
    assert q["count"] == 6
    assert q["value"] == pytest.approx(4.5)
    assert q["aggregates"] == {
        "count": 6, "sum": 27.0, "avg": 4.5, "min": 2.0, "max": 7.0,
        "last": 7.0,
    }
    assert q["points"] == [[float(i), float(i)] for i in range(2, 8)]
    assert hist.query("m", t0=0.0, t1=9.0, agg="sum")["value"] == 45.0
    assert hist.query("m", t0=0.0, t1=9.0, agg="count")["value"] == 10
    assert hist.query("m", t0=0.0, t1=9.0, agg="last")["value"] == 9.0


def test_query_rate_and_p99():
    hist = MetricHistorian(clock=_forbidden_clock)
    _fill(hist, "c", [(0.0, 0.0), (10.0, 50.0)])
    assert hist.query("c", t0=0.0, t1=10.0, agg="rate")["value"] == 5.0
    # Single point: no rate.
    _fill(hist, "one", [(0.0, 1.0)])
    assert hist.query("one", t0=0.0, t1=1.0, agg="rate")["value"] is None
    _fill(hist, "p", [(0.0, 0.0), (1.0, 100.0)])
    assert hist.query("p", t0=0.0, t1=1.0, agg="p99")["value"] == (
        pytest.approx(99.0)
    )
    assert percentile([0.0, 100.0], 0.5) == 50.0


def test_query_defaults_trailing_window_and_unknowns_raise():
    hist = MetricHistorian(clock=_forbidden_clock)
    _fill(hist, "m", [(1000.0, 1.0), (1500.0, 2.0), (2000.0, 3.0)])
    # t1 defaults to the series' last_ts, t0 to t1 - 600 — no clock read.
    q = hist.query("m")
    assert (q["t0"], q["t1"]) == (1400.0, 2000.0)
    assert q["count"] == 2
    with pytest.raises(ValueError):
        hist.query("m", agg="median")
    with pytest.raises(ValueError):
        hist.query("m", tier="5m")
    missing = hist.query("nope")
    assert missing["value"] is None and missing["count"] == 0


def test_query_auto_falls_back_to_rollup_when_ring_wraps():
    hist = MetricHistorian(raw_capacity=16, clock=_forbidden_clock)
    _fill(hist, "m", [(float(i), float(i)) for i in range(200)])
    # Ring wrapped: raw no longer covers t0=0, auto serves a rollup tier.
    q = hist.query("m", t0=0.0, t1=199.0, agg="avg", tier="auto")
    assert q["tier"] in ("10s", "1m") and q["approx"]
    assert q["count"] > 16  # rollups retained what the ring dropped
    assert q["value"] == pytest.approx(sum(range(200)) / 200)
    # p99 degrades to the bucket max (upper bound) and is marked approx.
    p = hist.query("m", t0=0.0, t1=150.0, agg="p99", tier="1m")
    assert p["approx"] and p["value"] >= 149.0
    # An explicit raw query still answers from what the ring kept.
    r = hist.query("m", t0=0.0, t1=199.0, tier="raw")
    assert r["count"] == 16 and not r["approx"]


def test_labelled_series_are_distinct_and_exported():
    hist = MetricHistorian(clock=_forbidden_clock)
    _fill(hist, "m", [(0.0, 1.0)], labels={"host": 0})
    _fill(hist, "m", [(0.0, 9.0)], labels={"host": 1})
    assert hist.query("m", t0=0.0, t1=1.0, labels={"host": "1"})["value"] == 9.0
    assert len(hist.series_list()) == 2
    trace = hist.export_chrome_counters(["m"])
    names = {ev["name"] for ev in trace["traceEvents"]}
    assert names == {"m{host=0}", "m{host=1}"}
    assert all(ev["ph"] == "C" for ev in trace["traceEvents"])


# ---------------------------------------------------------------------------
# Bounded memory: a 10k-tick scrape sim must plateau, not grow.
# ---------------------------------------------------------------------------


def test_memory_bounded_under_10k_tick_sim():
    hist = MetricHistorian(
        raw_capacity=64,
        tiers=((10.0, 32), (60.0, 16)),
        max_series=8,
        clock=_forbidden_clock,
    )
    hist.add_collector(
        lambda now: {f"sim_{i}": (now % 97.0) + i for i in range(4)}
    )
    steady = None
    for i in range(10_000):
        hist.tick(now=i * 5.0)
        if i == 8_999:
            steady = hist.stats()
    final = hist.stats()
    assert final["ticks_total"] == 10_000
    assert final["samples_total"] == 40_000
    assert final["series"] == 4
    assert final["raw_samples"] <= 4 * 64
    assert final["rollup_buckets"]["10s"] <= 4 * 32
    assert final["rollup_buckets"]["1m"] <= 4 * 16
    assert final["bucket_evictions_total"] > 0
    # Steady state: the footprint between tick 9k and 10k is identical —
    # retention evicts exactly what ingestion adds.
    assert final["estimated_bytes"] == steady["estimated_bytes"]
    assert final["raw_samples"] == steady["raw_samples"]
    assert final["rollup_buckets"] == steady["rollup_buckets"]


def test_series_registry_evicts_least_recently_written():
    hist = MetricHistorian(max_series=4, clock=_forbidden_clock)
    for i in range(10):
        hist.record("m", 1.0, ts=float(i), labels={"i": i})
    st = hist.stats()
    assert st["series"] == 4 and st["series_evicted_total"] == 6
    kept = {s["labels"]["i"] for s in hist.series_list()}
    assert kept == {"6", "7", "8", "9"}


def test_collector_failure_is_counted_not_raised():
    hist = MetricHistorian(clock=_forbidden_clock)
    def _boom(now):
        raise RuntimeError("collector exploded")
    hist.add_collector(_boom)
    hist.add_collector(lambda now: {"ok": 1.0})
    assert hist.tick(now=0.0) == 1
    assert hist.stats()["collector_errors_total"] == 1


# ---------------------------------------------------------------------------
# Virtual-clock determinism
# ---------------------------------------------------------------------------


def test_identical_replays_are_bit_identical():
    def build():
        h = MetricHistorian(clock=_forbidden_clock)
        c = IncidentCorrelator(clock=_forbidden_clock, stale_after_s=1e9)
        for i in range(300):
            h.record("step_time_s", 0.1 + (i % 7) * 0.01, ts=i * 0.5)
        c.ingest(records=_chain_records("chip-unhealthy", 3, 10.0, 0), now=50.0)
        return h, c
    h1, c1 = build()
    h2, c2 = build()
    for agg in ("avg", "min", "max", "last", "sum", "count", "rate", "p99"):
        assert h1.query("step_time_s", t0=0.0, t1=150.0, agg=agg) == (
            h2.query("step_time_s", t0=0.0, t1=150.0, agg=agg)
        )
    assert h1.buckets("step_time_s", 10.0) == h2.buckets("step_time_s", 10.0)
    assert c1.incidents(limit=0) == c2.incidents(limit=0)
    assert c1.stats() == c2.stats()


def test_ingest_counter_events_rebuilds_series_at_recorded_timestamps():
    hist = MetricHistorian(clock=_forbidden_clock)
    events = [
        {"kind": "counter", "name": "goodput", "ts": float(t),
         "attrs": {"fraction": t / 10.0, "note": "skip-me"}}
        for t in range(10)
    ]
    assert hist.ingest_counter_events(events) == 10
    q = hist.query("goodput.fraction", t0=0.0, t1=9.0, tier="raw")
    assert q["count"] == 10 and q["aggregates"]["last"] == 0.9
    # Non-counter and malformed records are ignored.
    assert hist.ingest_counter_events([{"kind": "span"}, {"kind": "counter"}]) == 0


# ---------------------------------------------------------------------------
# Incident stitching
# ---------------------------------------------------------------------------


def _chain_records(kind_value, device, base_ts, seq):
    """One self-heal chain as raw flight-recorder JSONL: FaultEvent detect,
    parented scheduler requeue, parented supervisor resume."""
    tid = f"trace-{seq}"
    return [
        {"record": "event", "event_id": f"f-{seq}", "trace_id": tid,
         "parent_id": None, "name": kind_value, "kind": "fault",
         "ts": base_ts, "attrs": {"device": device, "kind": kind_value}},
        {"record": "event", "event_id": f"a-{seq}", "trace_id": tid,
         "parent_id": f"f-{seq}", "name": "requeue", "kind": "scheduler",
         "ts": base_ts + 1.0, "attrs": {"submission_id": f"sub-{seq}"}},
        {"record": "event", "event_id": f"r-{seq}", "trace_id": tid,
         "parent_id": f"a-{seq}", "name": "resume", "kind": "supervisor",
         "ts": base_ts + 2.0, "attrs": {}},
    ]


def test_every_fault_kind_stitches_into_one_resolved_incident():
    corr = IncidentCorrelator(clock=_forbidden_clock, stale_after_s=1e9)
    kinds = [k.value for k in FaultKind]
    records = []
    for seq, kind in enumerate(kinds):
        records.extend(_chain_records(kind, seq, seq * 100.0, seq))
    assert corr.ingest(records=records, now=len(kinds) * 100.0) == 3 * len(kinds)
    st = corr.stats()
    assert st["opened_by_trigger"] == {"fault": len(kinds)}
    assert st["resolved_total"] == len(kinds)
    assert st["open"] == 0 and st["ignored_total"] == 0
    incs = corr.incidents(limit=0)
    assert len(incs) == len(kinds)
    by_name = {i["timeline"][0]["name"]: i for i in incs}
    assert set(by_name) == set(kinds)
    for seq, kind in enumerate(kinds):
        inc = by_name[kind]
        assert inc["state"] == "resolved"
        assert [e["role"] for e in inc["timeline"]] == (
            ["detect", "action", "resolution"]
        )
        assert inc["device_index"] == seq
        assert inc["submission_id"] == f"sub-{seq}"
        assert inc["duration_s"] == pytest.approx(2.0)


def test_detect_double_record_merges_span_and_event():
    """The live path records a fault twice — a detect span and the
    FaultEvent mirror at the same instant, same device. One incident."""
    corr = IncidentCorrelator(clock=_forbidden_clock, stale_after_s=1e9)
    records = [
        {"record": "span", "span_id": "s1", "trace_id": "t", "parent_id": None,
         "name": "chip-unhealthy", "kind": "fault", "t0": 100.0, "t1": 100.1,
         "attrs": {"device": 3}},
        {"record": "event", "event_id": "e1", "trace_id": "t",
         "parent_id": None, "name": "chip-unhealthy", "kind": "fault",
         "ts": 100.05, "attrs": {"device": 3}},
    ]
    corr.ingest(records=records, now=101.0)
    assert corr.stats()["opened_by_trigger"] == {"fault": 1}
    assert len(corr.incidents(limit=0)) == 1


def test_slo_alert_escalations_merge_and_resolve():
    corr = IncidentCorrelator(clock=_forbidden_clock, stale_after_s=1e9)
    def alert(eid, ts, transition):
        return {"record": "event", "event_id": eid, "trace_id": "t",
                "parent_id": None, "name": "slo_burn", "kind": "slo_alert",
                "ts": ts, "attrs": {"slo": "goodput",
                                    "transition": transition}}
    corr.ingest(
        records=[alert("a", 0.0, "page"), alert("b", 30.0, "escalate"),
                 alert("c", 60.0, "resolve")],
        now=61.0,
    )
    st = corr.stats()
    assert st["opened_by_trigger"] == {"slo_alert": 1}
    assert st["resolved_total"] == 1
    (inc,) = corr.incidents(limit=0)
    assert inc["slo"] == "goodput" and inc["state"] == "resolved"
    assert len(inc["timeline"]) == 3


def test_ingest_is_idempotent_and_stale_incidents_expire():
    corr = IncidentCorrelator(clock=_forbidden_clock, stale_after_s=900.0)
    records = _chain_records("host-slow", 1, 0.0, 0)[:2]  # no resolution
    corr.ingest(records=records, now=10.0)
    corr.ingest(records=records, now=10.0)  # dedup by record id
    st = corr.stats()
    assert st["opened_by_trigger"] == {"fault": 1}
    assert st["correlated_total"] == 2
    (inc,) = corr.incidents(limit=0)
    assert inc["state"] == "mitigating"
    # Idle past stale_after_s: moved to unresolved, no longer open.
    corr.ingest(records=[], now=2000.0)
    (inc,) = corr.incidents(limit=0)
    assert inc["state"] == "unresolved"
    assert corr.stats()["open"] == 0


def test_incident_metric_snippets_come_from_the_historian():
    hist = MetricHistorian(clock=_forbidden_clock)
    _fill(hist, "step_time_s", [(float(t), 0.1) for t in range(20)])
    corr = IncidentCorrelator(clock=_forbidden_clock, stale_after_s=1e9)
    corr.ingest(records=_chain_records("chip-unhealthy", 0, 5.0, 0), now=10.0)
    (inc,) = corr.incidents(
        limit=0, historian=hist, snippet_series=["step_time_s"]
    )
    snip = inc["metric_snippets"]["step_time_s"]
    assert snip["aggregates"]["count"] == 20  # 60s pad covers all samples
    assert snip["points"]


# ---------------------------------------------------------------------------
# Chaos replay fidelity gate (the twin lane)
# ---------------------------------------------------------------------------


def test_historian_chaos_replay_lane_gates():
    from tpu_engine.twin import historian_lane

    lane = historian_lane(seed=0)
    assert lane["ok"], lane["gates"]
    assert lane["max_series_error_pct"] < 1.0
    assert lane["gates"]["every_fault_one_incident"]
    assert lane["gates"]["causal_chains"]
    assert lane["gates"]["replay_incidents_match"]
    assert lane["fault_incidents"] > 0
    assert lane["resolved_incidents"] >= lane["fault_incidents"]
