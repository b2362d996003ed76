"""Heterogeneity plane: apportionment invariants, tracker EMA semantics,
and the hysteresis guards of the rebalance policy loop (PR 11).

The one invariant that must never bend: a row assignment always sums to
the declared global micro batch exactly — property-tested over random
throughputs, floors, and caps, with :class:`InfeasibleAssignment` raised
(never a silently resized batch) when the constraints cannot be met.
"""

import random
from types import SimpleNamespace

import pytest

from tpu_engine.hetero import (
    MIN_RELATIVE_THROUGHPUT,
    HeteroRebalancer,
    InfeasibleAssignment,
    ThroughputTracker,
    broadcast_agree_fn,
    clear_active,
    get_active,
    hbm_max_rows_fn,
    predicted_goodput,
    set_active,
    solve_row_assignment,
    uniform_assignment,
)
from tpu_engine.tracing import FlightRecorder


# -- apportionment ------------------------------------------------------------


def test_uniform_assignment_spreads_remainder():
    assert uniform_assignment(8, 2) == [4, 4]
    assert uniform_assignment(9, 2) == [5, 4]
    assert uniform_assignment(10, 4) == [3, 3, 2, 2]
    with pytest.raises(ValueError, match="at least one process"):
        uniform_assignment(8, 0)


def test_solver_uniform_rates_gives_uniform_split():
    assert solve_row_assignment([1.0] * 4, 16) == [4, 4, 4, 4]
    assert solve_row_assignment([1.0, 1.0], 9) == [5, 4]


def test_solver_shifts_rows_off_the_slow_process():
    rows = solve_row_assignment([1.0, 1.0, 1.0, 0.5], 16)
    assert sum(rows) == 16
    assert rows[3] < min(rows[:3])
    # And the weighted split predicts strictly better goodput.
    tput = [1.0, 1.0, 1.0, 0.5]
    assert predicted_goodput(rows, tput) > predicted_goodput(
        uniform_assignment(16, 4), tput
    )


def test_solver_exact_sum_property():
    """Sum preservation over random gangs — the invariant the data plane
    relies on (a wrong sum drops or double-reads rows every step)."""
    rng = random.Random(0)
    for trial in range(300):
        n = rng.randint(1, 16)
        min_rows = rng.randint(1, 3)
        total = rng.randint(n * min_rows, n * min_rows + 512)
        tput = [rng.uniform(0.01, 2.0) for _ in range(n)]
        caps = None
        if rng.random() < 0.5:
            # Feasible caps: at least the floor, summing to >= total.
            caps = [
                None if rng.random() < 0.3
                else rng.randint(min_rows, max(min_rows, total))
                for _ in range(n)
            ]
            short = total - sum(c if c is not None else total for c in caps)
            if short > 0:
                caps[0] = (caps[0] or 0) + short
        rows = solve_row_assignment(
            tput, total, min_rows=min_rows, max_rows=caps
        )
        assert sum(rows) == total, (trial, tput, total)
        assert all(r >= min_rows for r in rows), (trial, rows)
        if caps is not None:
            assert all(
                c is None or r <= c for r, c in zip(rows, caps)
            ), (trial, rows, caps)


def test_solver_deterministic():
    tput = [1.0, 0.7, 0.9, 0.7]
    a = solve_row_assignment(tput, 37)
    assert a == solve_row_assignment(list(tput), 37)
    assert sum(a) == 37


def test_solver_infeasible_raises_not_resizes():
    with pytest.raises(InfeasibleAssignment, match="floor"):
        solve_row_assignment([1.0, 1.0], 1, min_rows=1)
    with pytest.raises(InfeasibleAssignment, match="cap below"):
        solve_row_assignment([1.0, 1.0], 8, min_rows=2, max_rows=[1, 8])
    with pytest.raises(InfeasibleAssignment, match="sum to"):
        solve_row_assignment([1.0, 1.0], 8, max_rows=[3, 3])
    with pytest.raises(ValueError, match="non-empty"):
        solve_row_assignment([], 8)


def test_solver_floors_near_zero_throughput():
    # A ~dead process is clamped to MIN_RELATIVE_THROUGHPUT, never starved
    # below the floor and never a division by zero.
    rows = solve_row_assignment([1.0, 0.0], 8)
    assert sum(rows) == 8 and rows[1] >= 1


def test_predicted_goodput():
    assert predicted_goodput([4, 4], [1.0, 1.0]) == pytest.approx(1.0)
    # Uniform split on a 2x-slow host: step gated at 4/0.5 = 8 row-times,
    # ideal is 8/1.5 = 5.33 -> 2/3.
    assert predicted_goodput([4, 4], [1.0, 0.5]) == pytest.approx(2 / 3)
    assert predicted_goodput([], []) == 0.0


# -- HBM row caps -------------------------------------------------------------


class _Cfg:
    def __init__(self, micro):
        self.micro_batch_size = micro

    def model_copy(self, update):
        c = _Cfg(self.micro_batch_size)
        for k, v in update.items():
            setattr(c, k, v)
        return c


def _linear_estimate(cfg):
    # 1 GiB per effective micro-batch row: monotone, easy to reason about.
    return SimpleNamespace(device_total_gib=float(cfg.micro_batch_size))


def test_hbm_max_rows_binary_search():
    cfg = _Cfg(micro=2)
    # budget 2 GiB -> eff micro <= 2 -> rows <= 4 of a 4-row uniform share.
    fn = hbm_max_rows_fn(
        cfg, 2, 2.0, estimate_fn=_linear_estimate, margin_frac=0.0
    )
    assert fn(0, 4) == 4
    # Generous budget: the hi probe fits outright.
    fn = hbm_max_rows_fn(
        cfg, 2, 100.0, estimate_fn=_linear_estimate, margin_frac=0.0
    )
    assert fn(0, 4) == 8  # rows_uniform * n_processes


def test_hbm_max_rows_unpriceable_returns_none():
    cfg = _Cfg(micro=2)
    # Even one row over budget: "no cap known", not an impossible 0.
    fn = hbm_max_rows_fn(
        cfg, 2, 0.25, estimate_fn=_linear_estimate, margin_frac=0.0
    )
    assert fn(0, 4) is None

    def boom(cfg):
        raise RuntimeError("no estimator for this model")

    fn = hbm_max_rows_fn(cfg, 2, 8.0, estimate_fn=boom, margin_frac=0.0)
    assert fn(0, 4) is None
    # micro=0 (unknown config) short-circuits too.
    fn = hbm_max_rows_fn(
        _Cfg(micro=0), 2, 8.0, estimate_fn=_linear_estimate, margin_frac=0.0
    )
    assert fn(0, 4) is None


# -- throughput tracker -------------------------------------------------------


def test_tracker_starts_uniform():
    trk = ThroughputTracker(4)
    assert trk.relative_throughput() == [1.0] * 4
    assert trk.imbalance() == pytest.approx(1.0)


def test_tracker_host_slow_pulls_estimate_down():
    trk = ThroughputTracker(4, alpha=0.25)
    # Penalty equal to the baseline: the host ran at 1/2 speed.
    trk.note_host_slow(2, 1.0, 1.0)
    rel = trk.relative_throughput()
    assert rel[2] == pytest.approx(0.875)  # one EMA step toward 0.5
    assert rel[0] == rel[1] == rel[3] == 1.0
    for _ in range(30):
        trk.note_host_slow(2, 1.0, 1.0)
    assert trk.relative_throughput()[2] == pytest.approx(0.5, abs=0.01)
    assert trk.imbalance() == pytest.approx(2.0, abs=0.05)
    assert trk.slow_signals_total == 31


def test_tracker_decays_back_to_healthy_when_quiet():
    trk = ThroughputTracker(2, alpha=0.25, decay=0.02)
    for _ in range(30):
        trk.note_host_slow(1, 1.0, 1.0)
    # A reinforced estimate does not decay on the step that reinforced it.
    trk.note_host_slow(1, 1.0, 1.0)
    held = trk.relative_throughput()[1]
    trk.observe_step(1.0)
    assert trk.relative_throughput()[1] == pytest.approx(held)
    # Quiet steps relax it back toward 1.0 (transient stalls heal).
    for _ in range(200):
        trk.observe_step(1.0)
    assert trk.relative_throughput()[1] > 0.9


def test_tracker_attribution_seeding_filters():
    trk = ThroughputTracker(3, alpha=0.25)
    # Wrong cause / unsustained / implausible durations: all ignored.
    trk.note_attribution("ici-degraded", {"sustained": True, "duration_s": 2.0, "baseline_s": 1.0}, 1)
    trk.note_attribution("host-slow", {"sustained": False, "duration_s": 2.0, "baseline_s": 1.0}, 1)
    trk.note_attribution("host-slow", {"sustained": True, "duration_s": 0.5, "baseline_s": 1.0}, 1)
    assert trk.relative_throughput() == [1.0, 1.0, 1.0]
    assert trk.attribution_seeds_total == 0
    # A sustained host-slow attribution seeds base/dur.
    trk.note_attribution("host-slow", {"sustained": True, "duration_s": 2.0, "baseline_s": 1.0}, 1)
    assert trk.relative_throughput()[1] == pytest.approx(0.875)
    assert trk.attribution_seeds_total == 1


def test_tracker_baseline_and_index_clamp():
    trk = ThroughputTracker(2)
    trk.observe_step(2.0)
    trk.observe_step(1.0)  # new minimum wins outright
    assert trk.baseline_s() == pytest.approx(1.0)
    trk.observe_step(2.0)  # slower steps drift the baseline up gently
    assert trk.baseline_s() == pytest.approx(0.98 * 1.0 + 0.02 * 2.0)
    # Out-of-range process indices clamp instead of raising mid-step-loop.
    trk.note_host_slow(99, 1.0, 1.0)
    assert trk.relative_throughput()[1] < 1.0
    trk.note_host_slow(-5, 1.0, 1.0)
    assert trk.relative_throughput()[0] < 1.0
    with pytest.raises(ValueError, match="positive"):
        ThroughputTracker(0)


# -- rebalance policy ---------------------------------------------------------


def _slow_tracker(n=2, slow=1, signals=30):
    trk = ThroughputTracker(n)
    for _ in range(signals):
        trk.note_host_slow(slow, 1.0, 1.0)  # -> ~0.5 relative
    return trk


def test_rebalancer_balanced_gang_never_moves():
    t = [0.0]
    reb = HeteroRebalancer(
        ThroughputTracker(4), 16, sustain_consults=1, clock=lambda: t[0],
        recorder=FlightRecorder(clock=lambda: t[0]),
    )
    for step in range(5):
        assert reb.maybe_rebalance(step) is None
    assert reb.skips["balanced"] == 5
    assert reb.assignment == [4, 4, 4, 4]


def test_rebalancer_sustain_then_dry_run_then_live():
    t = [0.0]
    rec = FlightRecorder(clock=lambda: t[0])
    reb = HeteroRebalancer(
        _slow_tracker(), 8, sustain_consults=2, min_gain=0.01,
        cooldown_s=60.0, dry_run=True, clock=lambda: t[0], recorder=rec,
        trace_id="t-hetero",
    )
    # First consult proposing a change is held for sustain.
    assert reb.maybe_rebalance(10) is None
    assert reb.skips["sustain"] == 1
    # Second consecutive proposal fires — but dry-run leaves the gang alone.
    t[0] = 5.0
    plan = reb.maybe_rebalance(20)
    assert plan is not None and plan.dry_run
    assert sum(plan.assignment) == 8
    assert plan.assignment[1] < plan.assignment[0]
    assert plan.goodput_after > plan.goodput_before
    assert reb.assignment == [4, 4]  # unchanged
    assert reb.dry_runs_total == 1 and reb.rebalances_total == 0
    audits = [e for e in rec.events(kind="hetero") if e["name"] == "hetero_rebalance"]
    assert len(audits) == 1
    assert audits[0]["trace_id"] == "t-hetero"
    assert audits[0]["attrs"]["dry_run"] is True

    # Live mode applies the plan (fresh rebalancer, same tracker state).
    live = HeteroRebalancer(
        _slow_tracker(), 8, sustain_consults=1, min_gain=0.01,
        dry_run=False, clock=lambda: t[0], recorder=rec,
    )
    plan = live.maybe_rebalance(30)
    assert plan is not None and not plan.dry_run
    assert live.assignment == plan.assignment
    assert sum(live.assignment) == 8
    assert live.rebalances_total == 1


def test_rebalancer_cooldown_bounds_rebalance_rate():
    t = [0.0]
    trk = _slow_tracker()
    reb = HeteroRebalancer(
        trk, 8, sustain_consults=1, min_gain=0.01, cooldown_s=100.0,
        dry_run=False, clock=lambda: t[0],
        recorder=FlightRecorder(clock=lambda: t[0]),
    )
    assert reb.maybe_rebalance(1) is not None
    # Degrade further: the solver proposes yet another split...
    for _ in range(40):
        trk.note_host_slow(1, 4.0, 1.0)  # -> ~0.2 relative
    assert reb.maybe_rebalance(2) is None  # ...but cooldown holds it
    assert reb.skips["cooldown"] == 1
    t[0] = 200.0  # past the window: now it may act again
    assert reb.maybe_rebalance(3) is not None
    assert reb.rebalances_total == 2
    assert sum(reb.assignment) == 8


def test_rebalancer_gain_floor_skip_is_audited():
    t = [0.0]
    rec = FlightRecorder(clock=lambda: t[0])
    reb = HeteroRebalancer(
        _slow_tracker(), 8, sustain_consults=1, min_gain=0.5,
        imbalance_trigger=1.01, dry_run=False, clock=lambda: t[0],
        recorder=rec,
    )
    assert reb.maybe_rebalance(1) is None
    assert reb.skips["gain"] == 1
    assert reb.assignment == [4, 4]
    skips = [e for e in rec.events(kind="hetero") if e["name"] == "hetero_rebalance_skip"]
    assert skips and skips[-1]["attrs"]["reason"] == "gain-below-floor"


def test_rebalancer_hbm_infeasible_skips_and_audits():
    t = [0.0]
    rec = FlightRecorder(clock=lambda: t[0])
    reb = HeteroRebalancer(
        _slow_tracker(), 8, sustain_consults=1, min_gain=0.01,
        dry_run=False, clock=lambda: t[0], recorder=rec,
        max_rows_fn=lambda i, rows_u: 3,  # caps sum to 6 < 8: infeasible
    )
    assert reb.maybe_rebalance(1) is None
    assert reb.skips["hbm"] == 1
    assert reb.assignment == [4, 4]
    skips = [e for e in rec.events(kind="hetero") if e["name"] == "hetero_rebalance_skip"]
    assert skips and skips[-1]["attrs"]["reason"] == "hbm-infeasible"


def test_rebalancer_hbm_caps_shape_the_plan():
    t = [0.0]
    reb = HeteroRebalancer(
        _slow_tracker(n=4, slow=3), 16, sustain_consults=1, min_gain=0.01,
        dry_run=False, clock=lambda: t[0],
        recorder=FlightRecorder(clock=lambda: t[0]),
        max_rows_fn=lambda i, rows_u: 5,  # no host may exceed 5 rows
    )
    plan = reb.maybe_rebalance(1)
    assert plan is not None
    assert sum(plan.assignment) == 16
    assert max(plan.assignment) <= 5
    assert plan.hbm_capped == [0, 1, 2, 3]


def test_recovered_goodput_fraction():
    t = [0.0]
    reb = HeteroRebalancer(
        _slow_tracker(), 8, sustain_consults=1, min_gain=0.01,
        dry_run=False, clock=lambda: t[0],
        recorder=FlightRecorder(clock=lambda: t[0]),
    )
    assert reb.recovered_goodput_fraction() == 0.0  # still uniform
    assert reb.maybe_rebalance(1) is not None
    assert reb.recovered_goodput_fraction() > 0.1
    st = reb.stats()
    assert st["assignment"] == reb.assignment
    assert st["last_plan"]["step"] == 1
    assert st["tracker"]["n_processes"] == 2


def test_consult_request_is_served_and_cleared_by_any_consult():
    t = [0.0]
    reb = HeteroRebalancer(
        ThroughputTracker(2), 8, sustain_consults=1, clock=lambda: t[0],
        recorder=FlightRecorder(clock=lambda: t[0]),
    )
    assert not reb.consult_pending()
    reb.request_consult()
    assert reb.consult_pending()
    # A balanced gang declines the consult, but the request is still served.
    assert reb.maybe_rebalance(1) is None
    assert not reb.consult_pending()
    assert reb.stats()["consult_requested"] is False


def test_step_based_cooldown_ignores_wall_clock():
    t = [0.0]
    trk = _slow_tracker()
    reb = HeteroRebalancer(
        trk, 8, sustain_consults=1, min_gain=0.01, cooldown_s=0.0,
        cooldown_steps=10, dry_run=False, clock=lambda: t[0],
        recorder=FlightRecorder(clock=lambda: t[0]),
    )
    assert reb.maybe_rebalance(1) is not None
    for _ in range(40):
        trk.note_host_slow(1, 4.0, 1.0)  # degrade further -> new proposal
    # Clock skew must not let one rank act while its peers hold: with
    # cooldown_steps set, an enormous wall-clock jump changes nothing.
    t[0] = 1e6
    assert reb.maybe_rebalance(5) is None
    assert reb.skips["cooldown"] == 1
    assert reb.maybe_rebalance(11) is not None
    assert reb.rebalances_total == 2
    assert reb.stats()["last_rebalance_step"] == 11


def test_agree_fn_aligns_ranks_with_divergent_local_estimates():
    """Two ranks whose local trackers disagree still derive the identical
    plan when both solve from the broadcast (agreed) estimates."""
    t = [0.0]
    agreed = [1.0, 0.5]
    plans = []
    # Rank A saw the slowdown locally; rank B's local tracker is uniform
    # (it would have skipped as "balanced" without the agreement hook).
    for local in (_slow_tracker(), ThroughputTracker(2)):
        reb = HeteroRebalancer(
            local, 8, sustain_consults=1, min_gain=0.01, dry_run=False,
            agree_fn=lambda tput: list(agreed), clock=lambda: t[0],
            recorder=FlightRecorder(clock=lambda: t[0]),
        )
        plans.append(reb.maybe_rebalance(1))
    assert plans[0] is not None and plans[1] is not None
    assert plans[0].assignment == plans[1].assignment
    assert plans[0].throughputs == plans[1].throughputs == agreed


def test_broadcast_agree_fn_is_identity_on_single_process():
    agree = broadcast_agree_fn()
    assert agree([1.0, 0.5, 0.25]) == [1.0, 0.5, 0.25]


def test_revert_restores_assignment_and_audits():
    t = [0.0]
    rec = FlightRecorder(clock=lambda: t[0])
    reb = HeteroRebalancer(
        _slow_tracker(), 8, sustain_consults=1, min_gain=0.01,
        dry_run=False, clock=lambda: t[0], recorder=rec,
    )
    plan = reb.maybe_rebalance(1)
    assert plan is not None and reb.assignment == plan.assignment
    # The data layer refused the windows: the gauge must not keep
    # reporting a split that is not actually feeding the mesh.
    reb.revert(plan)
    assert reb.assignment == plan.previous == [4, 4]
    assert reb.reverts_total == 1
    assert reb.recovered_goodput_fraction() == 0.0
    names = [e["name"] for e in rec.events(kind="hetero")]
    assert "hetero_rebalance_reverted" in names

    # Dry-run plans never moved anything — revert is a no-op.
    dry = HeteroRebalancer(
        _slow_tracker(), 8, sustain_consults=1, min_gain=0.01,
        dry_run=True, clock=lambda: t[0], recorder=rec,
    )
    p2 = dry.maybe_rebalance(1)
    assert p2 is not None and p2.dry_run
    dry.revert(p2)
    assert dry.reverts_total == 0


def test_active_singleton():
    t = [0.0]
    reb = HeteroRebalancer(
        ThroughputTracker(2), 8, clock=lambda: t[0],
        recorder=FlightRecorder(clock=lambda: t[0]),
    )
    prev = get_active()  # tolerate leakage from earlier suite members
    try:
        set_active(reb)
        assert get_active() is reb
        clear_active()
        assert get_active() is None
    finally:
        set_active(prev)


def test_hetero_lane_rebalance_beats_uniform_and_shrink():
    """The slow-host lane (virtual clock, one of 8 hosts 25% slower by the
    seeded plan): rebalanced rows keep the gang near the heterogeneous
    ideal, a uniform gang is gated by the slow host, and shrinking throws
    away the host's remaining three quarters."""
    from benchmarks.chaos import HET_GLOBAL_MICRO, run_hetero_lane

    het = run_hetero_lane(seed=0)
    assert het["steady_goodput_on"] >= 0.90
    assert het["steady_goodput_off"] <= 0.80
    assert het["steady_goodput_on"] > het["steady_goodput_shrink"]
    assert sum(het["rebalance_on"]["assignment"]) == HET_GLOBAL_MICRO
    assert het == run_hetero_lane(seed=0)  # seeded: the same on a repeat
