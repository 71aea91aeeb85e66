"""Drift control plane invariants: detector determinism and scoring
semantics, RefreshPolicy("never") bit-identical to the PR-4 serving path,
write-barrier re-ANALYZE semantics (stage cache stays correct, catalog
catches up, gate caches are fenced), coverage probes shifting toward
drifted templates, and predictor refit generation fencing.

All scenarios come from tests/scenarios.py; the `agent` fixture is the
session-scoped one from conftest.py.
"""
import numpy as np
import pytest

from scenarios import (drifting_delta_stream, fast_query, fresh_db,
                       make_agent, mi_join_query, straggler_query)

from repro.learn import PolicyStore, ReplayBuffer, TrajectoryHarvester
from repro.serve.cache import StageCache
from repro.serve.deltas import DeltaBatch, apply_delta
from repro.serve.drift import (CoverageProbeSet, DriftController,
                               DriftDetector, RefreshPolicy, TableDrift)
from repro.serve.qos import LatencyPredictor, encode_query
from repro.serve.scheduler import Arrival, LaneScheduler
from repro.serve.service import QueryService
from repro.sql.catalog import analyze, analyze_table
from repro.sql.cbo import Estimator
from repro.sql.executor import run_adaptive
from repro.sql.plans import syntactic_plan


def _drift(table, score, lag=1):
    return TableDrift(table, lag, 1.0, 0.0, 0.0, score)


# --------------------------------------------------------------- detector
def test_detector_zero_lag_scores_zero():
    """A table whose data never changed is NOT stale, however bad the
    execution evidence on it looks — regret there is a policy problem."""
    db = fresh_db(scale=0.05)
    det = DriftDetector()
    det.snapshot(db)
    for _ in range(8):
        det.observe(("title",), regret=3.0, pred_err=2.0)
    d = det.score_table(db, "title")
    assert d.version_lag == 0 and d.score == 0.0
    assert d.regret > 0 and d.pred_err > 0        # evidence is recorded


def test_detector_lag_rows_and_evidence_compose():
    db = fresh_db(scale=0.05)
    det = DriftDetector()
    det.snapshot(db)
    apply_delta(db, DeltaBatch("movie_info", n_append=5000, seed=1))
    base = det.score_table(db, "movie_info")
    assert base.version_lag == 1 and base.rows_ratio > 1.0 and base.score > 0
    # execution evidence AMPLIFIES catalog lag, never replaces it
    det.observe(("movie_info",), regret=2.0, pred_err=1.0)
    amped = det.score_table(db, "movie_info")
    assert amped.score > base.score
    # a second delta raises the lag term
    apply_delta(db, DeltaBatch("movie_info", n_append=100, seed=2))
    assert det.score_table(db, "movie_info").version_lag == 2
    # refresh: lag returns to zero, evidence windows restart
    det.note_refreshed("movie_info", db.table_version("movie_info"))
    d = det.score_table(db, "movie_info")
    assert d.version_lag == 0 and d.score == 0.0 and d.regret == 0.0


def test_detector_sees_staleness_predating_attach():
    """analyze() stamps the data versions its statistics were taken at,
    so a delta that lands BETWEEN analyze and controller attachment still
    counts as catalog lag — stale-at-attach tables are not invisible."""
    from repro.sql.catalog import analyze
    db = fresh_db(scale=0.05)
    db.stats = analyze(db, rng=np.random.default_rng(3))
    assert db.stats.versions["movie_info"] == 0
    apply_delta(db, DeltaBatch("movie_info", n_append=2000, seed=1))
    det = DriftDetector()
    det.snapshot(db)                     # attach AFTER the delta
    d = det.score_table(db, "movie_info")
    assert d.version_lag == 1 and d.score > 0
    # a re-ANALYZE re-stamps: a fresh snapshot is back in sync
    db.stats = analyze(db, rng=np.random.default_rng(4))
    det2 = DriftDetector()
    det2.snapshot(db)
    assert det2.score_table(db, "movie_info").version_lag == 0


def test_detector_deterministic_across_identical_runs(job_workload, agent):
    """Same seed => identical scores, refresh decisions, refresh times and
    controller counters across two full serving runs."""
    def run():
        db = fresh_db(scale=0.05)
        rb = ReplayBuffer()
        ctl = DriftController(policy=RefreshPolicy("threshold",
                                                   threshold=0.5),
                              replay=rb)
        svc = QueryService(db, agent, est=Estimator(db, db.stats),
                           n_lanes=2, hooks=[TrajectoryHarvester(rb), ctl])
        stream = drifting_delta_stream(
            [fast_query(i) for i in range(4)], n_queries=10, seed=21,
            drift_table="movie_info", drift_at=4, growth_rows=4000,
            churn_table="movie_keyword", churn_every=3, churn_rows=200)
        comps, _ = svc.run(stream)
        scores = {t: (d.version_lag, d.score)
                  for t, d in ctl.scores().items()}
        summary = ctl.summary()
        for k in ("analyze_wall_s", "host_seconds"):   # host wall time
            summary.pop(k)
        return ([(c.seq, c.finish_t, tuple(c.traj.actions)) for c in comps],
                svc.scheduler.task_log, ctl.refresh_log, scores, summary)

    assert run() == run()


# ------------------------------------------------- never == the PR-4 path
def test_refresh_never_bit_identical_to_no_controller(job_workload, agent):
    """The full control plane attached with RefreshPolicy("never") (and no
    refit/probe actuators) must serve completion-bit-identically to a run
    with no controller at all — detection is free, actuation is opt-in."""
    stream = drifting_delta_stream(
        [fast_query(i) for i in range(4)], n_queries=12, seed=33,
        drift_table="movie_info", drift_at=5, growth_rows=4000)

    def serve(with_controller):
        db = fresh_db(scale=0.05)
        hooks = []
        if with_controller:
            rb = ReplayBuffer()
            hooks = [TrajectoryHarvester(rb),
                     DriftController(policy=RefreshPolicy("never"),
                                     replay=rb)]
        svc = QueryService(db, agent, est=Estimator(db, db.stats),
                           n_lanes=2, hooks=hooks)
        comps, _ = svc.run(stream)
        return comps, svc

    plain, _ = serve(False)
    gated, svc = serve(True)
    assert svc.scheduler.task_log == []           # never scheduled a task
    assert [c.seq for c in plain] == [c.seq for c in gated]
    assert [c.admit_t for c in plain] == [c.admit_t for c in gated]
    assert [c.finish_t for c in plain] == [c.finish_t for c in gated]
    assert [c.lane for c in plain] == [c.lane for c in gated]
    assert [c.traj.actions for c in plain] == \
        [c.traj.actions for c in gated]
    np.testing.assert_array_equal(
        np.concatenate([c.traj.logps for c in plain]),
        np.concatenate([c.traj.logps for c in gated]))


# ----------------------------------------------------- re-ANALYZE barrier
def test_reanalyze_is_write_barrier_and_catalog_catches_up(job_workload,
                                                           agent):
    """A threshold refresh runs as a write-barrier task: it lands after
    every previously admitted query drains, later queries admit at or
    after it, and the catalog's row counts equal the live table's."""
    db = fresh_db(scale=0.05)
    rb = ReplayBuffer()
    ctl = DriftController(policy=RefreshPolicy("threshold", threshold=0.5),
                          replay=rb)
    svc = QueryService(db, agent, est=Estimator(db, db.stats), n_lanes=2,
                       hooks=[TrajectoryHarvester(rb), ctl])
    stream = drifting_delta_stream(
        [fast_query(i) for i in range(4)], n_queries=10, seed=5,
        drift_table="movie_info", drift_at=4, growth_rows=5000)
    stale_rows = db.stats.tables["movie_info"].nrows
    comps, _ = svc.run(stream)
    assert len(svc.scheduler.task_log) >= 1
    t_task, label = svc.scheduler.task_log[0]
    assert label.startswith("re-analyze:") and "movie_info" in label
    # barrier semantics on the virtual clock
    before = [c for c in comps if c.admit_t < t_task]
    after = [c for c in comps if c.admit_t >= t_task]
    assert before and after
    assert all(c.finish_t <= t_task for c in before)
    # the catalog caught up: believed rows == live rows != stale snapshot
    live = db.table("movie_info").nrows
    assert db.stats.tables["movie_info"].nrows == live != stale_rows
    assert svc.est.stats.tables["movie_info"].nrows == live
    # the explicit cost charge is recorded (modeled deterministic + wall)
    assert ctl.stats.analyze_modeled_s > 0
    assert ctl.stats.refresh_events >= 1
    assert ctl.stats.tables_refreshed >= 1
    # and the detector no longer flags the refreshed table
    assert ctl.scores()["movie_info"].version_lag == 0


def test_barrier_task_charge_delays_later_admissions(job_workload, agent):
    """A barrier task's returned virtual charge is a foreground
    maintenance window: the task drains in-flight queries, applies at
    their last finish, and queries admitted afterwards are floored by
    apply + charge."""
    def serve(dt):
        db = fresh_db(scale=0.05)
        sched = LaneScheduler(db, Estimator(db, db.stats), agent,
                              n_lanes=1, policy="async")
        ran = []

        def hook(comp):
            if comp.seq == 0:
                sched.schedule_barrier(
                    lambda s, t_apply: ran.append(t_apply) or dt,
                    label="window")
        sched.on_complete.append(hook)
        comps = sched.run([Arrival(0.0, query=fast_query(0), seed=1),
                           Arrival(0.1, query=fast_query(1), seed=2)])
        return comps, sched.task_log, ran

    free, log_free, ran_free = serve(0.0)
    paid, log_paid, ran_paid = serve(5.0)
    # the task applied once q0 drained, at the same instant in both runs
    assert ran_free == ran_paid == [free[0].finish_t]
    assert log_free == [(free[0].finish_t, "window")]
    assert log_paid == [(paid[0].finish_t + 5.0, "window")]
    # q1 (already arrived at t=0.1) waits out the whole charged window
    assert free[1].admit_t == free[0].finish_t
    assert paid[1].admit_t == paid[0].finish_t + 5.0
    assert paid[0].admit_t == free[0].admit_t     # pre-task query untouched


def test_delta_behind_charged_window_does_not_rewind_write_floor(
        job_workload, agent):
    """A delta arriving inside a charged maintenance window applies at
    the window's END: the write floor is monotone, and queries behind the
    delta admit after both barriers."""
    db = fresh_db(scale=0.05)
    sched = LaneScheduler(db, Estimator(db, db.stats), agent, n_lanes=1,
                          policy="async")

    def hook(comp):
        if comp.seq == 0:
            sched.schedule_barrier(lambda s, t: 5.0, label="window")
    sched.on_complete.append(hook)
    comps = sched.run([
        Arrival(0.0, query=fast_query(0), seed=1),
        Arrival(0.1, delta=DeltaBatch("movie_info", n_append=500, seed=2)),
        Arrival(0.2, query=fast_query(1), seed=3)])
    t_window_end = sched.task_log[0][0]
    assert t_window_end == comps[0].finish_t + 5.0
    t_delta = sched.delta_log[0][0]
    assert t_delta >= t_window_end, "delta must not rewind the floor"
    assert comps[1].admit_t >= t_delta


def test_reanalyze_charge_virtual_shifts_barrier_end(job_workload, agent):
    """charge_virtual=True prices the controller's re-ANALYZE onto the
    virtual clock: same refresh decisions and modeled cost, but the
    barrier end (the floor for later admissions) moves out by exactly the
    modeled analyze seconds; no admission ever gets EARLIER."""
    stream = drifting_delta_stream(
        [fast_query(i) for i in range(4)], n_queries=10, seed=5,
        drift_table="movie_info", drift_at=4, growth_rows=5000)

    def serve(charge):
        db = fresh_db(scale=0.05)
        rb = ReplayBuffer()
        ctl = DriftController(policy=RefreshPolicy("threshold",
                                                   threshold=0.5),
                              replay=rb, charge_virtual=charge)
        svc = QueryService(db, agent, est=Estimator(db, db.stats),
                           n_lanes=2, hooks=[TrajectoryHarvester(rb), ctl])
        comps, _ = svc.run(stream)
        return comps, svc.scheduler.task_log, ctl

    free, log_free, ctl_free = serve(False)
    paid, log_paid, ctl_paid = serve(True)
    assert len(log_free) == len(log_paid) >= 1
    assert ctl_paid.stats.analyze_modeled_s == ctl_free.stats.analyze_modeled_s
    dt = ctl_paid.stats.analyze_modeled_s       # unrounded, single event
    assert dt > 0
    assert log_paid[0][0] == pytest.approx(log_free[0][0] + dt)
    for a, b in zip(free, paid):
        assert b.admit_t >= a.admit_t - 1e-12   # charging never speeds up


def test_delta_triggered_refresh_lands_at_the_same_barrier(job_workload,
                                                           agent):
    """Catalog lag is born at the delta — and the delta barrier already
    drained every lane. The controller decides there (on_delta), so the
    re-ANALYZE task applies at the delta's own apply time: no extra drain
    stall, and the FIRST post-delta query already plans on fresh stats."""
    db = fresh_db(scale=0.05)
    rb = ReplayBuffer()
    ctl = DriftController(policy=RefreshPolicy("always"), replay=rb)
    svc = QueryService(db, agent, est=Estimator(db, db.stats), n_lanes=2,
                       hooks=[TrajectoryHarvester(rb), ctl])
    stream = drifting_delta_stream(
        [fast_query(i) for i in range(4)], n_queries=8, seed=11,
        drift_table="movie_info", drift_at=3, growth_rows=3000)
    comps, _ = svc.run(stream)
    sched = svc.scheduler
    assert len(sched.delta_log) == 1 and len(sched.task_log) == 1
    t_delta = sched.delta_log[0][0]
    t_task, label = sched.task_log[0]
    assert t_task == t_delta and "movie_info" in label
    # the first post-delta admission already saw refreshed stats: the
    # catalog never lagged for any planned query
    assert db.stats.tables["movie_info"].nrows == \
        db.table("movie_info").nrows
    assert ctl.scores()["movie_info"].version_lag == 0


def test_reanalyze_leaves_stage_cache_correct(job_workload, agent):
    """Re-ANALYZE changes the catalog, not the data: resident stage-cache
    entries stay VALID (no version bump), and post-refresh executions are
    still bit-for-bit identical to cache-off runs."""
    db = fresh_db(scale=0.06)
    est = Estimator(db, db.stats)
    q = mi_join_query("q_reanalyze")
    cache = StageCache()
    r1 = run_adaptive(db, q, syntactic_plan(q), est, cache=cache)
    n_entries = len(cache)
    assert n_entries > 0
    # incremental re-ANALYZE of every table the query touches
    for t in ("title", "movie_info", "info_type"):
        db.stats.tables[t] = analyze_table(db, t,
                                           rng=np.random.default_rng(4))
    assert len(cache) == n_entries               # nothing was dropped
    assert cache.stats.invalidations == 0
    r2 = run_adaptive(db, q, syntactic_plan(q), est, cache=cache)   # warm
    ref = run_adaptive(db, q, syntactic_plan(q), est)       # no cache
    assert r2.latency == ref.latency == r1.latency
    assert [s.out_rows for s in r2.stages] == \
        [s.out_rows for s in ref.stages]
    assert cache.stats.hits > 0                  # the entries were reused


def test_reanalyze_fences_policy_store_incumbent_cache(job_workload, agent,
                                                       tmp_path):
    """Fresh statistics change probe planning WITHOUT a data-version bump:
    the store's version-keyed incumbent score must be dropped by the
    refresh (note_stats_refresh), and by probe-set swaps (set_probe)."""
    store = PolicyStore(tmp_path / "ps", [fast_query(0)])
    store._inc_score = (("sentinel",), 1.23)
    store.note_stats_refresh()
    assert store._inc_score is None
    store._inc_score = (("sentinel",), 1.23)
    store.set_probe([fast_query(1)], reason="coverage")
    assert store._inc_score is None
    assert store.probe_log[-1]["reason"] == "coverage"
    # end-to-end: a controller-run refresh fences an attached store
    db = fresh_db(scale=0.05)
    rb = ReplayBuffer()
    store2 = PolicyStore(tmp_path / "ps2", [fast_query(0)])
    store2._inc_score = (("sentinel",), 9.9)
    ctl = DriftController(policy=RefreshPolicy("always"), replay=rb,
                          store=store2)
    svc = QueryService(db, agent, est=Estimator(db, db.stats), n_lanes=2,
                       hooks=[TrajectoryHarvester(rb), ctl])
    svc.run(drifting_delta_stream([fast_query(i) for i in range(3)],
                                  n_queries=6, seed=3,
                                  drift_table="movie_info", drift_at=2,
                                  growth_rows=3000))
    assert ctl.stats.refresh_events >= 1
    assert store2._inc_score is None


# --------------------------------------------------------- refresh policy
def test_refresh_policy_kinds():
    cost = lambda t: 1.0
    drifts = {"a": _drift("a", 3.0), "b": _drift("b", 0.4),
              "c": TableDrift("c", 0, 1.0, 0.0, 0.0, 0.0)}   # no lag
    assert RefreshPolicy("never").decide(drifts, 0.0, cost).tables == ()
    # always: every table with version lag, regardless of score
    assert RefreshPolicy("always").decide(drifts, 0.0, cost).tables == \
        ("a", "b")
    # threshold: only the hot table
    assert RefreshPolicy("threshold", threshold=1.0).decide(
        drifts, 0.0, cost).tables == ("a",)
    with pytest.raises(AssertionError):
        RefreshPolicy("bogus")
    with pytest.raises(AssertionError):
        RefreshPolicy("budgeted")                 # budget_s required


def test_refresh_policy_budget_and_cooldown():
    drifts = {"a": _drift("a", 3.0), "b": _drift("b", 2.0),
              "d": _drift("d", 1.5)}
    pol = RefreshPolicy("budgeted", threshold=1.0, budget_s=2.5)
    dec = pol.decide(drifts, 0.0, lambda t: 1.0)
    # highest score first, stop when the NEXT table would bust the budget
    assert dec.tables == ("a", "b") and dec.modeled_cost_s == 2.0
    # the budget is RESERVED at decision time: a second decision taken
    # while the first task is still queued must not overshoot the ceiling
    assert pol.spent_modeled_s == 2.0
    # only 0.5s of budget left: nothing fits — even before note_refreshed
    assert pol.decide(drifts, 1.0, lambda t: 1.0).tables == ()
    for t in dec.tables:
        pol.note_refreshed(t, 0.0)
    assert pol.spent_modeled_s == 2.0             # no double charge
    # a cheaper lower-score table still fits a partial budget
    pol2 = RefreshPolicy("budgeted", threshold=1.0, budget_s=1.2)
    dec2 = pol2.decide(drifts, 0.0, lambda t: 1.0 if t == "a" else 0.2)
    assert dec2.tables == ("a", "b")              # 1.0 + 0.2 <= 1.2
    # min_interval floors per-table refresh frequency
    pol3 = RefreshPolicy("always", min_interval=10.0)
    assert pol3.decide(drifts, 0.0, lambda t: 0.0).tables == \
        ("a", "b", "d")
    pol3.note_refreshed("a", 0.0)
    assert pol3.decide(drifts, 5.0, lambda t: 0.0).tables == ("b", "d")
    assert "a" in pol3.decide(drifts, 10.0, lambda t: 0.0).tables


def test_incremental_analyze_matches_full_analyze_shape():
    """analyze() is now a fold over analyze_table(): same tables, same
    nrows (exact), deterministic given the rng seed."""
    db = fresh_db(scale=0.05)
    apply_delta(db, DeltaBatch("movie_info", n_append=1000, seed=1))
    full = analyze(db, rng=np.random.default_rng(7))
    assert set(full.tables) == set(db.tables)
    for name, ts in full.tables.items():
        assert ts.nrows == db.table(name).nrows
    one_a = analyze_table(db, "movie_info", rng=np.random.default_rng(9))
    one_b = analyze_table(db, "movie_info", rng=np.random.default_rng(9))
    assert one_a == one_b                          # seeded => deterministic
    assert one_a.nrows == db.table("movie_info").nrows
    assert set(one_a.columns) == set(db.table("movie_info").columns)


# -------------------------------------------------------- probe coverage
def test_coverage_probes_shift_toward_drifted_tables():
    # pool: 8 cast_info-touching traps + 8 title-only dimension joins
    from scenarios import trap_query
    pool = [trap_query(i, 1940 + i) for i in range(8)] + \
        [fast_query(i) for i in range(8)]
    cover = CoverageProbeSet(pool, k=6, seed=11)
    flat = cover.resample({})                      # no drift: uniform draw

    drifts = {"cast_info": _drift("cast_info", 8.0)}
    hot = cover.resample(drifts)
    touches = lambda qs: sum("cast_info" in {r.table for r in q.relations}
                             for q in qs)
    assert touches(hot) > touches(flat)
    assert touches(hot) >= 5                       # near-total coverage
    # weights: every pool entry keeps base mass (undrifted stay gateable)
    w = cover.weights(drifts)
    assert (w > 0).all() and w.max() > 10 * w.min()
    # deterministic: same seed, same call sequence => same sets
    cover2 = CoverageProbeSet(pool, k=6, seed=11)
    assert [q.name for q in cover2.resample({})] == \
        [q.name for q in flat]
    assert [q.name for q in cover2.resample(drifts)] == \
        [q.name for q in hot]


def test_controller_installs_coverage_probes(job_workload, agent, tmp_path):
    """When a table crosses probe_threshold the controller re-samples the
    gate's probe set toward it — once per drifted-table set, not per
    completion."""
    from scenarios import trap_query
    pool = [trap_query(i, 1940 + i) for i in range(6)] + \
        [fast_query(i) for i in range(6)]
    db = fresh_db(scale=0.05)
    rb = ReplayBuffer()
    store = PolicyStore(tmp_path / "ps", [fast_query(0), fast_query(1)])
    fixed = [q.name for q in store.probe]
    ctl = DriftController(policy=RefreshPolicy("never"), replay=rb,
                          store=store,
                          probes=CoverageProbeSet(pool, k=4, seed=2),
                          probe_threshold=0.5)
    svc = QueryService(db, agent, est=Estimator(db, db.stats), n_lanes=2,
                       hooks=[TrajectoryHarvester(rb), ctl])
    svc.run(drifting_delta_stream([fast_query(i) for i in range(3)],
                                  n_queries=8, seed=13,
                                  drift_table="cast_info", drift_at=3,
                                  growth_rows=20000))
    assert ctl.stats.probe_resamples == 1          # one set change, one swap
    assert [q.name for q in store.probe] != fixed
    assert sum("cast_info" in {r.table for r in q.relations}
               for q in store.probe) >= 2


# ------------------------------------------------------- refit generation
def test_refit_on_drift_generation_fencing(job_workload, agent):
    """refit_on_drift retrains from the live replay buffer, bumps the fit
    generation and drops the per-query memo — admissions after the refit
    see the new model, never a stale memoized estimate."""
    from repro.learn.replay import Experience
    from repro.core.rollout import Trajectory
    pred = LatencyPredictor(agent.meta, seed=3, lr=5e-3)
    strag = straggler_query()
    enc = encode_query(strag, agent.meta)
    rb = ReplayBuffer()
    for i in range(16):
        t = Trajectory()
        t.actions, t.states = [0], [enc]
        rb.add(Experience(seq=i, query_name="straggler", traj=t,
                          latency=300.0, failed=True, finish_t=float(i),
                          tables=("cast_info",), versions={"cast_info": 1}))
    before = pred.predict_query(strag)
    gen0 = pred.generation
    assert pred._pred_memo                          # memoized
    loss = pred.refit_on_drift(rb, np.random.default_rng(0),
                               current_versions={"cast_info": 1},
                               trigger="test")
    assert pred.generation > gen0 and pred.n_refits == 1
    assert not pred._pred_memo                      # memo fenced
    assert pred.refit_log[-1]["trigger"] == "test"
    for _ in range(11):
        pred.refit_on_drift(rb, np.random.default_rng(0))
    after = pred.predict_query(strag)
    assert after != before
    assert after > 100.0, f"refit should pull toward 300s, got {after}"
    assert np.isfinite(loss)
    # reset_stats: memos drop, generation/counters do NOT rewind
    pred.predict_query(strag)
    gen = pred.generation
    pred.reset_stats()
    assert not pred._pred_memo and not pred._enc_memo
    assert pred.generation == gen and pred.n_refits == 12


def test_controller_refit_trigger_and_cooldown(job_workload, agent):
    """The controller refits only once drift crosses refit_threshold, at
    most once per refit_every completions, with deterministic triggers."""
    def run():
        db = fresh_db(scale=0.05)
        rb = ReplayBuffer()
        pred = LatencyPredictor(agent.meta, seed=1)
        ctl = DriftController(policy=RefreshPolicy("never"), replay=rb,
                              predictor=pred, refit_threshold=0.5,
                              refit_every=4, refit_samples=8)
        svc = QueryService(db, agent, est=Estimator(db, db.stats),
                           n_lanes=2, hooks=[TrajectoryHarvester(rb), ctl])
        svc.run(drifting_delta_stream([fast_query(i) for i in range(4)],
                                      n_queries=12, seed=7,
                                      drift_table="movie_info", drift_at=4,
                                      growth_rows=4000))
        return ctl, pred

    ctl, pred = run()
    assert ctl.stats.refits >= 1
    assert pred.n_refits == ctl.stats.refits
    # cooldown: at most one refit per refit_every completions
    assert ctl.stats.refits <= ctl.stats.completions // 4
    ctl2, pred2 = run()
    assert [r["trigger"] for r in pred.refit_log] == \
        [r["trigger"] for r in pred2.refit_log]


# ------------------------------------------- curriculum demotion actuator
def test_curriculum_note_drift_floor_and_cooldown():
    """`note_drift` semantics in isolation: threshold-gated, one demotion
    per cooldown window, floored at stage 1, window/dwell reset."""
    from types import SimpleNamespace

    from repro.learn import AdaptiveCurriculum

    cur = AdaptiveCurriculum(start_stage=3, window=4, min_dwell=4,
                             drift_demote_threshold=0.4, drift_cooldown=3)
    comp = SimpleNamespace(result=SimpleNamespace(failed=False, latency=1.0))
    assert not cur.note_drift(0.39) and cur.stage == 3   # below threshold
    assert cur.note_drift(0.41) and cur.stage == 2
    assert cur.drift_demotions == [0] and cur.demotions == [0]
    assert len(cur._window) == 0                         # track record reset
    assert not cur.note_drift(0.9) and cur.stage == 2    # cooldown holds
    for _ in range(3):
        cur.observe(comp)
    assert cur.note_drift(0.9) and cur.stage == 1        # cooldown elapsed
    assert not cur.note_drift(0.9) and cur.stage == 1    # floored at 1
    assert cur.stats()["drift_demotions"] == [0, 3]


def test_controller_demotes_curriculum_on_attributed_drift(job_workload,
                                                           agent):
    """The fourth actuator: a growth delta raises the detector's peak
    score past `drift_demote_threshold`, and the shared curriculum drops
    a stage — PROACTIVELY, while the success-rate window is still clean
    (every completion here succeeds). Deterministic across runs."""
    from repro.learn import AdaptiveCurriculum

    class CurriculumWire:           # what BackgroundLearner does in prod
        def __init__(self, cur):
            self.cur = cur

        def attach(self, sched):
            sched.on_complete.append(self.cur.observe)

    def run(with_curriculum):
        db = fresh_db(scale=0.05)
        cur = AdaptiveCurriculum(start_stage=3, drift_demote_threshold=0.3) \
            if with_curriculum else None
        ctl = DriftController(policy=RefreshPolicy("never"), curriculum=cur)
        hooks = ([CurriculumWire(cur)] if cur else []) + [ctl]
        svc = QueryService(db, agent, est=Estimator(db, db.stats),
                           n_lanes=2, hooks=hooks)
        comps, _ = svc.run(drifting_delta_stream(
            [fast_query(i) for i in range(4)], n_queries=12, seed=11,
            drift_table="movie_info", drift_at=4, growth_rows=6000))
        return comps, ctl, cur

    comps, ctl, cur = run(True)
    assert all(not c.result.failed for c in comps)       # success governor
    assert cur.stage == 2                                #   never fired...
    assert ctl.stats.curriculum_demotions == 1           #   ...this did
    assert cur.drift_demotions and cur.demotions == cur.drift_demotions
    # demotion lands only after the growth delta's completions
    assert cur.drift_demotions[0] > 4
    # bit-deterministic: same stream, same demotion point
    _, ctl2, cur2 = run(True)
    assert cur2.drift_demotions == cur.drift_demotions
    assert ctl2.stats.curriculum_demotions == 1
    # no curriculum => the actuator (and its counter) stays off
    _, ctl0, _ = run(False)
    assert ctl0.stats.curriculum_demotions == 0
