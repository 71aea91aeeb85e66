"""Plan memory + background superoptimization (serve/plans/).

Pins the PR-10 contracts:
  * memory OFF (absent, or attached-but-empty with ingest off) is
    completion-bit-identical to the bare scheduler;
  * a probe hit replays EXACTLY the stored action sequence with zero
    act_batch participation, and the replayed plan actually takes
    effect (latency matches the scripted plan, not the agent's);
  * deltas and re-ANALYZEs FENCE entries (skip probe, survive as
    priors) instead of deleting them, and a fenced template falls back
    to the agent;
  * the superoptimizer is run-to-run deterministic and never promotes
    a candidate that fails or loses to the re-simulated incumbent;
  * checkpoint save/load restores entries bit-identically;
  * the QoS ladder's memo rung admits only on a memory hit;
  * the harvester skips memoized completions and feeds observed
    latencies back into entry stats;
  * the RCA engine attributes regressions to stale memos only when
    fence events are present.
"""
import numpy as np
import pytest

from scenarios import (barrier_stream, fast_query, fresh_db, mi_join_query,
                       noop_agent_for, trap_query)

from repro.serve.plans import (PlanEntry, PlanMemory, Superoptimizer,
                               band_for, template_signature)
from repro.serve.scheduler import Arrival, LaneScheduler
from repro.sql.cbo import Estimator
from repro.sql.query import Query


def _sched(db, agent, **kw):
    return LaneScheduler(db, Estimator(db, db.stats), agent, **kw)


def _sig(comps):
    return [(c.seq, c.admit_t, c.finish_t, tuple(c.traj.actions),
             c.result.latency, c.result.failed) for c in comps]


def _mixed_stream():
    qs = [trap_query(0, 1980), fast_query(0), mi_join_query()]
    out = [Arrival(0.1 * i, query=qs[i % 3], seed=i) for i in range(9)]
    return qs, out


# ----------------------------------------------------------- keying
def test_template_signature_is_structural_not_named():
    a, b = trap_query(0, 1980), trap_query(1, 1980)
    assert a.name != b.name
    assert template_signature(a) == template_signature(b)
    assert template_signature(a) != template_signature(trap_query(0, 1985))


def test_band_keying_moves_with_versions():
    q = fast_query(0)
    assert band_for(q, {}) == band_for(q, {t: 0 for t, _ in band_for(q, {})})
    v1 = {t: 1 for t, _ in band_for(q, {})}
    assert band_for(q, v1) != band_for(q, {})
    # band_width coarsens: version 0 and 1 share a band at width 2
    assert band_for(q, v1, band_width=2) == band_for(q, {}, band_width=2)


# ------------------------------------------------- off => bit-identical
def test_memory_off_is_completion_bit_identical():
    qs, stream = _mixed_stream()
    agent = noop_agent_for(*qs, max_steps=2)

    bare = _sched(fresh_db(), agent, n_lanes=2)
    plain = bare.run(stream)

    mem = PlanMemory(ingest_serving=False)
    withmem = _sched(fresh_db(), agent, n_lanes=2, plan_memory=mem)
    memo = withmem.run(stream)

    assert _sig(plain) == _sig(memo)
    assert not any(c.memoized for c in memo)
    assert mem.stats()["hits"] == 0
    assert mem.stats()["probes"] == len(stream)
    assert len(mem) == 0


# ------------------------------------------------------------- replay
def test_hit_replays_exact_stored_sequence_without_act_batch():
    q = trap_query(0, 1980)
    agent = noop_agent_for(q, max_steps=2)

    # noop baseline: what the agent would have served
    base = _sched(fresh_db(), agent, n_lanes=1)
    base_comps = base.run([Arrival(0.0, query=q, seed=1)])
    assert sum(base.decide_sizes) > 0

    db = fresh_db()
    mem = PlanMemory(ingest_serving=False)
    e = mem.install(q, db.versions, (0,), cost=0.5, source="superopt")
    sched = _sched(db, agent, n_lanes=1, plan_memory=mem)
    comps = sched.run([Arrival(0.0, query=q, seed=1)])

    c = comps[0]
    assert c.memoized
    # the STORED action, not the agent's noop
    assert tuple(c.traj.actions) == (0,)
    assert c.traj.actions != base_comps[0].traj.actions
    # zero policy participation, and the replayed plan took effect
    assert sum(sched.decide_sizes) == 0
    assert c.result.latency < base_comps[0].result.latency
    # stats folded back into the entry
    assert mem.n_hits == 1 and e.n_hits == 1
    assert e.best <= 0.5 and e.n_obs == 2      # install cost + replay


def test_serving_ingest_memoizes_repeats_and_skips_act_batch():
    q = mi_join_query()
    agent = noop_agent_for(q, max_steps=2)
    mem = PlanMemory()
    sched = _sched(fresh_db(), agent, n_lanes=1, plan_memory=mem)
    comps = sched.run([Arrival(0.2 * i, query=q, seed=i)
                       for i in range(4)])
    assert [c.memoized for c in comps] == [False, True, True, True]
    # every memoized completion replayed the first completion's sequence
    first = tuple(comps[0].traj.actions)
    assert all(tuple(c.traj.actions) == first for c in comps[1:])
    assert mem.stats()["hits"] == 3
    assert sum(sched.decide_sizes) == len(comps[0].traj.actions)


# ------------------------------------------------------------ fencing
def test_delta_fences_entry_and_falls_back_to_agent():
    q = mi_join_query()
    agent = noop_agent_for(q, max_steps=2)
    mem = PlanMemory()
    sched = _sched(fresh_db(), agent, n_lanes=1, plan_memory=mem)
    comps = sched.run(barrier_stream(q, "movie_info", n_pre=3, n_post=2))

    pre, post = comps[:3], comps[3:]
    assert [c.memoized for c in pre] == [False, True, True]
    # the delta fenced the pre-drift entry: first post-delta arrival
    # misses (its band moved AND the old band's entry is fenced)
    assert post[0].memoized is False
    assert mem.stats()["fenced"] >= 1
    fenced = [e for e in mem.entries() if e.fenced]
    assert fenced and fenced[0].fence_reason == "delta"
    # fenced entries skip probe but survive as priors
    assert mem.prior(q, {"movie_info": 0, "title": 0,
                         "movie_keyword": 0}) is not None
    # ...and serving re-memoizes on the new band
    assert post[1].memoized is True


def test_stats_refresh_fences_matching_tables_only():
    db = fresh_db()
    mem = PlanMemory()
    mem.install(mi_join_query(), db.versions, (0,), cost=1.0)
    mem.install(fast_query(0), db.versions, (0,), cost=1.0)
    n = mem.note_stats_refresh(["movie_info"])
    assert n == 1                          # fast_query has no movie_info
    fenced = [e for e in mem.entries() if e.fenced]
    assert len(fenced) == 1
    assert fenced[0].fence_reason == "re-analyze"
    assert any(t == "movie_info" for t, _ in fenced[0].band)
    assert mem.would_hit(fast_query(0), db.versions)
    assert not mem.would_hit(mi_join_query(), db.versions)


def test_drift_controller_refresh_fences_memory():
    from repro.serve.drift import DriftController, RefreshPolicy
    q = mi_join_query()
    agent = noop_agent_for(q, max_steps=2)
    db = fresh_db()
    mem = PlanMemory()
    ctl = DriftController(policy=RefreshPolicy("threshold", threshold=0.0),
                          plan_memory=mem)
    sched = _sched(db, agent, n_lanes=1, plan_memory=mem)
    ctl.attach(sched)
    sched.run(barrier_stream(q, "movie_info", n_pre=2, n_post=2))
    assert ctl.stats.tables_refreshed >= 1
    reasons = {e.fence_reason for e in mem.entries() if e.fenced}
    assert "re-analyze" in reasons or "delta" in reasons
    assert mem.stats()["fenced"] >= 1


# ------------------------------------------------------- superoptimizer
def _superopt_pass():
    qs = [trap_query(i % 2, 1980) for i in range(10)]
    agent = noop_agent_for(*qs, max_steps=2)
    mem = PlanMemory()
    so = Superoptimizer(mem, opt_every=4, sim_budget=16)
    sched = _sched(fresh_db(), agent, n_lanes=2, plan_memory=mem)
    so.attach(sched)
    comps = sched.run([Arrival(0.2 * i, query=q, seed=i)
                       for i, q in enumerate(qs)])
    return mem, so, comps


def test_superoptimizer_promotes_deterministically():
    mem1, so1, comps1 = _superopt_pass()
    mem2, so2, comps2 = _superopt_pass()
    assert so1.promote_log == so2.promote_log
    assert _sig(comps1) == _sig(comps2)
    assert so1.stats.promotions >= 1
    for p in so1.promote_log:
        # every promotion strictly beat its re-simulated incumbent
        assert p["incumbent_cost"] is None or \
            p["cost"] < p["incumbent_cost"]
    # the promoted sequence serves subsequent arrivals
    assert any(c.memoized and tuple(c.traj.actions) ==
               tuple(so1.promote_log[0]["actions"]) for c in comps1)
    assert mem1.stats()["promoted_superopt"] == so1.stats.promotions


def test_superoptimizer_simulations_leave_serving_cache_alone():
    """Simulations run without the scheduler's stage cache: its counters,
    entries and bytes are the same after every simulation as before."""
    from repro.serve.cache import StageCache
    q = trap_query(0, 1980)
    cache = StageCache()
    mem = PlanMemory()
    so = Superoptimizer(mem, opt_every=2, sim_budget=16)
    sched = _sched(fresh_db(), noop_agent_for(q, max_steps=2), n_lanes=1,
                   plan_memory=mem, cache=cache)
    so.attach(sched)
    simulate, same = so._simulate, []

    def state():
        return cache.stats.as_dict(), len(cache), cache.bytes

    def spy(*args):
        before = state()
        out = simulate(*args)
        same.append(state() == before)
        return out

    so._simulate = spy
    sched.run([Arrival(0.2 * i, query=q, seed=i) for i in range(4)])
    assert so.stats.sims == len(same) > 0 and all(same)
    assert cache.stats.misses > 0           # the served runs did use it


def test_superoptimizer_never_regresses_incumbent():
    q = trap_query(0, 1980)
    agent = noop_agent_for(q, max_steps=2)
    db = fresh_db()
    mem = PlanMemory(ingest_serving=False)
    # plant the known-best plan as the incumbent
    best = mem.install(q, db.versions, (0,), cost=0.529, source="superopt")
    so = Superoptimizer(mem, opt_every=2, sim_budget=16)
    sched = _sched(db, agent, n_lanes=1, plan_memory=mem)
    so.attach(sched)
    sched.run([Arrival(0.2 * i, query=q, seed=i) for i in range(4)])
    assert so.stats.rounds >= 1
    # nothing beat the incumbent: it must still be installed, unfenced
    assert mem.prior(q, db.versions) is best
    assert not best.fenced


def test_superoptimizer_reads_heat_from_plan_ledger():
    from repro.serve.obs.monitor import PlanLedger
    q = trap_query(0, 1980)
    db = fresh_db()
    mem = PlanMemory()
    ledger = PlanLedger()
    sig = template_signature(q)
    band = band_for(q, db.versions)
    for _ in range(5):
        ledger.observe(0, q.name, band, 0.6, False)
    so = Superoptimizer(mem, ledger=ledger)
    so._heat[(sig, band)] = 1
    so._repr[(sig, band)] = q
    assert so._heat_of((sig, band)) == 5   # ledger counts win
    so.ledger = None
    assert so._heat_of((sig, band)) == 1   # local fallback


# ---------------------------------------------------------- persistence
def test_checkpoint_round_trip_is_bit_identical(tmp_path):
    db = fresh_db()
    mem = PlanMemory(band_width=2)
    e = mem.install(mi_join_query(), db.versions, (0, 3), cost=0.123456789,
                    decoded=("('cbo', 1)", "('lead', 0)"), t=4.2)
    e.observe(0.777)
    mem.install(fast_query(1), db.versions, (), cost=2.5, source="serve")
    mem.fence_table("movie_info", "delta", t=5.0)

    step = mem.save(tmp_path)
    back = PlanMemory.load(tmp_path, step)
    assert back.to_dict() == mem.to_dict()   # floats exact via JSON
    # and the restored memory serves: same key -> same entry actions
    got = back.prior(fast_query(1), db.versions)
    assert got is not None and got.actions == ()

    # a second save goes to a new step; load(None) takes the latest
    mem.install(fast_query(2), db.versions, (1,), cost=0.5)
    step2 = mem.save(tmp_path)
    assert step2 != step
    assert len(PlanMemory.load(tmp_path)) == len(mem)


# ------------------------------------------------------------ QoS rung
def test_ladder_memo_rung_gates_on_memory_hit():
    from repro.serve.qos.degrade import DegradationLadder, _as_budget
    lad = DegradationLadder.with_memo_rung()
    # inside the classic rungs the memo bit changes nothing
    assert lad.choose(1.0, 2.0, memo_hit=True).hook_budget is None
    # severity in (4, 8]: memo hit -> replay rung; miss -> cheapest budget
    hit = lad.choose(6.0, 1.0, memo_hit=True)
    assert (hit.action, hit.hook_budget, hit.memo_only) == ("admit", 0, True)
    miss = lad.choose(6.0, 1.0, memo_hit=False)
    assert (miss.action, miss.hook_budget, miss.memo_only) == \
        ("admit", 0, False)
    assert miss.degraded
    # past reject_above both reject
    assert lad.choose(9.0, 1.0, memo_hit=True).action == "reject"
    assert _as_budget("memo") == 0 and _as_budget(None) is None
    assert _as_budget(2) == 2


def test_qos_admission_counts_memo_admits():
    from scenarios import FixedPredictor
    from repro.serve.qos import QoSAdmission, TenantRegistry
    from repro.serve.qos.degrade import DegradationLadder
    q = fast_query(0)
    agent = noop_agent_for(q, max_steps=2)
    db = fresh_db()
    mem = PlanMemory(ingest_serving=False)
    mem.install(q, db.versions, (), cost=0.25)
    adm = QoSAdmission(TenantRegistry(), predictor=FixedPredictor(),
                       ladder=DegradationLadder.with_memo_rung(),
                       plan_memory=mem)
    sched = _sched(db, agent, n_lanes=1, admission=adm, plan_memory=mem)
    # predicted 1s, deadline slack ~0.2s => severity ~5: memo rung
    comps = sched.run([Arrival(0.0, query=q, seed=1, deadline=0.2)])
    assert len(comps) == 1 and comps[0].memoized
    assert adm.n_memo_admits == 1
    assert adm.stats()["memo_admits"] == 1


# ----------------------------------------------------- harvester seam
def test_harvester_skips_memoized_and_feeds_back_latency():
    from repro.learn import TrajectoryHarvester
    q = mi_join_query()
    agent = noop_agent_for(q, max_steps=2)
    db = fresh_db()
    mem = PlanMemory(ingest_serving=False)
    e = mem.install(q, db.versions, (10,), cost=1.0)
    n_obs0, best0 = e.n_obs, e.best
    harv = TrajectoryHarvester(plan_memory=mem)
    sched = _sched(db, agent, n_lanes=1, plan_memory=mem)
    harv.attach(sched)
    comps = sched.run([Arrival(0.2 * i, query=q, seed=i)
                       for i in range(3)])
    assert all(c.memoized for c in comps)
    assert harv.n_memoized == 3 and harv.n_harvested == 0
    assert len(harv.replay) == 0

    # a non-memoized completion feeds its latency into the entry stats
    mem2 = PlanMemory(ingest_serving=False)
    e2 = mem2.install(q, db.versions, (10,), cost=1.0)
    e2.fenced = True                      # probe misses, entry remains
    harv2 = TrajectoryHarvester(plan_memory=mem2)
    sched2 = _sched(fresh_db(), agent, n_lanes=1, plan_memory=mem2)
    harv2.attach(sched2)
    comps2 = sched2.run([Arrival(0.0, query=q, seed=1)])
    assert not comps2[0].memoized
    assert harv2.n_fed_back == 1 and harv2.n_harvested == 1
    assert e2.n_obs == 2
    assert e2.best == 1.0                 # feedback never moves best


# ------------------------------------------------------------- obs/RCA
def test_obs_events_and_stale_memo_attribution():
    from repro.serve.obs import Tracer
    from repro.serve.obs.rca import CAUSES, attribute
    q = mi_join_query()
    agent = noop_agent_for(q, max_steps=2)
    mem = PlanMemory()
    tracer = Tracer()
    sched = _sched(fresh_db(), agent, n_lanes=1)
    tracer.attach(sched)
    mem.attach(sched)
    sched.run(barrier_stream(q, "movie_info", n_pre=2, n_post=2))
    kinds = {e.kind for e in tracer.events}
    assert {"plan_memory_miss", "plan_memory_hit", "plan_memory_promoted",
            "plan_memory_fenced"} <= kinds
    assert tracer.metrics.snapshot()["counters"]["events[plan_memory_hit]"] >= 1

    assert "stale_memo" in CAUSES
    fences = [e for e in tracer.events if e.kind == "plan_memory_fenced"]
    win = [{"latency": 3.0, "arrival_t": 1.0, "tenant": "default",
            "template": "q_mi", "band": (("movie_info", 1),), "step": 0,
            "phases": {"queue": 0.1, "execute": 2.9, "retry": 0.0,
                       "hedge": 0.0},
            "failed": False, "failure_kind": "", "fail_kinds": ()}]
    hyps = attribute(tenant="", metric_label="p99", window=win,
                     baseline=[], events=fences)
    assert any(h.cause == "stale_memo" for h in hyps)
    # no fence events => no stale_memo hypothesis (the gate)
    hyps2 = attribute(tenant="", metric_label="p99", window=win,
                      baseline=[], events=[])
    assert not any(h.cause == "stale_memo" for h in hyps2)


# ------------------------------------------------------- service stats
def test_service_reports_plan_memory_stats():
    from repro.serve.service import QueryService
    q = mi_join_query()
    agent = noop_agent_for(q, max_steps=2)
    mem = PlanMemory()
    svc = QueryService(fresh_db(), agent, n_lanes=2, plan_memory=mem)
    comps, stats = svc.run_queries([q] * 4)
    assert stats.n_memoized == sum(c.memoized for c in comps) > 0
    assert stats.plan_memory == mem.stats()
    assert stats.plan_memory["hits"] > 0
    svc.reset_stats()
    assert mem.stats()["probes"] == 0 and len(mem) > 0
    svc.reset_stats(clear_entries=True)
    assert len(mem) == 0
