"""Online serving subsystem invariants: LRU stage cache (eviction, stats,
O(1) version-tag invalidation, bit-for-bit equality with cache-off runs),
async lane scheduling (seeded async == seeded serial; stragglers do not
block other lanes; lockstep remains a reproducible special case), the
delta write barrier, and the service façade's metrics.

Shared scenario builders (fresh dbs, fast/straggler/mi-join queries,
barrier streams) live in tests/scenarios.py; the `agent` fixture is the
session-scoped one from conftest.py.
"""
import numpy as np

from scenarios import (barrier_stream, fast_query, fresh_db, mi_join_query,
                       straggler_mix_stream, straggler_query)

from repro.core.rollout import rollout
from repro.serve.cache import StageCache
from repro.serve.deltas import DeltaBatch, apply_delta
from repro.serve.driver import open_loop_stream
from repro.serve.scheduler import Arrival, LaneScheduler
from repro.serve.service import QueryService
from repro.sql.cbo import Estimator
from repro.sql.executor import Executor, run_adaptive
from repro.sql.plans import syntactic_plan


# ------------------------------------------------------------- stage cache
def test_stage_cache_lru_eviction_not_clear_all():
    c = StageCache(max_bytes=100, max_entry_bytes=100)
    for i in range(4):
        assert c.put(("sig", i), f"entry{i}", 30)
    # 4*30 > 100: the LRU entry (sig 0) was evicted, the rest survive —
    # the old dict dropped EVERYTHING on overflow
    assert c.stats.evictions == 1 and len(c) == 3
    assert c.get(("sig", 0)) is None
    assert c.get(("sig", 1)) == "entry1"
    c.put(("sig", 4), "entry4", 30)          # now 2 is LRU (1 was touched)
    assert c.get(("sig", 2)) is None and c.get(("sig", 1)) == "entry1"
    assert c.bytes <= c.max_bytes
    assert not c.put("huge", "x", 101)       # oversized: never admitted
    s = c.stats.as_dict()
    assert s["hits"] == 2 and s["misses"] == 2 and s["evictions"] == 2


def test_stage_cache_oversized_entry_and_zero_budget():
    # an entry larger than the WHOLE budget is refused outright: no
    # eviction storm, resident entries untouched, counters unchanged
    c = StageCache(max_bytes=100, max_entry_bytes=1000)
    assert c.put(("a",), "x", 60)
    assert not c.put(("big",), "y", 101)
    assert len(c) == 1 and c.bytes == 60 and c.stats.evictions == 0
    assert c.get(("a",)) == "x"
    # budget = 0: nothing with real bytes is ever admitted or evicted
    z = StageCache(max_bytes=0)
    assert not z.put(("s",), "x", 1)
    assert len(z) == 0 and z.bytes == 0 and z.stats.evictions == 0
    assert z.get(("s",)) is None and z.stats.misses == 1


def test_stage_cache_eviction_counter_consistency():
    """admitted - evicted == resident at every point, including refreshes
    of an existing signature (which must not double-count bytes)."""
    c = StageCache(max_bytes=100, max_entry_bytes=100)
    admitted = sum(c.put(("sig", i), i, 27) for i in range(20))
    assert admitted == 20
    assert admitted - c.stats.evictions == len(c)
    assert c.bytes == 27 * len(c) <= c.max_bytes
    # refreshing a resident sig with a new size replaces, never duplicates
    # — and is not an eviction: the counters keep adding up
    sig = next(iter(c._entries))
    before, evictions_before = len(c), c.stats.evictions
    c.put(sig, "new", 10)
    assert len(c) == before
    assert c.bytes == 27 * (len(c) - 1) + 10
    assert c.stats.evictions == evictions_before
    assert admitted - c.stats.evictions == len(c)


def test_stage_cache_reset_stats_keeps_entries():
    """reset_stats zeroes the counters without touching residency — the
    between-runs measurement seam."""
    c = StageCache(max_bytes=100, max_entry_bytes=100)
    c.put(("a",), "x", 30)
    assert c.get(("a",)) == "x" and c.get(("b",)) is None
    assert c.stats.hits == 1 and c.stats.misses == 1
    c.reset_stats()
    assert c.stats.as_dict() == {"hits": 0, "misses": 0, "evictions": 0,
                                 "invalidations": 0, "hit_rate": 0.0}
    assert len(c) == 1 and c.get(("a",)) == "x"   # entries survived


def test_executor_exposes_cache_stats_and_hits(job_workload):
    db = fresh_db(scale=0.05)
    est = Estimator(db, db.stats)
    q = job_workload.test[0]
    cache = StageCache()
    ex = Executor(db, cache=cache)
    assert ex.cache_stats is cache.stats and ex.cache_stats.hits == 0
    r1 = run_adaptive(db, q, syntactic_plan(q), est, cache=cache)
    misses_after_first = ex.cache_stats.misses
    r2 = run_adaptive(db, q, syntactic_plan(q), est, cache=cache)
    assert ex.cache_stats.hits > 0, "replaying a query must hit the cache"
    assert ex.cache_stats.misses == misses_after_first
    assert r1.latency == r2.latency
    assert [s.out_rows for s in r1.stages] == [s.out_rows for s in r2.stages]
    assert Executor(db).cache_stats is None


def test_executor_eviction_under_tiny_budget(job_workload):
    db = fresh_db(scale=0.05)
    cache = StageCache(max_bytes=64 * 1024)
    est = Estimator(db, db.stats)
    for q in job_workload.test[:6]:
        run_adaptive(db, q, syntactic_plan(q), est, cache=cache)
    st = cache.stats
    assert st.evictions > 0, "tiny budget must evict"
    assert len(cache) > 0, "eviction is LRU, not clear-all"
    assert cache.bytes <= 64 * 1024


def test_runs_without_a_cache_leave_none_and_reuse_nothing(monkeypatch):
    """An executor or `run_adaptive` call given no cache attaches none to
    the database and computes every scan and join again on every run."""
    db = fresh_db(scale=0.05)
    est = Estimator(db, db.stats)
    attrs = dict(vars(db))
    q = mi_join_query("q_no_cache")
    probes = []
    join = Executor.join

    def spy(self, *args):
        out = join(self, *args)
        probes.append(self.probe)
        return out

    monkeypatch.setattr(Executor, "join", spy)
    runs = [run_adaptive(db, q, syntactic_plan(q), est) for _ in range(2)]
    assert runs[0].stages == runs[1].stages
    # a join served from a cache records no probe: every one was computed
    assert len(probes) == 2 * len(runs[0].stages) > 0
    assert all(p is not None for p in probes)
    ex = Executor(db)
    assert ex.cache_stats is None
    a, _ = ex.scan(q, "mi")
    b, _ = Executor(db).scan(q, "mi")
    assert a.columns.keys() == b.columns.keys()
    assert all(a.columns[k] is not b.columns[k] for k in a.columns)
    assert vars(db).keys() == attrs.keys()
    assert not any(isinstance(v, StageCache) for v in vars(db).values())


# ----------------------------------------------------- delta invalidation
def test_invalidation_recomputes_bit_for_bit_vs_cache_off():
    db = fresh_db(scale=0.08)
    est = Estimator(db, db.stats)
    q = mi_join_query()
    cache = StageCache()
    r1 = run_adaptive(db, q, syntactic_plan(q), est, cache=cache)
    r2 = run_adaptive(db, q, syntactic_plan(q), est,
                      cache=cache)                         # warm: cache hit
    assert [s.out_rows for s in r2.stages] == [s.out_rows for s in r1.stages]
    hits_before = cache.stats.hits
    assert hits_before > 0

    counts = apply_delta(db, DeltaBatch("movie_info", n_append=2000, seed=1),
                         cache)
    assert counts["appended"] == 2000
    assert db.table_version("movie_info") == 1
    assert cache.stats.invalidations == 1

    r3 = run_adaptive(db, q, syntactic_plan(q), est,
                      cache=cache)                         # post-delta
    ref = run_adaptive(db, q, syntactic_plan(q), est)      # no cache
    # bit-for-bit vs cache-off on the NEW data — a stale cached stage
    # would differ, because appended rows join with existing titles
    assert r3.latency == ref.latency
    assert r3.total_shuffles == ref.total_shuffles
    assert [s.out_rows for s in r3.stages] == [s.out_rows for s in ref.stages]
    assert [s.out_rows for s in r3.stages] != [s.out_rows for s in r1.stages]


def test_delta_delete_and_append_roundtrip():
    db = fresh_db(scale=0.05)
    t = db.table("movie_keyword")
    n0 = t.nrows
    counts = apply_delta(db, DeltaBatch("movie_keyword", n_append=100,
                                        delete_frac=0.5, seed=2))
    assert t.nrows == n0 + 100 - counts["deleted"]
    assert counts["deleted"] > 0
    assert db.table_version("movie_keyword") == 1
    # FKs still live: every movie_id points at an existing title row
    assert t.columns["movie_id"].max() < db.table("title").nrows


# --------------------------------------------------------- lane scheduler
def test_async_scheduler_matches_seeded_serial(job_db, job_workload,
                                               estimator, agent):
    qs = job_workload.test[:6]
    seeds = [11, 22, 33, 44, 55, 66]
    serial = [rollout(job_db, q, estimator, agent, stage=3, explore=True,
                      key=s) for q, s in zip(qs, seeds)]
    sched = LaneScheduler(job_db, estimator, agent, n_lanes=3,
                          explore=True, policy="async")
    comps = sched.run([Arrival(0.3 * i, query=q, seed=s)
                       for i, (q, s) in enumerate(zip(qs, seeds))])
    assert [c.seq for c in comps] == list(range(6))
    for s, c in zip(serial, comps):
        assert s.actions == c.traj.actions
        assert s.t_execute == c.traj.t_execute
        assert s.rewards == c.traj.rewards
        np.testing.assert_allclose(s.logps, c.traj.logps, atol=1e-6)


def test_scheduler_window_does_not_change_results(job_db, job_workload,
                                                  estimator, agent):
    qs = job_workload.test[:5]
    streams = []
    for window in (None, 0.0, 1.0):
        sched = LaneScheduler(job_db, estimator, agent, n_lanes=2,
                              explore=True, policy="async", window=window)
        streams.append(sched.run([Arrival(0.5 * i, query=q, seed=i)
                                  for i, q in enumerate(qs)]))
    for comps in streams[1:]:
        for a, b in zip(streams[0], comps):
            assert a.traj.actions == b.traj.actions
            assert a.finish_t == b.finish_t and a.admit_t == b.admit_t


def test_straggler_does_not_block_other_lanes(job_workload, agent):
    db = fresh_db(scale=0.1)
    est = Estimator(db, db.stats)
    strag_q = straggler_query()
    # precondition: the straggler really dominates (OOM -> timeout charge)
    r_strag = run_adaptive(db, strag_q, syntactic_plan(strag_q), est)
    fast0 = fast_query(0)
    r_fast = run_adaptive(db, fast0, syntactic_plan(fast0), est)
    assert r_strag.latency > 10 * r_fast.latency

    def serve(policy):
        sched = LaneScheduler(db, est, agent, n_lanes=2, explore=False,
                              policy=policy, window=0.0)
        return sched.run(straggler_mix_stream(6))

    a = serve("async")
    strag = a[0]
    # every fast query finished (virtually) before the straggler...
    assert all(c.finish_t < strag.finish_t for c in a[1:])
    # ...because none of them ever waited behind it: the straggler holds
    # exactly one lane while the other lane streams through all 6
    assert all(c.lane != strag.lane for c in a[1:])
    # step-count: the straggler got at most its hook-budget of decisions,
    # yet the scheduler kept ticking for everyone else
    assert len(strag.traj.actions) <= agent.cfg.max_steps
    fast_steps = sum(len(c.traj.actions) for c in a[1:])
    assert fast_steps >= 6

    ls = serve("lockstep")
    strag_l = ls[0]
    done_before_async = sum(c.finish_t < strag.finish_t for c in a[1:])
    done_before_lock = sum(c.finish_t < strag_l.finish_t for c in ls[1:])
    # lockstep barriers every later wave behind the straggler
    assert done_before_async == 6 and done_before_lock <= 1
    p99 = lambda comps: float(np.percentile([c.latency for c in comps], 99))
    assert p99(a) < p99(ls), "async must beat lockstep on a straggler mix"
    # ...and on throughput: the same work drains sooner on the clock
    assert max(c.finish_t for c in a) < max(c.finish_t for c in ls)


def test_lockstep_policy_matches_rollout_batch(job_db, job_workload,
                                               estimator, agent):
    from repro.core.vec_rollout import rollout_batch
    qs = job_workload.test[:4]
    trajs = rollout_batch(job_db, qs, estimator, agent, explore=True,
                          seeds=[7, 8, 9, 10])
    sched = LaneScheduler(job_db, estimator, agent, n_lanes=4, explore=True,
                          policy="lockstep")
    comps = sched.run([Arrival(0.0, query=q, seed=s)
                       for q, s in zip(qs, [7, 8, 9, 10])])
    for t, c in zip(trajs, comps):
        assert t.actions == c.traj.actions
        assert t.t_execute == c.traj.t_execute


# ------------------------------------------------------- delta write barrier
def test_delta_write_barrier_orders_queries(job_workload, agent):
    db = fresh_db(scale=0.08)
    est = Estimator(db, db.stats)
    stream = barrier_stream(mi_join_query("q_mi_barrier"))
    sched = LaneScheduler(db, est, agent, n_lanes=2, explore=False,
                          policy="async")
    comps = sched.run(stream)
    assert len(sched.delta_log) == 1
    t_apply = sched.delta_log[0][0]
    pre, post = comps[:2], comps[2:]
    assert all(c.finish_t <= t_apply for c in pre), "barrier drains in-flight"
    assert all(c.admit_t >= t_apply for c in post), "later queries wait"
    # queries behind the barrier saw the appended rows: stage cardinalities
    # differ from the pre-delta executions of the SAME query
    rows = lambda c: [s.out_rows for s in c.result.stages]
    assert rows(post[0]) != rows(pre[0])
    assert rows(post[0]) == rows(post[1])


# ---------------------------------------------------------------- service
def test_query_service_empty_stream(job_workload, agent):
    """An empty arrival stream must yield zeroed stats, not a divide by
    zero (qps, percentiles, mean decide batch)."""
    db = fresh_db(scale=0.05)
    svc = QueryService(db, agent, est=Estimator(db, db.stats), n_lanes=2)
    comps, stats = svc.run([])
    assert comps == []
    assert stats.n_completed == 0 and stats.n_failed == 0
    assert stats.qps == 0.0 and stats.latency_p99 == 0.0
    assert stats.mean_decide_batch == 0.0 and stats.ticks == 0
    assert 0.0 <= stats.cache["hit_rate"] <= 1.0
    # and run_queries of an empty batch goes through the same path
    comps, stats = svc.run_queries([])
    assert comps == [] and stats.n_completed == 0


def test_query_service_stats_and_driver(job_workload, agent):
    db = fresh_db(scale=0.08)
    est = Estimator(db, db.stats)
    stream = open_loop_stream(job_workload.test[:6], rate=4.0,
                              n_queries=10, seed=5)
    assert len(stream) == 10
    assert all(stream[i].t <= stream[i + 1].t for i in range(9))
    svc = QueryService(db, agent, est=est, n_lanes=3, policy="async")
    comps, stats = svc.run(stream)
    assert stats.n_completed == 10
    assert stats.qps > 0 and stats.latency_p99 >= stats.latency_p50 > 0
    assert 0.0 <= stats.cache["hit_rate"] <= 1.0
    assert stats.ticks == len(svc.scheduler.decide_sizes)
    # same trace through lockstep: identical per-query service times,
    # scheduling differences only show up in queueing latency
    svc2 = QueryService(db, agent, est=est, n_lanes=3, policy="lockstep")
    comps2, _ = svc2.run(stream)
    for a, b in zip(comps, comps2):
        assert a.result.latency == b.result.latency
        assert a.traj.actions == b.traj.actions


def test_query_service_reset_stats_between_runs(job_workload, agent):
    """Consecutive runs on one service ACCUMULATE cache counters (the
    executor state is shared); reset_stats(clear_entries=True) makes the
    second run's stats independently measurable — and identical to the
    first run's on an unmutated database."""
    db = fresh_db(scale=0.05)
    svc = QueryService(db, agent, est=Estimator(db, db.stats), n_lanes=2)
    stream = open_loop_stream(job_workload.test[:4], rate=4.0,
                              n_queries=6, seed=9)
    _, s1 = svc.run(stream)
    _, s2 = svc.run(stream)           # warm cache + counters carry over
    assert s2.cache["hits"] > s1.cache["hits"]
    svc.reset_stats(clear_entries=True)
    assert len(svc.cache) == 0
    _, s3 = svc.run(stream)           # cold again: full independent rerun
    d1, d3 = s1.as_dict(), s3.as_dict()
    d1.pop("hook_seconds"), d3.pop("hook_seconds")   # host wall time
    assert d3 == d1
    # counters-only reset keeps entries resident: same completions, all
    # prior misses now hit
    svc.reset_stats()
    assert len(svc.cache) > 0
    _, s4 = svc.run(stream)
    assert s4.cache["misses"] == 0 and s4.cache["hits"] > 0
    assert s4.n_completed == s1.n_completed
