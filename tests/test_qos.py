"""QoS control-plane invariants: deterministic admissions/degradations on
the virtual clock, tenant isolation (a flooding tenant can neither evict
another tenant's cache entries nor starve its lanes), EDF ordering, the
degradation ladder / token bucket units, predictor warm-start semantics,
and the predictor-off fallback being bit-identical to plain async.

Scenario builders (fresh dbs, fast/straggler queries, the two-tenant QoS
setup + stream, the FixedPredictor stub) live in tests/scenarios.py; the
`agent` fixture is the session-scoped one from conftest.py.
"""
import pytest

from scenarios import (FixedPredictor, fast_query, fast_subset, fresh_db,
                       qos_setup, qos_stream, straggler_query)

from repro.serve.cache import PartitionedStageCache
from repro.serve.driver import TenantTraffic, multi_tenant_stream
from repro.serve.qos import (AdmissionPolicy, DegradationLadder,
                             LatencyPredictor, QoSAdmission, TenantRegistry,
                             TenantSpec, encode_query)
from repro.serve.scheduler import Arrival, LaneScheduler
from repro.serve.service import QueryService
from repro.sql.cbo import Estimator


# ------------------------------------------------------------------ units
def test_token_bucket_on_virtual_clock():
    reg = TenantRegistry([TenantSpec("t", rate=1.0, burst=2)])
    # probing is pure: repeated calls at the same time agree
    assert reg.earliest_admit("t", 0.0) == 0.0
    assert reg.earliest_admit("t", 0.0) == 0.0
    reg.acquire("t", 0.0)
    reg.acquire("t", 0.0)                       # burst of 2 consumed
    t_next = reg.earliest_admit("t", 0.0)
    assert t_next == pytest.approx(1.0)         # 1 token / virtual second
    assert reg.earliest_admit("t", 0.0) == pytest.approx(1.0)
    reg.acquire("t", t_next)
    assert reg.earliest_admit("t", t_next) == pytest.approx(t_next + 1.0)
    # unknown tenants are unlimited
    assert reg.earliest_admit("other", 5.0) == 5.0
    # a fresh run restarts the bucket clock (one admission object can
    # serve several streams reproducibly)
    reg.reset_clock()
    assert reg.earliest_admit("t", 0.0) == 0.0
    # degenerate specs are rejected at registration, not mid-run
    with pytest.raises(AssertionError):
        TenantRegistry([TenantSpec("bad", rate=1.0, burst=0)])
    with pytest.raises(AssertionError):
        TenantRegistry([TenantSpec("bad", rate=0.0)])


def test_partitioned_cache_default_tenant_budget():
    """An explicit budget for the 'default' tenant sizes the base cache
    itself (partition('default') IS the object); UNBUDGETED tenant ids
    share the default partition, so a stream of distinct ids cannot grow
    memory past sum(budgets) + default."""
    c = PartitionedStageCache(default_bytes=1 << 20,
                              budgets={"default": 100, "t": 200})
    assert c.partition("default") is c and c.max_bytes == 100
    assert c.partition("t").max_bytes == 200
    assert c.partition("other") is c
    assert c.partition("another") is c and not c._parts.keys() - {"t"}


def test_degradation_ladder_rungs():
    lad = DegradationLadder()                   # (1, full) (2, 1) (4, 0)
    assert lad.choose(10.0, 20.0).hook_budget is None        # on track
    assert not lad.choose(10.0, 20.0).degraded
    d = lad.choose(30.0, 20.0)                  # severity 1.5
    assert d.action == "admit" and d.hook_budget == 1 and d.degraded
    d = lad.choose(50.0, 20.0)                  # severity 2.5
    assert d.action == "admit" and d.hook_budget == 0
    assert lad.choose(100.0, 20.0).action == "reject"        # severity 5
    assert lad.choose(1.0, 0.0).action == "reject"           # no slack
    # no reject rung configured: the bottom budget catches everything
    soft = DegradationLadder(reject_above=None)
    d = soft.choose(100.0, 20.0)
    assert d.action == "admit" and d.hook_budget == 0 and d.degraded
    # a reject threshold the rungs would shadow is a config error
    with pytest.raises(AssertionError):
        DegradationLadder(rungs=((1.0, None), (4.0, 0)), reject_above=2.0)


def test_predictor_warm_start_matches_critic(job_workload, agent):
    """Warm-started predictor params ARE the critic: its latency estimate
    must equal max(0, -v)^2 at the same encoded state."""
    pred = LatencyPredictor(agent.meta, agent=agent)
    enc = encode_query(job_workload.test[0], agent.meta)
    v = agent.value(enc)
    assert pred.predict_enc(enc) == pytest.approx(max(0.0, -v) ** 2,
                                                  rel=1e-5)


def test_predictor_fit_separates_slow_from_fast(job_workload, agent):
    pred = LatencyPredictor(agent.meta, seed=3, lr=5e-3)
    fast_enc = encode_query(job_workload.test[0], agent.meta)
    slow_enc = encode_query(straggler_query(), agent.meta)
    encs = [fast_enc, slow_enc] * 8
    lats = [1.0, 300.0] * 8
    first = pred.fit(encs, lats, batch_size=8, epochs=1)
    for _ in range(12):
        last = pred.fit(encs, lats, batch_size=8, epochs=2)
    assert last < first
    p_fast, p_slow = pred.predict_enc(fast_enc), pred.predict_enc(slow_enc)
    assert p_slow > 10 * p_fast, (p_fast, p_slow)
    # the memo is fenced by fit generation: query-level predictions move
    q = job_workload.test[0]
    a = pred.predict_query(q)
    pred.fit([fast_enc], [200.0], batch_size=4, epochs=4)
    assert pred.predict_query(q) != a


# ----------------------------------------------------------- determinism
def test_qos_same_seed_identical_admissions(job_workload, agent):
    """Same seed => identical admissions, degradations, rejections and
    completion times on the virtual clock, including token-bucket
    deferrals and per-tenant cache partitions."""
    runs = []
    for _ in range(2):
        db = fresh_db()
        reg, adm = qos_setup()
        svc = QueryService(db, agent, est=Estimator(db, db.stats),
                           n_lanes=2, policy="edf", tenants=reg,
                           admission=adm)
        comps, stats = svc.run(qos_stream(job_workload))
        d = stats.as_dict()
        d.pop("hook_seconds")           # host wall time: not virtual-clock
        runs.append((
            [(c.seq, c.tenant, c.admit_t, c.finish_t, c.hook_budget,
              c.degraded, tuple(c.traj.actions)) for c in comps],
            [(r.seq, r.reject_t, r.reason) for r in svc.scheduler.rejections],
            adm.stats(), d))
    assert runs[0] == runs[1]
    comp_rows, reject_rows, adm_stats, _ = runs[0]
    assert len(reject_rows) == 1               # the monster was rejected
    assert adm_stats["deferred"] > 0           # bulk hit its rate limit


def test_qos_admission_reusable_across_runs(job_workload, agent):
    """One admission object serving two streams: the second run must not
    inherit the first run's token-bucket end time (prepare resets the
    virtual-clock-relative state)."""
    db = fresh_db()
    reg, adm = qos_setup()
    svc = QueryService(db, agent, est=Estimator(db, db.stats), n_lanes=2,
                       policy="edf", tenants=reg, admission=adm)
    rows = []
    for _ in range(2):
        comps, _ = svc.run(qos_stream(job_workload))
        rows.append([(c.seq, c.admit_t, c.hook_budget) for c in comps])
    assert rows[0] == rows[1]


# ------------------------------------------------------------- isolation
def test_flood_cannot_evict_other_tenants_cache(job_workload, agent):
    victims = [fast_query(i) for i in range(3)]
    floods = [fast_query(100 + i) for i in range(24)]

    # solo pass: learn the victim's working-set signatures
    db = fresh_db()
    svc = QueryService(db, agent, est=Estimator(db, db.stats), n_lanes=2)
    svc.run_queries(victims * 2, seeds=range(6))
    sigs = list(svc.cache._entries.keys())
    ws = svc.cache.bytes
    assert sigs and ws > 0

    # the flood's partition has room for one of its 24 title scans, not
    # for all of them
    reg = TenantRegistry([TenantSpec("victim", cache_bytes=2 * ws),
                          TenantSpec("flood", cache_bytes=ws)])
    db = fresh_db()
    svc = QueryService(db, agent, est=Estimator(db, db.stats), n_lanes=2,
                       tenants=reg)
    stream = multi_tenant_stream([
        TenantTraffic("victim", victims, rate=4.0, n_queries=8, seed=3),
        TenantTraffic("flood", floods, rate=4.0, n_queries=24, seed=4)])
    _, stats = svc.run(stream)
    parts = svc.cache.partitions()
    # zero cross-tenant evictions BY CONSTRUCTION: the victim's partition
    # never evicted although the flood churned its own partition hard
    assert parts["victim"].stats.evictions == 0
    assert parts["flood"].stats.evictions > 0
    assert all(s in parts["victim"] for s in sigs)
    assert stats.per_tenant["victim"].cache["evictions"] == 0
    # the aggregate counters still add up
    agg = svc.cache.aggregate_stats()
    by_tenant = svc.cache.stats_by_tenant()
    assert agg["evictions"] == sum(d["evictions"]
                                   for d in by_tenant.values())
    # reset_stats reaches every partition (counters only: entries stay)
    svc.reset_stats()
    assert all(d["hits"] == 0 and d["misses"] == 0 and d["evictions"] == 0
               for d in svc.cache.stats_by_tenant().values())
    assert all(s in parts["victim"] for s in sigs)


def test_partition_invalidation_is_shared(job_workload, agent):
    """One delta fences EVERY tenant's stale entries (shared version tags):
    post-delta executions are correct in all partitions."""
    from repro.serve.deltas import DeltaBatch, apply_delta
    from repro.sql.executor import AdaptiveRun, run_adaptive
    from repro.sql.plans import syntactic_plan
    db = fresh_db()
    est = Estimator(db, db.stats)
    cache = PartitionedStageCache(default_bytes=32 << 20)
    db._stage_cache = cache
    q = fast_query(1)
    rows = {}
    for tenant in ("a", "b"):
        run = AdaptiveRun(db, q, syntactic_plan(q), est, max_hook_steps=0,
                          cache=cache.partition(tenant))
        assert run.start() is None
        rows[tenant] = [s.out_rows for s in run.result.stages]
    assert rows["a"] == rows["b"]
    apply_delta(db, DeltaBatch("title", n_append=1000, seed=9))
    assert cache.stats.invalidations == 1      # one shared O(1) counter
    ref = run_adaptive(db, q, syntactic_plan(q), est, reuse_stages=False)
    for tenant in ("a", "b"):
        run = AdaptiveRun(db, q, syntactic_plan(q), est, max_hook_steps=0,
                          cache=cache.partition(tenant))
        assert run.start() is None
        got = [s.out_rows for s in run.result.stages]
        assert got == [s.out_rows for s in ref.stages]
        assert got != rows[tenant]             # stale entries never served


def test_rate_limited_flood_cannot_starve_other_lanes(job_workload, agent):
    """A tenant flooding at t=0 occupies the lane FCFS; with QoS its token
    bucket spaces it out and fair-share tie-breaks favor the underserved
    tenant, so the other tenant's queries stop queueing behind the burst."""
    fast = fast_subset(job_workload)

    def build_stream():
        s = [Arrival(0.0, query=fast[i % 4], seed=i, tenant="flood")
             for i in range(8)]
        s += [Arrival(0.5 + i, query=fast[4 + i % 2], seed=100 + i,
                      tenant="light") for i in range(3)]
        return s

    def serve(admission):
        db = fresh_db()
        sched = LaneScheduler(db, Estimator(db, db.stats), agent,
                              n_lanes=1, policy="edf" if admission else
                              "async", admission=admission)
        comps = sched.run(build_stream())
        return {t: [c.queue_wait for c in comps if c.tenant == t]
                for t in ("flood", "light")}

    plain = serve(None)
    reg = TenantRegistry([TenantSpec("flood", rate=0.5, burst=1),
                          TenantSpec("light", weight=4.0)])
    adm = QoSAdmission(reg, predictor=None)
    fair = serve(adm)
    assert adm.n_deferred > 0
    # under FCFS the light tenant queues behind the whole burst; under
    # QoS each light query gets a lane promptly
    assert max(fair["light"]) < max(plain["light"])
    assert max(fair["light"]) < 2.0


# ---------------------------------------------------------------- fallback
def test_qos_off_bit_identical_to_plain_async(job_workload, agent):
    """Tenancy metadata + partitioned cache with NO admission policy (and
    the FCFS base policy) must serve bit-identically to the PR-2 path."""
    def serve(**kw):
        db = fresh_db()
        svc = QueryService(db, agent, est=Estimator(db, db.stats),
                           n_lanes=3, policy="async", **kw)
        comps, _ = svc.run(qos_stream(job_workload))
        return comps

    plain = serve()
    reg, _ = qos_setup()
    off = serve(tenants=reg)
    passthrough = serve(admission=AdmissionPolicy())

    # arrivals are copied per run: a stream that already went through a
    # QoS scheduler (deferral floors, stamped deadlines) must replay
    # through plain async untouched
    shared = qos_stream(job_workload)
    db = fresh_db()
    reg2, adm2 = qos_setup()
    QueryService(db, agent, est=Estimator(db, db.stats), n_lanes=3,
                 policy="edf", tenants=reg2, admission=adm2).run(shared)
    assert all(a.not_before == 0.0 for a in shared)
    db = fresh_db()
    svc = QueryService(db, agent, est=Estimator(db, db.stats), n_lanes=3,
                       policy="async")
    reused, _ = svc.run(shared)

    for other in (off, passthrough, reused):
        assert [c.seq for c in plain] == [c.seq for c in other]
        assert [c.finish_t for c in plain] == [c.finish_t for c in other]
        assert [c.admit_t for c in plain] == [c.admit_t for c in other]
        assert [c.lane for c in plain] == [c.lane for c in other]
        assert [c.traj.actions for c in plain] == \
            [c.traj.actions for c in other]


# -------------------------------------------------------------- scheduling
def test_edf_reorders_by_deadline(job_workload, agent):
    fast = fast_subset(job_workload)

    def build_stream():
        return [Arrival(0.0, query=fast[i], seed=i, deadline=dl)
                for i, dl in enumerate((30.0, 10.0, 20.0))]

    def order(policy):
        db = fresh_db()
        sched = LaneScheduler(db, Estimator(db, db.stats), agent,
                              n_lanes=1, policy=policy)
        comps = sched.run(build_stream())
        return [c.seq for c in sorted(comps, key=lambda c: c.admit_t)]

    assert order("async") == [0, 1, 2]          # FCFS: stream order
    assert order("edf") == [1, 2, 0]            # earliest deadline first


def test_degraded_budget_caps_hook_steps(job_workload, agent):
    """An admission-assigned hook budget really limits act_batch
    decisions: budget 1 -> at most one action, budget 0 -> none (the
    pure syntactic/AQE plan runs)."""
    reg = TenantRegistry([TenantSpec("t", slo=200.0)])   # severity 1.5
    adm = QoSAdmission(reg, predictor=FixedPredictor(),
                       ladder=DegradationLadder())
    db = fresh_db()
    sched = LaneScheduler(db, Estimator(db, db.stats), agent, n_lanes=1,
                          policy="edf", admission=adm)
    comps = sched.run([Arrival(0.0, query=straggler_query(), seed=0,
                               tenant="t")])
    assert len(comps) == 1
    c = comps[0]
    assert c.degraded and c.hook_budget == 1
    assert len(c.traj.actions) <= 1
    # severity 2.5 -> budget 0: no hook decisions at all
    reg0 = TenantRegistry([TenantSpec("t", slo=120.0)])
    adm0 = QoSAdmission(reg0, predictor=FixedPredictor(),
                        ladder=DegradationLadder())
    db = fresh_db()
    sched = LaneScheduler(db, Estimator(db, db.stats), agent, n_lanes=1,
                          policy="edf", admission=adm0)
    comps = sched.run([Arrival(0.0, query=straggler_query(), seed=0,
                               tenant="t")])
    assert comps[0].hook_budget == 0
    assert comps[0].traj.actions == []
