"""The online SLO watchdog (serve.obs.monitor/anomaly/rca): detectors
are deterministic streaming state machines with warmup/cooldown, the
plan ledger holds the swap counterfactual, a served drift outage yields
a detected incident with the RIGHT root cause, monitor-on with alerts
unwired is completion-bit-identical, and the wired alert path actually
heals (alert-driven re-ANALYZE un-arms the stale-stats trap)."""
import json

import numpy as np
import pytest

from scenarios import fast_query, fresh_db, qos_setup, qos_stream, trap_query

from repro.core.encoding import WorkloadMeta
from repro.serve.deltas import DeltaBatch
from repro.serve.obs import (AlertHooks, CusumDetector, DetectorBank,
                             EwmaDetector, Incident, MonitorConfig,
                             PlanLedger, SloMonitor, Tracer)
from repro.serve.obs.rca import Hypothesis, attribute
from repro.serve.scheduler import Arrival
from repro.serve.service import QueryService
from repro.sql.cbo import Estimator
from repro.sql.cluster import ClusterModel
from repro.sql.workloads import Workload


# --------------------------------------------------------------- detectors
def test_ewma_detector_warmup_spike_and_cooldown():
    det = EwmaDetector(alpha=0.25, z=4.0, min_n=5, cooldown=3,
                       direction="high")
    for i in range(5):                        # warmup: never alerts
        assert det.observe(float(i), 10.0 + 0.1 * (i % 2)) is None
    base = det.mean
    a = det.observe(5.0, 50.0)                # spike
    assert a is not None and a.kind == "ewma" and a.direction == "high"
    assert a.value == 50.0 and a.score > 4.0
    # the spike was NOT folded: the baseline still reflects ~10
    assert det.mean == base
    # cooldown mutes, but folds — a durable shift becomes the new normal
    for t in (6.0, 7.0, 8.0):
        assert det.observe(t, 50.0) is None
    assert det.mean > base
    # direction is respected: a drop is not "high"
    low = EwmaDetector(min_n=3, direction="high")
    for i in range(6):
        low.observe(float(i), 10.0)
    assert low.observe(9.0, -100.0) is None
    # ... but a "low" detector fires on it
    lo = EwmaDetector(min_n=3, direction="low")
    for i in range(6):
        lo.observe(float(i), 10.0)
    got = lo.observe(9.0, -100.0)
    assert got is not None and got.direction == "low"


def test_cusum_detector_catches_slow_drift_and_resets():
    det = CusumDetector(alpha=0.1, k=0.5, h=4.0, min_n=4, cooldown=2,
                        min_sigma=0.5, direction="high")
    for i in range(8):
        assert det.observe(float(i), 0.0) is None
    # a level shift one sigma up: never z-alertable, but S accumulates
    alerts = []
    for i in range(40):
        a = det.observe(10.0 + i, 1.0)
        if a is not None:
            alerts.append(a)
    assert alerts and alerts[0].kind == "cusum"
    # S reset on alert: the next alert needs re-accumulation (cooldown 2,
    # and the folding baseline adapts, so alerts THIN OUT over time)
    if len(alerts) > 1:
        assert alerts[1].t - alerts[0].t > 2


def test_detector_bank_routes_by_prefix_and_isolates_series():
    bank = DetectorBank({"p99": lambda: EwmaDetector(min_n=3, z=3.0,
                                                     min_sigma=0.1)})
    for i in range(6):
        bank.observe("p99[a]", float(i), 1.0)
        bank.observe("p99[b]", float(i), 1.0)
        bank.observe("unwatched", float(i), 1.0)
    a = bank.observe("p99[a]", 6.0, 100.0)
    assert a is not None and a.metric == "p99[a]"
    # tenant b's baseline is independent — no cross-talk, no alert
    assert bank.observe("p99[b]", 6.0, 1.0) is None
    # unknown prefixes are ignored, not errors
    assert bank.observe("unwatched", 6.0, 1e9) is None
    assert [x.metric for x in bank.anomalies] == ["p99[a]"]
    bank.reset()
    assert bank.anomalies == [] and bank.detectors == {}


# ------------------------------------------------------------- plan ledger
def test_plan_ledger_regression_counterfactual():
    led = PlanLedger(band_width=1)
    band = (("cast_info", 0),)
    for lat in (1.0, 1.2, 0.9):
        led.observe(1, "q7", band, lat, False)
    led.observe(2, "q7", band, 10.0, True)
    reg = led.regression(2, "q7", band)
    assert reg is not None and reg["same_band"]
    assert reg["prior_step"] == 1
    assert reg["ratio"] == pytest.approx(10.0 / np.mean([1.0, 1.2, 0.9]),
                                         rel=1e-3)
    # no prior step -> no counterfactual; unseen key -> None
    assert led.regression(1, "q7", band) is None
    assert led.regression(2, "q9", band) is None
    # prior stats below min_n don't count
    led.observe(1, "q8", band, 1.0, False)
    led.observe(2, "q8", band, 9.0, False)
    assert led.regression(2, "q8", band, min_n=2) is None
    # a different band still serves as an (off-band) counterfactual
    band2 = (("cast_info", 1),)
    led.observe(2, "q7", band2, 10.0, False)
    reg2 = led.regression(2, "q7", band2)
    assert reg2 is not None and not reg2["same_band"]
    rows = led.rows()
    assert {r["template"] for r in rows} == {"q7", "q8"}
    assert rows == json.loads(json.dumps(rows))
    led.reset()
    assert len(led) == 0


# ------------------------------------------------------------ rca gating
def test_rca_causes_are_event_gated():
    """No swap event -> no policy_swap hypothesis, however regressed the
    ledger looks; a quiet log leaves only the unknown floor."""
    rec = {"seq": 0, "tenant": "a", "template": "q", "t": 10.0,
           "arrival_t": 9.0, "latency": 1.0, "failed": False,
           "failure_kind": "", "fail_kinds": (), "attempts": 1,
           "recovered": False, "step": 2, "band": (),
           "phases": {"queue": 0.2, "execute": 0.8, "retry": 0.0,
                      "hedge": 0.0}}
    hyps = attribute(tenant="a", metric_label="p99", window=[rec],
                     baseline=[], events=[], ledger=None)
    assert [h.cause for h in hyps] == ["unknown"]

    class Ev:
        def __init__(self, kind, t, attrs):
            self.kind, self.t, self.attrs = kind, t, attrs

    hyps = attribute(tenant="a", metric_label="p99", window=[rec],
                     baseline=[],
                     events=[Ev("policy_swap", 9.5,
                                {"from_step": 1, "to_step": 2})],
                     ledger=None)
    assert hyps[0].cause == "policy_swap" and "v2" in hyps[0].summary
    assert hyps[-1].cause == "unknown"      # floor always present
    assert all(h.as_dict() == json.loads(json.dumps(h.as_dict()))
               for h in hyps)


# ----------------------------------------- served outage: detect + attribute
_TRAP_CLUSTER = ClusterModel(materialize_cap=1_500_000, timeout=60.0,
                             oom_charge="detect", oom_spill_penalty=5.0)
_GROWTH_X = 24
_DRIFT_AT = 12


def _watch_cfg():
    return MonitorConfig(window=8, min_warm=4, min_n=5, cooldown=4,
                         merge_gap=8, lookback=10, baseline_max=48)


def _drift_queries():
    return ([trap_query(i, 1940 + 5 * i) for i in range(3)],
            [fast_query(i) for i in range(5)])


def _replan_agent():
    """Stats-DRIVEN planner over the scenario's templates: on the stale
    catalog it walks into the blown join; fresh stats un-arm the trap
    (what the alert path exploits)."""
    from repro.baselines import CboReplanAgent
    traps, fasts = _drift_queries()
    wl = Workload(name="watchdog", max_tables=3, train=traps + fasts,
                  test=[])
    return CboReplanAgent(WorkloadMeta.from_workload(wl), max_steps=3)


def _drift_world():
    """The drift scenario's stale-stats shape: movie_info shrunk young, the
    catalog ANALYZEd post-shrink — in sync until the growth delta lands,
    after which every trap OOMs under the cap until a re-ANALYZE."""
    from repro.sql.catalog import analyze
    from repro.serve.deltas import apply_delta
    db = fresh_db(scale=0.06, seed=0)
    apply_delta(db, DeltaBatch("movie_info", delete_frac=0.9, seed=7))
    db.stats = analyze(db, rng=np.random.default_rng(0))
    return db, Estimator(db, db.stats)


def _drift_stream(db, n=36, rate=2.0, seed=11):
    rng = np.random.default_rng(seed)
    traps, fasts = _drift_queries()
    mi_rows = db.table("movie_info").nrows       # post-shrink
    t, out = 0.0, []
    for i in range(n):
        t += float(rng.exponential(1.0 / rate))
        q = traps[(i // 3) % 3] if i % 3 == 0 else fasts[i % 5]
        out.append(Arrival(t, query=q, seed=int(rng.integers(2 ** 31)),
                           deadline=t + 30.0))
        if i + 1 == _DRIFT_AT:
            out.append(Arrival(t, delta=DeltaBatch(
                "movie_info", n_append=_GROWTH_X * mi_rows, seed=999)))
    return out


def _drift_serve(*, monitor=None, hooks=(), n_lanes=2):
    db, est = _drift_world()
    stream = _drift_stream(db)
    svc = QueryService(db, _replan_agent(), est=est, n_lanes=n_lanes,
                       cluster=_TRAP_CLUSTER, hooks=list(hooks),
                       monitor=monitor)
    comps, stats = svc.run(stream)
    return comps, stats, svc, stream


def test_monitor_detects_and_attributes_drift_outage():
    mon = SloMonitor(config=_watch_cfg())
    comps, stats, svc, stream = _drift_serve(monitor=mon)
    t_drift = next(a.t for a in stream if a.delta is not None)
    assert any(c.result.failed for c in comps), "trap must be armed"

    # one record per completion (in FINISH order — the monitor is an
    # on_complete hook); phases partition each latency exactly
    by_seq = {c.seq: c for c in comps}
    assert sorted(r["seq"] for r in mon.records) == sorted(by_seq)
    for r in mon.records:
        c = by_seq[r["seq"]]
        assert sum(r["phases"].values()) == pytest.approx(c.latency,
                                                          abs=1e-9)
    assert len(mon.ledger) > 0

    # detected: an incident opens after the delta lands, and RCA blames
    # drift on the grown table — not the (absent) swap/faults/load causes
    incs = [i for i in mon.incidents if i.t_open >= t_drift]
    assert incs, "post-drift outage must be detected"
    inc = incs[0]
    assert inc.closed                      # finalize() sealed it
    assert inc.top is not None and inc.top.cause == "stats_drift"
    assert "movie_info" in inc.top.evidence.get("tables", ())
    causes = [h.cause for h in inc.hypotheses]
    assert "policy_swap" not in causes and "fault_burst" not in causes

    # the tracer's event log carries the full lifecycle (report renders
    # from the JSONL alone) and the flight recorder snapped the incident
    tracer = svc.scheduler.obs
    kinds = [e.kind for e in tracer.events]
    for k in ("anomaly", "incident_open", "incident_rca",
              "incident_close"):
        assert k in kinds
    assert any(d["reason"] == f"incident:{inc.id}"
               for d in tracer.flight.dumps)
    closes = [e for e in tracer.events if e.kind == "incident_close"
              and e.attrs["id"] == inc.id]
    assert closes and closes[0].attrs["top_cause"] == "stats_drift"

    # watchdog counters surface through the service stats
    assert stats.n_incidents == len(mon.incidents) > 0
    assert stats.n_anomalies == sum(mon.n_anomalies.values()) > 0


def test_monitor_on_is_bit_identical_and_reset_clears():
    def sig(comps):
        return [(c.seq, c.admit_t, c.finish_t, c.lane, c.attempts,
                 c.result.failed) for c in comps]

    off, _, _, _ = _drift_serve()
    mon = SloMonitor(config=_watch_cfg())
    on, _, svc, _ = _drift_serve(monitor=mon)
    assert sig(off) == sig(on)             # the watchdog only watches
    assert mon.records and mon.incidents

    svc.reset_stats(clear_entries=True)
    assert mon.records == [] and mon.incidents == []
    assert len(mon.ledger) == 0 and mon.bank.detectors == {}
    assert mon.totals() == (0, 0) and mon._open is None


def test_tenant_stats_carry_watchdog_counters(job_workload, agent):
    """Pinned JSON surface: per-tenant n_anomalies/n_incidents ride the
    TenantStats blob and agree with the monitor's own counters."""
    db = fresh_db(scale=0.05, seed=0)
    reg, adm = qos_setup()
    mon = SloMonitor(config=MonitorConfig(window=6, min_warm=3, min_n=4,
                                          cooldown=3, merge_gap=6,
                                          lookback=8))
    svc = QueryService(db, agent, est=Estimator(db, db.stats), n_lanes=2,
                       policy="edf", tenants=reg, admission=adm,
                       monitor=mon)
    _, stats = svc.run(qos_stream(job_workload))
    d = stats.as_dict()
    assert d == json.loads(json.dumps(d))
    assert {"n_anomalies", "n_incidents"} <= set(d)
    assert d["n_anomalies"] == sum(mon.n_anomalies.values())
    assert d["n_incidents"] == sum(mon.n_incidents.values())
    for name, td in d["per_tenant"].items():
        assert {"n_anomalies", "n_incidents"} <= set(td)
        assert (td["n_anomalies"], td["n_incidents"]) == \
            mon.tenant_counts(name)
    assert sum(td["n_incidents"] for td in d["per_tenant"].values()) <= \
        d["n_incidents"]                   # global-series incidents extra


# ------------------------------------------------------------ alert hooks
class _Sink:
    """Duck-typed breaker/drift stand-in: `ret` mimics the real return
    (breaker -> tripped bool, drift -> tuple of scheduled tables)."""

    def __init__(self, ret=True):
        self.calls, self.ret = [], ret

    def note_external_evidence(self, *a, **kw):
        self.calls.append((a, kw))
        return self.ret


class _Comp:
    seq, finish_t = 7, 42.0


def _incident(cause, score, evidence=None):
    inc = Incident(1, "b", "p99[b]", 10.0, 5)
    inc.hypotheses = [Hypothesis(cause, score, f"{cause} it was",
                                 evidence or {})]
    return inc


def test_alert_hooks_route_once_and_respect_min_score():
    brk, drf = _Sink(), _Sink(ret=("cast_info",))
    seen = []
    hooks = AlertHooks(breaker=brk, drift=drf, on_incident=seen.append,
                       min_score=2.0)
    inc = _incident("policy_swap", 3.0)
    hooks.fire(inc, _Comp())
    hooks.fire(inc, _Comp())               # same incident: sinks fire once
    assert len(brk.calls) == 1 and brk.calls[0][0] == (7, "policy_swap it was")
    assert drf.calls == [] and len(seen) == 1
    assert hooks.log == [{"sink": "breaker", "incident": 1,
                          "tripped": True}]

    inc2 = _incident("stats_drift", 2.5, {"tables": ["cast_info"]})
    hooks.fire(inc2, _Comp())
    assert len(drf.calls) == 1
    assert drf.calls[0][0][0] == ["cast_info"]
    assert drf.calls[0][1]["reason"] == "stats_drift it was"

    weak = AlertHooks(breaker=_Sink(), drift=_Sink(ret=()), min_score=2.0)
    weak.fire(_incident("policy_swap", 1.0), _Comp())
    assert weak.breaker.calls == [] and weak.log == []
    # causes route to their matching sink only
    hooks3 = AlertHooks(breaker=_Sink(), drift=_Sink(ret=()))
    hooks3.fire(_incident("hot_tenant", 9.0), _Comp())
    assert hooks3.breaker.calls == [] and hooks3.drift.calls == []


def test_breaker_external_evidence_is_noop_without_watched_swap(tmp_path):
    from repro.learn.policy_store import PolicyStore
    from repro.serve.recover import PolicyBreaker

    store = PolicyStore(str(tmp_path), probe=[], mode="gate")
    brk = PolicyBreaker(store, object(), window=8, min_post=3)
    assert brk.note_external_evidence(5, "spurious") is False
    assert brk.trips == []


def test_alert_driven_reanalyze_heals_the_drift_outage():
    """End-to-end actuation: monitor detects the stale-stats outage,
    attributes it to movie_info, and the wired DriftController schedules
    an alert re-ANALYZE barrier — after which the stats-driven planner
    stops walking into the trap. Unwired, the traps fail to stream end."""
    from repro.serve.drift import DriftController

    unwired, _, _, _ = _drift_serve(monitor=SloMonitor(config=_watch_cfg()))

    ctl = DriftController()                # RefreshPolicy("never"): the
    alerts = AlertHooks(drift=ctl)         # alert path is the ONLY actuator
    mon = SloMonitor(config=_watch_cfg(), alerts=alerts)
    wired, _, svc, _ = _drift_serve(monitor=mon, hooks=[ctl])

    assert any(e["sink"] == "drift" and "movie_info" in e["tables"]
               for e in alerts.log)
    labels = [lbl for _, lbl in svc.scheduler.task_log]
    assert any(lbl.startswith("re-analyze[alert]:") and "movie_info" in lbl
               for lbl in labels)
    assert ctl.stats.refresh_events >= 1

    fails = lambda cs: sum(c.result.failed for c in cs)
    assert fails(unwired) > fails(wired)   # the alert path healed traffic
    # the tail is clean: after the refresh barrier no trap fails again
    t_fix = next(t for t, lbl in svc.scheduler.task_log
                 if lbl.startswith("re-analyze[alert]:"))
    assert fails([c for c in wired if c.admit_t > t_fix]) == 0


# ------------------------------------------ refresh policies vs the drift
def _churned_drift_stream(db):
    """`_drift_stream` with a movie_keyword churn delta after every sixth
    post-drift query: tables a refresh policy may or may not pay for."""
    mk = db.table("movie_keyword").nrows
    out, n, drifted = [], 0, False
    for a in _drift_stream(db):
        out.append(a)
        if a.delta is not None:
            drifted = True
        elif drifted:
            n += 1
            if n % 6 == 0:
                out.append(Arrival(a.t, delta=DeltaBatch(
                    "movie_keyword", n_append=mk // 50, delete_frac=0.02,
                    seed=1000 + n)))
    return out


def test_refresh_policies_unarm_the_drift_trap_within_budget():
    """Under "never" the stale catalog keeps walking into the trap after
    movie_info grows; "always" and "threshold" refresh at the barrier, so
    no trap fails and the post-drift tail collapses; "budgeted" does the
    same while paying for the one big refresh and not for the churn."""
    from repro.serve.drift import DriftController, RefreshPolicy
    db, _ = _drift_world()
    big = _TRAP_CLUSTER.scan_time(
        (1 + _GROWTH_X) * db.table("movie_info").bytes() * 0.05) + \
        _TRAP_CLUSTER.stage_overhead
    policies = {
        "never": RefreshPolicy("never"),
        "always": RefreshPolicy("always"),
        "threshold": RefreshPolicy("threshold", threshold=1.0),
        "budgeted": RefreshPolicy("budgeted", threshold=0.25,
                                  budget_s=1.5 * big)}
    arms = {}
    for name, policy in policies.items():
        db, est = _drift_world()
        stream = _churned_drift_stream(db)
        ctl = DriftController(policy=policy)
        svc = QueryService(db, _replan_agent(), est=est, n_lanes=2,
                           cluster=_TRAP_CLUSTER, hooks=[ctl])
        comps, _ = svc.run(stream)
        t_drift = next(a.t for a in stream if a.delta is not None)
        post = [c.latency for c in comps if c.arrival_t > t_drift]
        arms[name] = (sum(c.result.failed for c in comps),
                      float(np.percentile(post, 99)),
                      ctl.stats.analyze_modeled_s)
    fails, p99, _ = arms["never"]
    assert fails > 0                           # the trap is armed
    for name in ("always", "threshold", "budgeted"):
        assert arms[name][0] == 0 and arms[name][1] < p99 / 5
    assert 0 < arms["budgeted"][2] < arms["always"][2]


# ----------------------------------------- seeded incidents: detect + RCA
_SEEDED_CFG = dict(window=12, min_warm=6, min_n=8, cooldown=6, merge_gap=10,
                   lookback=16, baseline_max=96)
_LAG_BOUND = 24                # completions from injection to detection


def _sig(comps):
    return [(c.seq, c.finish_t, c.latency, c.result.failed, c.failure_kind,
             c.attempts) for c in comps]


def _monitor(monitored, **kw):
    return SloMonitor(config=MonitorConfig(**_SEEDED_CFG), **kw) \
        if monitored else None


def _scn_fault_burst(monitored, tmp_path):
    """Stats in sync (no drift), an injector confined to stream positions
    28-43; the retry ladder absorbs most of the burst."""
    from repro.serve.recover import (FaultInjector, RecoveryManager,
                                     RetryPolicy)
    db, est = _drift_world()
    traps, fasts = _drift_queries()
    rng = np.random.default_rng(31)
    t, stream = 0.0, []
    for i in range(64):
        t += float(rng.exponential(1.0))
        q = traps[(i // 5) % 3] if i % 5 == 0 else fasts[i % 5]
        stream.append(Arrival(t, query=q, seed=int(rng.integers(2 ** 31)),
                              deadline=t + 30.0))
    injector = FaultInjector(seed=23, p_crash=0.02, p_transient=0.3,
                             p_slow=0.1, slow_factor=(8.0, 48.0),
                             window=(28, 44))
    mgr = RecoveryManager(injector=injector,
                          retry=RetryPolicy(max_attempts=3, backoff=0.5))
    mon = _monitor(monitored)
    svc = QueryService(db, _replan_agent(), est=est, n_lanes=4,
                       cluster=_TRAP_CLUSTER, recovery=mgr, monitor=mon)
    comps, _ = svc.run(stream)
    return comps, mon, stream[28].t


def _scn_hot_tenant(monitored, tmp_path):
    """Two lanes: tenant a at a steady 0.8 q/s, tenant b at 0.1 q/s until
    a flood of 24 arrivals at 6 q/s from t=55 backs the queue up."""
    from repro.baselines import CboReplanAgent
    db, est = _drift_world()
    fast = [fast_query(i) for i in range(8)]
    rng = np.random.default_rng(17)

    def arrival(t, q, tenant):
        return Arrival(t, query=q, seed=int(rng.integers(2 ** 31)),
                       deadline=t + 30.0, tenant=tenant)

    stream, t, i = [], 0.0, 0
    while t < 90.0:
        t += float(rng.exponential(1.0 / 0.8))
        stream.append(arrival(t, fast[i % 8], "a"))
        i += 1
    t = rng.exponential(1.0 / 0.1)
    while t < 55.0:
        stream.append(arrival(t, fast[(i + 3) % 8], "b"))
        i += 1
        t += float(rng.exponential(1.0 / 0.1))
    t = 55.0
    for j in range(24):
        t += float(rng.exponential(1.0 / 6.0))
        stream.append(arrival(t, fast[j % 8], "b"))
    stream.sort(key=lambda a: a.t)
    wl = Workload(name="flood", max_tables=3, train=fast, test=[])
    mon = _monitor(monitored)
    svc = QueryService(db, CboReplanAgent(WorkloadMeta.from_workload(wl),
                                          max_steps=3),
                       est=est, n_lanes=2, cluster=ClusterModel(
                           timeout=60.0, oom_charge="detect",
                           oom_spill_penalty=10.0), monitor=mon)
    comps, _ = svc.run(stream)
    return comps, mon, 55.0


def _pin_head(agent, idx):
    """Pin the actor head to one action: argmax then picks `idx` wherever
    it is legal."""
    import jax.numpy as jnp
    head = dict(agent.actor["head"])
    head["w2"] = jnp.zeros_like(head["w2"])
    b2 = np.zeros(head["b2"].shape, np.float32)
    b2[idx] = 50.0
    head["b2"] = jnp.asarray(b2)
    agent.actor = {**agent.actor, "head": head}


def _scn_policy_swap(monitored, tmp_path):
    """An incumbent pinned to cbo-replan (traps re-planned on fresh stats)
    is hot-swapped after completion 23 for one pinned to noop (the traps'
    fact-fact join blows the cap)."""
    from repro.core.agent import AgentConfig, AqoraAgent
    from repro.learn.policy_store import PolicyStore
    from repro.serve.deltas import apply_delta
    from repro.serve.scheduler import LaneScheduler
    from repro.sql.catalog import analyze
    db = fresh_db(scale=0.06)
    apply_delta(db, DeltaBatch("cast_info", n_append=120_000, seed=999))
    db.stats = analyze(db, rng=np.random.default_rng(0))
    traps = [trap_query(i, 1896 + i) for i in range(5)]
    fast = [fast_query(i) for i in range(6)]
    wl = Workload(name="swap", max_tables=3, train=traps + fast, test=[])
    agent = AqoraAgent(WorkloadMeta.from_workload(wl),
                       AgentConfig(max_steps=1), seed=0)
    _pin_head(agent, 0)                        # action 0 == cbo(1)
    store = PolicyStore(tmp_path / f"ps_{monitored}", probe=[], mode="gate")
    store.commit(agent, 1)
    sched = LaneScheduler(db, Estimator(db, db.stats), agent, n_lanes=4,
                          cluster=ClusterModel(
                              timeout=60.0, oom_charge="detect",
                              oom_spill_penalty=10.0,
                              materialize_cap=400_000))
    mon = _monitor(monitored, store=store)
    if monitored:
        tracer = Tracer()
        tracer.attach(sched)
        store.obs = tracer                     # commits land in the log
        mon.attach(sched)
    rng = np.random.default_rng(41)
    t, stream = 0.0, []
    for i in range(60):
        t += float(rng.exponential(0.5))
        q = traps[(i // 2) % 5] if i % 2 == 0 else fast[i % 6]
        stream.append(Arrival(t, query=q, seed=int(rng.integers(2 ** 31)),
                              deadline=t + 30.0))

    def swapper(comp):
        if comp.seq == 23 and store.serving_step == 1:
            _pin_head(agent, agent.space.noop_idx)
            store.commit(agent, 2)
    sched.on_complete.insert(0, swapper)
    comps = sched.run(stream)
    if monitored:
        mon.finalize()
    return comps, mon, next(c.finish_t for c in comps if c.seq == 23)


_SCENARIOS = {"policy_swap": _scn_policy_swap,
              "fault_burst": _scn_fault_burst,
              "hot_tenant": _scn_hot_tenant}


@pytest.mark.parametrize("cause", sorted(_SCENARIOS))
def test_monitor_detects_and_attributes_seeded_incident(cause, tmp_path):
    """A seeded incident is detected within a bounded number of
    completions after its injection, its incident's top cause is the
    injected one, and the watched run serves what an unwatched run
    serves."""
    import bisect
    comps, mon, inject_t = _SCENARIOS[cause](True, tmp_path)
    hit = next(((inc, a) for inc in mon.incidents for a in inc.anomalies
                if a.t >= inject_t - 1e-9), None)
    assert hit is not None, "the injected incident must be detected"
    inc, a = hit
    recs = [r["t"] for r in mon.records]
    lag = bisect.bisect_right(recs, a.t) - bisect.bisect_left(recs, inject_t)
    assert 0 <= lag <= _LAG_BOUND
    assert inc.top is not None and inc.top.cause == cause
    off, _, _ = _SCENARIOS[cause](False, tmp_path)
    assert _sig(off) == _sig(comps)
