"""Lifelong-learning loop invariants: prioritized replay math, adaptive
curriculum promotion, harvested trajectories == offline rollouts (same
PPO gradients), bit-reproducible serving with the learner ON, and the
policy-store gate (corrupted candidate rejected, serving continues on the
prior version; shadow mode never swaps; rollback restores).

Scenario builders (fresh dbs, seeded agents) live in tests/scenarios.py."""
import numpy as np
import pytest

import jax

from scenarios import fresh_db, make_agent

from repro.checkpoint import (agent_state, copy_tree, install_agent_state,
                              params_finite)
from repro.core.rollout import Trajectory, rollout
from repro.learn import (AdaptiveCurriculum, Experience, PolicyStore,
                         ReplayBuffer, TrajectoryHarvester, make_online_loop)
from repro.serve.scheduler import Arrival, LaneScheduler
from repro.serve.service import QueryService
from repro.sql.cbo import Estimator
from repro.sql.cluster import ClusterModel


def _exp(seq, name, latency, versions, tables=("title",), failed=False):
    t = Trajectory()
    t.actions = [0]
    return Experience(seq=seq, query_name=name, traj=t, latency=latency,
                      failed=failed, finish_t=float(seq), tables=tables,
                      versions=dict(versions))


# -------------------------------------------------------------- replay
def test_replay_priorities_fresh_regret_and_failure_dominate():
    rb = ReplayBuffer(capacity=8, recency_decay=1.0, fresh_boost=4.0,
                      regret_scale=1.0, fail_boost=2.0)
    rb.add(_exp(0, "q", 1.0, {"title": 0}))        # stale after the delta
    rb.add(_exp(1, "q", 1.0, {"title": 1}))        # fresh, zero regret
    rb.add(_exp(2, "q", 3.0, {"title": 1}))        # fresh, 2x regret
    rb.add(_exp(3, "q", 3.0, {"title": 1}, failed=True))
    p = rb.priorities({"title": 1})
    assert p[1] > p[0]                 # freshness beats stale
    assert p[2] > p[1]                 # regret adds weight
    assert p[3] > p[2]                 # failure boosts further
    assert p[1] == pytest.approx(4.0) and p[2] == pytest.approx(12.0)
    assert p[0] == pytest.approx(1.0) and p[3] == pytest.approx(24.0)


def test_replay_recency_decay_and_eviction():
    rb = ReplayBuffer(capacity=3, recency_decay=0.5, fresh_boost=1.0,
                      regret_scale=0.0)
    for i in range(5):
        rb.add(_exp(i, f"q{i}", 1.0, {}))
    assert len(rb) == 3 and rb.n_evicted == 2
    assert [e.seq for e in rb.all()] == [2, 3, 4]
    p = rb.priorities({})
    assert p[0] == pytest.approx(0.25) and p[2] == pytest.approx(1.0)


def test_replay_sampling_is_deterministic():
    rb = ReplayBuffer(capacity=16)
    for i in range(10):
        rb.add(_exp(i, f"q{i % 3}", 1.0 + i, {"title": i % 2}))
    a = rb.sample(4, np.random.default_rng(7), {"title": 1})
    b = rb.sample(4, np.random.default_rng(7), {"title": 1})
    assert [e.seq for e in a] == [e.seq for e in b]
    assert len(a) == 4
    assert rb.sample(99, np.random.default_rng(0), {})  # clamps to size


# ---------------------------------------------------------- curriculum
class _FakeComp:
    def __init__(self, failed, latency):
        self.result = type("R", (), {"failed": failed, "latency": latency})()


def test_adaptive_curriculum_promotes_on_success_window():
    cur = AdaptiveCurriculum(window=4, promote_success=0.75, min_dwell=4)
    assert cur.stage == 1
    for _ in range(3):
        cur.observe(_FakeComp(False, 1.0))
    assert cur.stage == 1              # window not yet full
    cur.observe(_FakeComp(False, 1.0))
    assert cur.stage == 2 and cur.promotions == [4]
    # failures hold the next promotion back
    for _ in range(8):
        cur.observe(_FakeComp(True, 1.0))
    assert cur.stage == 2
    for _ in range(4):
        cur.observe(_FakeComp(False, 1.0))
    assert cur.stage == 3
    for _ in range(8):                 # stage 3 is terminal
        cur.observe(_FakeComp(False, 1.0))
    assert cur.stage == 3


def test_adaptive_curriculum_demotes_on_failure_spike():
    cur = AdaptiveCurriculum(window=4, promote_success=0.75, min_dwell=4,
                             demote_success=0.5)
    for _ in range(8):
        cur.observe(_FakeComp(False, 1.0))
    assert cur.stage == 3 and cur.promotions == [4, 8]
    # drift hits: 3 of 4 in the window fail -> demote one stage
    for _ in range(2):
        cur.observe(_FakeComp(False, 1.0))
    for _ in range(3):
        cur.observe(_FakeComp(True, 1.0))
    assert cur.stage == 2 and cur.demotions == [13]
    # keeps failing -> demote to 1, never below
    for _ in range(8):
        cur.observe(_FakeComp(True, 1.0))
    assert cur.stage == 1
    for _ in range(8):
        cur.observe(_FakeComp(True, 1.0))
    assert cur.stage == 1
    # recovery re-earns the stages
    for _ in range(8):
        cur.observe(_FakeComp(False, 1.0))
    assert cur.stage == 3


def test_adaptive_curriculum_latency_ceiling():
    cur = AdaptiveCurriculum(window=2, promote_success=0.5, min_dwell=2,
                             promote_p50=1.0)
    for _ in range(6):
        cur.observe(_FakeComp(False, 5.0))
    assert cur.stage == 1              # succeeds but too slow
    for _ in range(2):
        cur.observe(_FakeComp(False, 0.5))
    assert cur.stage == 2


# ------------------------------------------------- harvest == offline
def test_harvested_trajectories_match_offline_gradients(job_workload):
    """Acceptance (c): trajectories captured from the serving scheduler,
    replayed through ppo_update_batch, produce the same params as an
    offline agent updated on serial rollouts of the same episodes."""
    db = fresh_db(scale=0.05)
    est = Estimator(db, db.stats)
    serve_agent = make_agent(job_workload, seed=11)
    offline_agent = make_agent(job_workload, seed=11)

    qs = job_workload.test[:5]
    seeds = [101, 102, 103, 104, 105]
    harv = TrajectoryHarvester()
    sched = LaneScheduler(db, est, serve_agent, n_lanes=2, explore=True,
                          policy="async")
    harv.attach(sched)
    sched.run([Arrival(0.4 * i, query=q, seed=s)
               for i, (q, s) in enumerate(zip(qs, seeds))])
    assert harv.n_seen == 5
    exps = harv.replay.all()
    assert [e.seq for e in exps] == sorted(e.seq for e in exps)

    offline = [rollout(db, q, est, offline_agent, stage=3, explore=True,
                       key=s) for q, s in zip(qs, seeds)]
    for e, t in zip(exps, [t for t in offline if t.actions]):
        assert e.traj.actions == t.actions and e.traj.rewards == t.rewards

    serve_agent.ppo_update_batch([e.traj for e in exps])
    offline_agent.ppo_update_batch(offline)
    for a, b in zip(jax.tree_util.tree_leaves(agent_state(serve_agent)),
                    jax.tree_util.tree_leaves(agent_state(offline_agent))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


# ------------------------------------------------------- reproducibility
def test_online_serving_bit_reproducible_with_learner_on(job_workload,
                                                         tmp_path):
    """Acceptance (a): same seed => bit-identical completions, updates,
    swaps and curriculum promotions with the learner running."""
    def run(tag):
        db = fresh_db(scale=0.05)
        est = Estimator(db, db.stats)
        agent = make_agent(job_workload, seed=0)
        store = PolicyStore(tmp_path / f"ps_{tag}", job_workload.test[:2])
        h, l = make_online_loop(
            agent, store=store, update_every=3, sample_size=3,
            gate_every=2, seed=5,
            curriculum=AdaptiveCurriculum(window=4, min_dwell=4))
        svc = QueryService(db, agent, est=est, n_lanes=2, policy="async",
                           explore=True, hooks=[h, l])
        qs = job_workload.train[:6]
        rng = np.random.default_rng(9)
        stream = [Arrival(0.5 * i, query=qs[i % len(qs)],
                          seed=int(rng.integers(2 ** 31)))
                  for i in range(12)]
        comps, _ = svc.run(stream)
        return comps, l

    c1, l1 = run("a")
    c2, l2 = run("b")
    assert [c.traj.actions for c in c1] == [c.traj.actions for c in c2]
    assert [c.finish_t for c in c1] == [c.finish_t for c in c2]
    assert [c.result.latency for c in c1] == [c.result.latency for c in c2]
    np.testing.assert_array_equal(
        np.concatenate([c.traj.logps for c in c1]),
        np.concatenate([c.traj.logps for c in c2]))
    s1, s2 = l1.stats.as_dict(), l2.stats.as_dict()
    s1.pop("host_seconds"), s2.pop("host_seconds")
    assert s1 == s2
    assert l1.curriculum.promotions == l2.curriculum.promotions
    assert [g["accepted"] for g in l1.store.gate_log] == \
        [g["accepted"] for g in l2.store.gate_log]


def test_shadow_learning_serves_bit_identically_to_frozen(job_workload,
                                                         tmp_path):
    """The whole loop running with its store in shadow mode (harvest,
    updates, probe gates on the service's stage cache) changes no
    completion: it serves exactly what the frozen policy serves."""
    qs = job_workload.train[:6]
    rng = np.random.default_rng(9)
    stream = [Arrival(0.5 * i, query=qs[i % len(qs)],
                      seed=int(rng.integers(2 ** 31))) for i in range(12)]

    def serve(hooks):
        db = fresh_db(scale=0.05)
        svc = QueryService(db, agent, est=Estimator(db, db.stats),
                           n_lanes=2, hooks=hooks)
        comps, _ = svc.run(stream)
        return comps, svc

    agent = make_agent(job_workload, seed=0)
    frozen, _ = serve([])
    store = PolicyStore(tmp_path / "ps", job_workload.test[:2],
                        mode="shadow")
    h, l = make_online_loop(agent, store=store, update_every=3,
                            sample_size=3, gate_every=2, seed=5)
    shadow, svc = serve([h, l])
    assert [c.traj.actions for c in shadow] == \
        [c.traj.actions for c in frozen]
    assert [c.finish_t for c in shadow] == [c.finish_t for c in frozen]
    assert l.stats.updates > 0 and store.gate_log
    assert not any(g["swapped"] for g in store.gate_log)
    assert store.cache is svc.cache            # probes reuse its stages


# ------------------------------------------------------------- the gate
def _nan_corrupt(agent):
    agent.actor = jax.tree_util.tree_map(lambda x: x * np.nan, agent.actor)


def test_gate_rejects_corrupted_candidate_and_serving_continues(
        job_workload, tmp_path):
    """Acceptance (b): a corrupted candidate never swaps in; the serving
    agent keeps its prior params and keeps serving."""
    db = fresh_db(scale=0.05)
    est = Estimator(db, db.stats)
    cluster = ClusterModel()
    serving = make_agent(job_workload, seed=0)
    store = PolicyStore(tmp_path / "ps", job_workload.test[:2])
    store.commit(serving, step=0)

    cand = make_agent(job_workload, seed=1)
    install_agent_state(cand, agent_state(serving))
    _nan_corrupt(cand)
    assert not params_finite(cand)
    before = copy_tree(agent_state(serving))

    rec = store.evaluate_and_maybe_swap(serving, cand, db=db, est=est,
                                        cluster=cluster, step=1)
    assert not rec["accepted"] and "non-finite" in rec["reason"]
    assert store.serving_step == 0 and len(store.versions) == 1
    for a, b in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(agent_state(serving))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # serving continues on the prior version
    traj = rollout(db, job_workload.test[0], est, serving, stage=3,
                   explore=False, cluster=cluster)
    assert np.isfinite(traj.result.latency)


def test_gate_accepts_equal_candidate_and_shadow_never_swaps(
        job_workload, tmp_path):
    db = fresh_db(scale=0.05)
    est = Estimator(db, db.stats)
    cluster = ClusterModel()
    serving = make_agent(job_workload, seed=0)
    cand = make_agent(job_workload, seed=1)
    install_agent_state(cand, agent_state(serving))

    shadow = PolicyStore(tmp_path / "shadow", job_workload.test[:2],
                         mode="shadow")
    rec = shadow.evaluate_and_maybe_swap(serving, cand, db=db, est=est,
                                         cluster=cluster, step=1)
    assert rec["accepted"] and not rec["swapped"] and not shadow.versions

    gate = PolicyStore(tmp_path / "gate", job_workload.test[:2])
    rec = gate.evaluate_and_maybe_swap(serving, cand, db=db, est=est,
                                       cluster=cluster, step=1)
    assert rec["accepted"] and rec["swapped"]
    assert gate.serving_step == 1 and len(gate.versions) == 1


def test_policy_store_rollback_restores_committed_version(job_workload,
                                                          tmp_path):
    db = fresh_db(scale=0.05)
    agent = make_agent(job_workload, seed=0)
    store = PolicyStore(tmp_path / "ps", [])
    store.commit(agent, step=0)
    committed = copy_tree(agent_state(agent))
    _nan_corrupt(agent)
    assert not params_finite(agent)
    assert store.rollback(agent) == 0
    assert params_finite(agent)
    for a, b in zip(jax.tree_util.tree_leaves(committed),
                    jax.tree_util.tree_leaves(agent_state(agent))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------- harvester
def test_harvester_skips_empty_trajectories():
    rb = ReplayBuffer()
    h = TrajectoryHarvester(rb)

    class _Sched:
        db = type("D", (), {"table_version": staticmethod(lambda n: 0)})()
        on_complete = []
    h.attach(_Sched())

    class _Rel:
        table = "title"

    class _Q:
        name = "q0"
        relations = (_Rel(),)
    traj = Trajectory()
    res = type("R", (), {"latency": 1.0, "failed": False})()
    comp = type("C", (), {"seq": 0, "query": _Q(), "traj": traj,
                          "result": res, "finish_t": 1.0})()
    h._on_complete(comp)
    assert h.n_empty == 1 and len(rb) == 0
    traj.actions = [1]
    h._on_complete(comp)
    assert h.n_harvested == 1 and len(rb) == 1
    assert rb.all()[0].tables == ("title",)
