"""The served path's profile spans (`repro.spans`).

One small JOB chunk is served through `QueryService` under the JAX
profiler on the CPU; the `.xplane.pb` it writes is read back with
`ProfileData`, as a reader of a chip trace would (the spans land on the
host plane beside the device planes). The spans must name every layer,
nest as the program's call structure does, and carry counts that agree
with the program's own counters; and turning the profiler on must change
no decision, plan or latency.
"""
from __future__ import annotations

import glob
import os
import subprocess
import sys
from collections import Counter, defaultdict
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.serve.driver import open_loop_stream
import repro
from repro.serve.service import QueryService
from repro.spans import SPANS
from repro.sql import datagen, workloads
from repro.sql.cbo import Estimator
from scenarios import make_agent

N_QUERIES = 8
NAMES = {name for name, _ in SPANS}

# child span -> the spans it must lie inside (any one of them)
PARENTS = {
    "lqrs.exec.scan": ("lqrs.resume", "lqrs.admit"),
    "lqrs.exec.join": ("lqrs.resume", "lqrs.admit"),
    "lqrs.policy.feed": ("lqrs.policy",),
    "lqrs.policy.fetch": ("lqrs.policy",),
    "lqrs.policy": ("lqrs.decide",),
    "lqrs.encode": ("lqrs.decide",),
    "lqrs.decide": ("lqrs.tick",),
    "lqrs.admit": ("lqrs.tick",),
    "lqrs.apply": ("lqrs.tick",),
    "lqrs.resume": ("lqrs.tick",),
    "lqrs.finish": ("lqrs.tick",),
    "lqrs.tick": ("lqrs.serve",),
}


@pytest.fixture(scope="module")
def world():
    wl = workloads.make_workload("job", n_train=8, n_test_per_template=1,
                                 seed=7)
    stream = open_loop_stream(wl.test, rate=4.0, n_queries=N_QUERIES,
                              seed=3)
    return wl, make_agent(wl, seed=0), stream


def serve(world, trace_dir=None):
    """Serve the stream on a fresh database; returns (service,
    completions, the stage cache's hits during the run)."""
    _, agent, stream = world
    db = datagen.make_job_like(scale=0.05, seed=0)
    svc = QueryService(db, agent, est=Estimator(db, db.stats), n_lanes=4)
    h0 = svc.cache.stats.hits
    if trace_dir is None:
        comps, _ = svc.run(stream)
    else:
        with jax.profiler.trace(str(trace_dir)):
            comps, _ = svc.run(stream)
    return svc, comps, svc.cache.stats.hits - h0


def read_spans(trace_dir):
    """Every `lqrs.` event of the trace as (name, start, end, stats), by
    host thread."""
    from jax.profiler import ProfileData
    path, = glob.glob(str(Path(trace_dir) / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    data = ProfileData.from_file(path)
    by_line = defaultdict(list)
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("lqrs."):
                    by_line[(plane.name, line.name)].append(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    return by_line


@pytest.fixture(scope="module")
def traced(world, tmp_path_factory):
    trace_dir = tmp_path_factory.mktemp("profile")
    svc, comps, hits = serve(world, trace_dir)
    by_line = read_spans(trace_dir)
    assert len(by_line) == 1, "the served path runs on one host thread"
    spans, = by_line.values()
    return svc, comps, hits, sorted(spans, key=lambda s: (s[1], -s[2]))


def test_every_span_name_appears_and_no_other(traced):
    *_, spans = traced
    seen = {s[0] for s in spans}
    assert seen == NAMES


def test_spans_nest_as_the_calls_do(traced):
    *_, spans = traced
    by_name = defaultdict(list)
    for s in spans:
        by_name[s[0]].append(s)
    for name, own in by_name.items():
        # spans of one name never nest inside each other
        ends = [e for _, _, e, _ in own]
        starts = [s for _, s, _, _ in own]
        assert all(s >= e for s, e in zip(starts[1:], ends[:-1])), name
        for _, s, e, _ in own:
            assert any(ps <= s and e <= pe
                       for parent in PARENTS.get(name, ())
                       for _, ps, pe, _ in by_name[parent]) \
                or name == "lqrs.serve", (name, s, e)


def test_decide_spans_match_the_decide_batches(traced):
    svc, *_, spans = traced
    lanes = [s[3]["lanes"] for s in spans if s[0] == "lqrs.decide"]
    assert lanes == svc.scheduler.decide_sizes
    ticks = [s[3]["lanes"] for s in spans if s[0] == "lqrs.tick"]
    assert [n for n in ticks if n] == svc.scheduler.decide_sizes
    top, = [s for s in spans if s[0] == "lqrs.serve"]
    assert top[3]["queries"] == N_QUERIES


def test_finish_spans_match_the_completions(traced):
    _, comps, _, spans = traced
    finish = [s[3] for s in spans if s[0] == "lqrs.finish"]
    assert len(finish) == len(comps) == N_QUERIES
    assert sorted(f["seq"] for f in finish) == [c.seq for c in comps]
    decisions = sum(len(c.traj.actions) for c in comps)
    assert Counter(s[0] for s in spans)["lqrs.apply"] == decisions
    assert Counter(s[0] for s in spans)["lqrs.encode"] == decisions


def test_exec_span_hits_are_the_stage_cache_hits(traced):
    _, comps, hits, spans = traced
    execs = [s[3] for s in spans if s[0].startswith("lqrs.exec.")]
    assert all(set(x) >= {"rows", "hit"} and x["hit"] in (0, 1)
               for x in execs)
    assert sum(x["hit"] for x in execs) == hits
    joins = [s[3] for s in spans if s[0] == "lqrs.exec.join"]
    assert {x["method"] for x in joins} <= {"SMJ", "BHJ"}
    # the probe path is recorded on the joins the executor ran, only
    assert all(x["probe"] in ("unique", "dense", "sorted")
               for x in joins if not x["hit"])
    assert not any("probe" in x for x in joins if x["hit"])
    assert not all(x["hit"] for x in joins)
    # so are the key columns a join carried and those it left out
    missed = [x for x in joins if not x["hit"]]
    assert all(x["cols"] >= 0 and x["dropped"] >= 0 for x in missed)
    assert not any("cols" in x or "dropped" in x for x in joins if x["hit"])
    assert any(x["cols"] == 0 for x in missed)       # a query's last join
    assert any(x["dropped"] > 0 for x in missed)
    # and the row-index arrays it built: no right one for an output that
    # reads no column, as at a query's last join
    assert all(x["built"] in (0, 1, 2) for x in missed)
    assert all(x["built"] != 1 for x in missed if x["cols"] == 0)
    assert any(x["built"] == 0 for x in missed)
    assert not any("built" in x for x in joins if x["hit"])
    assert len(joins) >= sum(len(c.result.stages) for c in comps)


def test_policy_spans_carry_the_batch_shape(traced):
    svc, *_, spans = traced
    policy = [s[3] for s in spans if s[0] == "lqrs.policy"]
    assert len(policy) == len(svc.scheduler.decide_sizes)
    assert all(0 < p["nodes"] <= svc.agent._nodes for p in policy)


def test_the_profiler_changes_no_result(world, traced):
    _, on, _, _ = traced
    _, off, _ = serve(world)
    assert len(on) == len(off) == N_QUERIES
    for a, b in zip(on, off):
        assert a.seq == b.seq
        assert a.traj.actions == b.traj.actions
        assert a.traj.logps == b.traj.logps
        assert a.result.final_plan == b.result.final_plan
        assert a.result.latency == b.result.latency
        assert a.finish_t == b.finish_t


def test_policy_program_keeps_its_name(world):
    """Profile readers find the policy program on the device by the name
    XLA gives the jitted `act_batch` body."""
    _, agent, _ = world
    B, n, F = 4, agent._nodes, agent.meta.feat_dim
    args = (agent.actor, np.zeros((B, n, F), np.float32),
            np.zeros((B, n), np.int32), np.zeros((B, n), np.int32),
            np.ones((B, n), np.float32),
            np.ones((B, agent.space.d), np.float32),
            np.zeros((B, 2), np.uint32))
    text = agent._act_batch_jit.lower(*args, explore=False).as_text()
    assert text.splitlines()[0].startswith("module @jit_act_batch_fn ")


def test_the_sql_engine_loads_without_jax():
    """The executor's spans do not pull in the accelerator runtime: before
    JAX is loaded no profiler can run, and a span records nothing."""
    code = ("import sys, repro.sql.executor, repro.spans as s\n"
            "with s.span('lqrs.exec.scan') as sp:\n"
            "    sp.set_metadata(rows=1, hit=0)\n"
            "assert not sp.is_enabled()\n"
            "assert 'jax' not in sys.modules, 'jax loaded'\n")
    src = str(Path(repro.__path__[0]).parent)
    env = {**os.environ, "PYTHONPATH": src}
    subprocess.run([sys.executable, "-c", code], check=True, env=env)
