"""The trace reduction on traces whose busy, idle, per-program and span
times are known: ones written out by hand, one recorded on a TPU v5 lite
(three policy calls inside a window), and one the program's spans write
on the CPU while it serves."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace_reduce  # noqa: E402

DATA = ROOT / "bench" / "testdata"

# window [1000, 11000] ns on one device. Ops [1000,3000] and [2000,4000]
# overlap (busy 3000), [6000,7000] (busy 1000) and [10500,12000], clipped
# to [10500,11000] (busy 500): busy 4500 ns of 10000, idle gaps 2000 ns
# ([4000,6000], inside the policy call [3500,6500]) and 3500 ns
# ([7000,10500], inside the chunk only). Programs: two runs of
# jit_act_batch_fn (1000 + 2000 ns), the second outside any policy call,
# and one run of jit_other that starts after the window.
HAND = {
    "ops": {"0": [["a", 1000, 2000], ["b", 2000, 2000], ["c", 6000, 1000],
                  ["d", 10500, 1500]]},
    "programs": {"0": [["jit_act_batch_fn", 4000, 1000],
                       ["jit_act_batch_fn", 7000, 2000],
                       ["jit_other", 11500, 100]]},
    "spans": [["bench.window", 1000, 10000], ["bench.chunk", 1000, 10000],
              ["bench.policy_call", 3500, 3000]],
    "program_spans": [],
}


# The same window with the program's spans of one query served in it
# (ns; self time in brackets): lqrs.serve [1000, 12000] clipped to
# [1000, 11000] (100) holds two ticks. The first, [1100, 7000] (50), holds
# the admission [1100, 2000] (200) with a scan [1200, 1500] (300, a
# stage-cache hit) and a join [1500, 1900] (400); the decision
# [2000, 6450] (1150) with an encode [2000, 2500] (500) and the policy
# call [3600, 6400] (100) of feed [3600, 4000] (400) and fetch
# [4000, 6300] (2300); an apply [6450, 6600] (150); a resume
# [6600, 6950] (150) with a join [6700, 6900] (200). The second,
# [7000, 11500] clipped to [7000, 11000] (3500), holds the finish
# [7000, 7500] (500). A serve span before the window counts for nothing.
SERVED = dict(HAND, program_spans=[
    ["lqrs.serve", 0, 500, {"queries": 16}],
    ["lqrs.serve", 1000, 11000, {"queries": 4}],
    ["lqrs.tick", 1100, 5900, {"lanes": 1}],
    ["lqrs.admit", 1100, 900, {"seq": 0}],
    ["lqrs.exec.scan", 1200, 300, {"rows": 10, "hit": 1}],
    ["lqrs.exec.join", 1500, 400, {"rows": 5, "hit": 0, "method": "SMJ",
                                   "probe": "dense"}],
    ["lqrs.decide", 2000, 4450, {"lanes": 1}],
    ["lqrs.encode", 2000, 500, {"seq": 0}],
    ["lqrs.policy", 3600, 2800, {"nodes": 16}],
    ["lqrs.policy.feed", 3600, 400, {}],
    ["lqrs.policy.fetch", 4000, 2300, {}],
    ["lqrs.apply", 6450, 150, {"seq": 0}],
    ["lqrs.resume", 6600, 350, {"seq": 0}],
    ["lqrs.exec.join", 6700, 200, {"rows": 3, "hit": 0, "method": "BHJ",
                                   "probe": "unique"}],
    ["lqrs.tick", 7000, 4500, {"lanes": 0}],
    ["lqrs.finish", 7000, 500, {"seq": 0}],
])
SELF_NS = {"lqrs.serve": 100, "lqrs.tick": 3550, "lqrs.admit": 200,
           "lqrs.exec.scan": 300, "lqrs.exec.join": 600,
           "lqrs.decide": 1150, "lqrs.encode": 500, "lqrs.policy": 100,
           "lqrs.policy.feed": 400, "lqrs.policy.fetch": 2300,
           "lqrs.apply": 150, "lqrs.resume": 150, "lqrs.finish": 500}


def test_hand_trace():
    r = trace_reduce.reduce(HAND)
    assert r["window_s"] == pytest.approx(10000e-9)
    assert r["busy_s"] == pytest.approx(4500e-9)
    assert r["programs"] == {"jit_act_batch_fn": {
        "runs": 2, "seconds": pytest.approx(3000e-9)}}
    assert r["in_policy_calls"] == {"jit_act_batch_fn": {
        "runs": 1, "seconds": pytest.approx(1000e-9)}}
    assert r["idle_by_span"] == {
        "bench.policy_call": pytest.approx(2000e-9),
        "bench.chunk": pytest.approx(3500e-9)}
    assert [g[0] for g in r["idle_gaps"]] == ["bench.chunk",
                                              "bench.policy_call"]
    assert r["idle_gaps"][0][1] == pytest.approx(3500e-9)


def test_program_span_self_time_and_counts():
    r = trace_reduce.reduce(SERVED)
    assert r["self_s"] == {n: pytest.approx(v * 1e-9)
                           for n, v in SELF_NS.items()}
    # a parent's self time is its (clipped) duration less its children's
    assert r["self_s"]["lqrs.serve"] == pytest.approx(
        (10000 - 5900 - 4000) * 1e-9)
    assert r["self_s"]["lqrs.policy"] == pytest.approx(
        (2800 - 400 - 2300) * 1e-9)
    # the spans cover the window, so their self times add up to it
    assert sum(r["self_s"].values()) == pytest.approx(r["window_s"])
    assert r["span_counts"]["lqrs.serve"] == {"spans": 1, "queries": 4}
    assert r["span_counts"]["lqrs.tick"] == {"spans": 2, "lanes": 1}
    assert r["span_counts"]["lqrs.exec.join"] == {"spans": 2, "rows": 8,
                                                  "hit": 0}
    assert r["span_counts"]["lqrs.policy.feed"] == {"spans": 1}
    # the benchmark's own numbers are those of the hand trace
    plain = trace_reduce.reduce(HAND)
    assert plain["self_s"] == {} and plain["span_counts"] == {}
    for key in ("busy_s", "window_s", "programs", "in_policy_calls"):
        assert r[key] == plain[key]


def test_idle_gaps_name_the_innermost_program_span():
    """The gap inside the policy call lies in its fetch, and the one
    after the query's finish in the scheduler's last tick."""
    r = trace_reduce.reduce(SERVED)
    assert r["idle_by_span"] == {
        "lqrs.policy.fetch": pytest.approx(2000e-9),
        "lqrs.tick": pytest.approx(3500e-9)}
    assert [g[0] for g in r["idle_gaps"]] == ["lqrs.tick",
                                              "lqrs.policy.fetch"]


def test_spans_that_do_not_nest_are_refused():
    bad = dict(HAND, program_spans=[["lqrs.tick", 1000, 2000, {}],
                                    ["lqrs.admit", 2500, 1000, {}]])
    with pytest.raises(ValueError, match="without nesting"):
        trace_reduce.reduce(bad)


def test_program_name_drops_the_fingerprint():
    assert trace_reduce.program_name(
        "jit_act_batch_fn(344907724036923763)") == "jit_act_batch_fn"


def test_window_must_be_one_span():
    bad = dict(HAND, spans=[s for s in HAND["spans"]
                            if s[0] != "bench.window"])
    with pytest.raises(ValueError):
        trace_reduce.reduce(bad)


def test_recorded_trace():
    """Three policy calls of the JOB agent (batch 8) in a window, recorded
    on a TPU v5 lite. The device ops do not overlap there, so busy time is
    the plain sum of their durations inside the window."""
    got = trace_reduce.extract(str(DATA / "trace_small.xplane.pb"))
    want = trace_reduce.load(str(DATA / "trace_small.json"))
    assert {k: got[k] for k in want} == want
    assert got["program_spans"] == []   # recorded before the program had
    #                                     spans of its own
    lo, hi = trace_reduce.window_of(got)
    ops = sorted((s, d) for _, s, d in got["ops"]["0"] if lo <= s < hi)
    assert all(s1 + d1 <= s2 for (s1, d1), (s2, _) in zip(ops, ops[1:]))
    busy = sum(d for _, d in ops) * 1e-9
    runs = got["programs"]["0"]
    assert [n for n, _, _ in runs] == ["jit_act_batch_fn"] * 3
    calls = [(s, s + d) for n, s, d in got["spans"]
             if n == "bench.policy_call"]
    assert all(any(a <= s <= b for a, b in calls) for _, s, _ in runs)

    r = trace_reduce.reduce(got)
    assert r["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert r["busy_s"] == pytest.approx(busy)
    assert r["in_policy_calls"]["jit_act_batch_fn"]["runs"] == 3
    assert r["programs"]["jit_act_batch_fn"]["seconds"] == pytest.approx(
        sum(d for _, _, d in runs) * 1e-9)
    idle = sum(r["idle_by_span"].values())
    assert idle == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["device_ops"][0][0] == "jit_act_batch_fn"


def test_serving_layer_readers_on_a_trace():
    """Every per-layer metric BENCHMARK.json lists for a serving cell reads
    a number from a record built on the hand trace with the program's
    spans (one policy call, one run of the policy program inside it)."""
    import numpy as np
    from bench import harness, work
    F, d, n = 26, 172, 7
    mask = np.zeros((2, 64), np.float32)
    mask[0, 1:n + 1] = 1.0
    call = {"s": 0.003, "lanes": 1, "real": np.array([True, False]),
            "inputs": (np.zeros((2, 64, F), np.float32),
                       np.zeros((2, 64), np.int32),
                       np.zeros((2, 64), np.int32), mask,
                       np.ones((2, d), np.float32))}
    record = {"drive": "serve", "policy_calls": [call], "learn_s": [],
              "comps": [object()] * 4, "window_s": 2.0, "online": False,
              "decide_sizes": [1, 2, 3],
              "trace": trace_reduce.reduce(SERVED),
              "work": work, "peak": harness.peaks_for("TPU v5 lite"),
              "dims": {"feat": F, "hidden": 96, "head_hidden": 96,
                       "actions": d, "param_bytes": 354_000},
              "notes": []}
    spec = harness.load_spec()
    cell = spec["workloads"][0]["name"]
    for m in harness.cell_plan(spec, cell)["per_layer"]:
        value = harness.metric_reader(m["name"], per_layer=True)(record)
        assert value is not None and 0 < value < float("inf"), m["name"]
    read = {m: harness.metric_reader(m, True)(record) for m in (
        "policy_feed_ms", "policy_fetch_ms", "exec_ms_per_query",
        "sched_ms_per_query", "encode_ms_per_decision", "stage_hit_pct",
        "decide_batch_mean")}
    assert read == {"policy_feed_ms": pytest.approx(400e-6),
                    "policy_fetch_ms": pytest.approx(2300e-6),
                    "exec_ms_per_query": pytest.approx(900e-6 / 4),
                    "sched_ms_per_query": pytest.approx(5800e-6 / 4),
                    "encode_ms_per_decision": pytest.approx(500e-6),
                    "stage_hit_pct": pytest.approx(100 / 3),
                    "decide_batch_mean": 2.0}
    # a window that served queries and holds no executor span fails
    record["trace"] = trace_reduce.reduce(HAND)
    with pytest.raises(harness.MissingLayer):
        harness.metric_reader("exec_ms_per_query", True)(record)


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """Eight JOB queries served by the program under the profiler on the
    CPU, inside a `bench.window` span; the trace as `extract` reads it."""
    import jax
    from bench.harness import annotate
    from repro.core.agent import AgentConfig, AqoraAgent
    from repro.core.encoding import WorkloadMeta
    from repro.serve.driver import open_loop_stream
    from repro.serve.service import QueryService
    from repro.sql import datagen, workloads
    from repro.sql.cbo import Estimator
    wl = workloads.make_workload("job", n_train=8, n_test_per_template=1,
                                 seed=7)
    db = datagen.make_job_like(scale=0.05, seed=0)
    agent = AqoraAgent(WorkloadMeta.from_workload(wl), AgentConfig(), seed=0)
    svc = QueryService(db, agent, est=Estimator(db, db.stats), n_lanes=4)
    stream = open_loop_stream(wl.test, rate=4.0, n_queries=8, seed=3)
    trace_dir = tmp_path_factory.mktemp("profile")
    with jax.profiler.trace(str(trace_dir)):
        with annotate("bench.window"):
            comps, _ = svc.run(stream)
    return svc, comps, trace_reduce.extract(str(trace_dir))


def test_extract_keeps_the_program_spans_and_counts(cpu_trace):
    from repro.spans import SPANS
    svc, comps, got = cpu_trace
    spans = got["program_spans"]
    assert {s[0] for s in spans} == {name for name, _ in SPANS}
    assert all(len(s) == 4 and isinstance(s[3], dict) for s in spans)
    assert [s[3]["lanes"] for s in spans if s[0] == "lqrs.decide"] == \
        svc.scheduler.decide_sizes
    serve, = [s[3] for s in spans if s[0] == "lqrs.serve"]
    assert serve == {"queries": 8}
    assert sorted(s[3]["seq"] for s in spans if s[0] == "lqrs.finish") == \
        sorted(c.seq for c in comps)
    execs = [s[3] for s in spans if s[0].startswith("lqrs.exec.")]
    assert all(x["hit"] in (0, 1) and x["rows"] >= 0 for x in execs)
    assert all(x["probe"] in ("unique", "dense", "sorted") for x in execs
               if "probe" in x)
    assert [s[0] for s in got["spans"]] == ["bench.window"]


def test_program_time_on_a_served_trace(cpu_trace):
    """On the program's own spans: the serve span's self time is its
    duration less its ticks', the self times cover the window, and the
    readers of the program's spans read a positive number from them."""
    from bench import harness
    svc, comps, got = cpu_trace
    lo, hi = trace_reduce.window_of(got)
    r = trace_reduce.reduce(dict(got, ops={"0": [["op", lo, 1.0]]}))
    spans = got["program_spans"]
    (_, s0, d0, _), = [s for s in spans if s[0] == "lqrs.serve"]
    ticks = sum(d for n, _, d, _ in spans if n == "lqrs.tick")
    assert r["self_s"]["lqrs.serve"] == pytest.approx((d0 - ticks) * 1e-9)
    assert 0.95 * r["window_s"] <= sum(r["self_s"].values()) <= r["window_s"]
    assert r["span_counts"]["lqrs.finish"]["spans"] == len(comps) == 8
    assert r["span_counts"]["lqrs.exec.join"]["hit"] == sum(
        s[3]["hit"] for s in spans if s[0] == "lqrs.exec.join")
    assert set(r["idle_by_span"]) <= set(r["self_s"])
    record = {"drive": "serve", "comps": comps, "trace": r}
    for name in ("exec_ms_per_query", "sched_ms_per_query",
                 "encode_ms_per_decision", "policy_feed_ms",
                 "policy_fetch_ms", "stage_hit_pct"):
        value = harness.metric_reader(name, per_layer=True)(record)
        assert 0 < value < float("inf"), name
