"""The trace reduction on traces whose busy, idle and per-program times are
known: one written out by hand, and one recorded on a TPU v5 lite (three
policy calls inside a window)."""
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
}


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
    assert got == trace_reduce.load(str(DATA / "trace_small.json"))
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
    a number from a record built on the hand trace (one policy call, one
    run of the policy program inside it)."""
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
              "decide_sizes": [1, 2, 3], "trace": trace_reduce.reduce(HAND),
              "work": work, "peak": harness.peaks_for("TPU v5 lite"),
              "dims": {"feat": F, "hidden": 96, "head_hidden": 96,
                       "actions": d, "param_bytes": 354_000},
              "notes": []}
    spec = harness.load_spec()
    cell = spec["workloads"][0]["name"]
    for m in harness.cell_plan(spec, cell)["per_layer"]:
        value = harness.metric_reader(m["name"], per_layer=True)(record)
        assert value is not None and 0 < value < float("inf"), m["name"]
    assert harness.metric_reader("policy_call_ms", True)(record) == \
        pytest.approx(3.0)
    assert harness.metric_reader("decide_batch_mean", True)(record) == 2.0
