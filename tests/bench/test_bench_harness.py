"""The benchmark's harness on the CPU at a tiny scale: every mix runs, the
result line carries what BENCHMARK.json names, cells are found by name,
and the run refuses to report without a TPU."""
import json
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from benchtest import ROOT, RUN, plan_for, tiny
from bench import harness


@pytest.fixture(scope="module")
def serve_run():
    plan = tiny(harness.cell_plan(harness.load_spec(), "job.serve"))
    return plan, RUN.run(plan, 2 ** 31 + 7, 1.0, False, time.perf_counter())


def test_serve_line_matches_benchmark(serve_run):
    plan, out = serve_run
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True
    assert out["attempted"] >= 16 and out["failed"] <= out["attempted"]
    assert set(out["metrics"]) == {m["name"] for m in plan["end_to_end"]}
    for m in plan["end_to_end"]:
        got = out["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    for name, c in out["checks"].items():
        assert c["value"] <= c["limit"], name


@pytest.mark.parametrize("traffic,metrics", [
    ("online", {"served_qps", "exec_s_per_query", "setup_s"}),
    ("train", {"train_episodes_per_s", "setup_s"})])
def test_other_mixes_run(traffic, metrics):
    out = RUN.run(plan_for("job", traffic), 5, 1.0, False,
                  time.perf_counter())
    assert out["correct"] is True and out["attempted"] > 0
    assert set(out["metrics"]) == metrics
    assert all(m["value"] > 0 for m in out["metrics"].values())


def test_stack_config_serves(capsys):
    plan = plan_for("stack", "serve")
    out = RUN.run(plan, 3, 1.0, False, time.perf_counter())
    assert out["correct"] is True and out["attempted"] >= 16
    # the answer check's own seconds are printed, not compared
    checked = [ln for ln in capsys.readouterr().out.splitlines()
               if ln.startswith("checked: ")]
    assert json.loads(checked[-1][len("checked: "):])["answers_s"] > 0
    assert "answers_s" not in out["checks"]


def test_same_seed_same_inputs():
    """Every run serves the same queries, with the same PRNG keys, at the
    same arrivals, in the pool's order, whatever its seed."""
    plan = tiny(harness.cell_plan(harness.load_spec(), "job.serve"))
    cfg, mix = plan["config"], plan["traffic"]
    a, b = (harness.pool_streams(cfg, mix, n) for n in (3, 5))
    key = lambda ss: [[(x.t, x.query.name, x.seed) for x in s]  # noqa: E731
                      for s in ss]
    assert key(a) == key(b)[:3]
    t = [x.t for s in a for x in s]
    assert t == sorted(t) and len(set(t)) == len(t) == 3 * 16
    assert len({x.seed for s in a for x in s}) == 3 * 16


@pytest.mark.parametrize("seconds,nominal,units", [
    (30.0, 3.0, 10), (31.0, 3.0, 11), (1.0, 3.0, 1), (51.0, 6.0, 9)])
def test_window_work_is_fixed(seconds, nominal, units):
    assert harness.window_units(seconds, nominal) == units


def test_no_tpu_no_result(tmp_path):
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(tmp_path)}
    cmd = [sys.executable, "bench/run.py", "--workload", "job.serve",
           "--seed", "1", "--seconds", "1", "--trace", "0"]
    got = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                         text=True, timeout=120)
    assert got.returncode != 0 and "metrics" not in got.stdout
    # a directory that holds only BENCHMARK.json and the benchmark's files
    alone = tmp_path / "alone"
    shutil.copytree(ROOT / "bench", alone / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", alone)
    got = subprocess.run(cmd, cwd=alone, env=env, capture_output=True,
                         text=True, timeout=120)
    assert got.returncode != 0 and "metrics" not in got.stdout


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks_for("a chip nobody listed")


def test_new_cell_found_by_name(tmp_path):
    """A configuration over another of the program's template sets
    (`extjob`), a mix and a metric, each added as new files only, run as
    a cell of their own."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    spec = harness.load_spec()
    conf = json.loads((ROOT / "bench/configs/job.json").read_text())
    (tmp_path / "bench/configs/jobsmall.json").write_text(
        json.dumps(dict(conf, name="jobsmall", scale=0.02,
                        workload=dict(conf["workload"], name="extjob"))))
    mix = json.loads((ROOT / "bench/traffic/serve.json").read_text())
    (tmp_path / "bench/traffic/burst.json").write_text(
        json.dumps(dict(mix, rate_qps=8.0)))
    (tmp_path / "bench/layer_metrics/chunks_served.py").write_text(
        "def read(record):\n    return float(record['summary']['chunks'])\n")
    spec["configs"].append({"name": "jobsmall", "source": "test",
                            "file": "bench/configs/jobsmall.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "jobsmall.burst", "config": "jobsmall",
                              "traffic": "burst", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "chunks_served", "unit": "chunks",
                              "better": "higher", "source": "host_clock",
                              "layer": "scheduler", "moves": "served_qps",
                              "workloads": ["jobsmall.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    plan = harness.cell_plan(harness.load_spec(tmp_path), "jobsmall.burst",
                             tmp_path)
    assert plan["config"]["scale"] == 0.02
    assert plan["traffic"]["rate_qps"] == 8.0
    assert [m["name"] for m in plan["per_layer"]] == ["chunks_served"]
    read = harness.metric_reader("chunks_served", True, tmp_path)
    assert read({"summary": {"chunks": 3}}) == 3.0
    plan = tiny(plan)
    plan["end_to_end"] = [m for m in spec["end_to_end"]
                          if m["name"] == "served_qps"]
    out = RUN.run(plan, 2 ** 32 + 5, 1.0, False, time.perf_counter())
    assert out["correct"] is True and out["attempted"] == 16
    assert out["metrics"]["served_qps"]["value"] > 0
