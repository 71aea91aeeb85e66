"""Benchmark entry point: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds the program under `src/`. It
stops with a non-zero exit and no result unless JAX's first device is a
TPU and there are as many chips as the cell asks for. Otherwise it sets
up the cell (data, workload, set-up training, warm-up of every shape the
window uses), measures a window of fixed work sized to last about
`--seconds` (`harness.window_units`), checks what the window produced
against the references, and prints as its last stdout line one JSON
object: `correct`, `attempted`, `failed`, `metrics`, `device`, with
`--trace 1` also `breakdown`, and last `checks`, each number compared
beside its limit. With `--trace 0` the metrics are the cell's end-to-end
metrics; with `--trace 1` (the profiler on during the window) its
per-layer metrics.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def say(*parts) -> None:
    print(*parts, flush=True)


def run(plan: dict, seed: int, seconds: float, trace: bool,
        t_process: float) -> dict:
    """Set up, measure and check one cell; returns the result object."""
    import jax
    import numpy as np
    from bench import checks, harness, trace_reduce, work

    devs = jax.devices()[:plan["cell"]["chips"]]
    dev = device_info(devs)
    cfg, traffic = plan["config"], plan["traffic"]
    say("device:", json.dumps(dev))
    clock = harness.CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(clock)

    world = harness.build_world(cfg, tape=3 if traffic["drive"] == "train"
                                else 0)
    trace_dir = None
    if trace:
        trace_dir = harness.OUT / "trace" / plan["cell"]["name"]
        shutil.rmtree(trace_dir, ignore_errors=True)
    drive = harness.DRIVES[traffic["drive"]]
    t_setup_end = [None]

    res = drive(world, cfg, traffic, seed, seconds, clock, trace_dir,
                on_start=lambda: t_setup_end.__setitem__(
                    0, time.perf_counter()))
    setup_s = t_setup_end[0] - t_process
    stats = [d.memory_stats() or {} for d in devs]
    dev["memory_peak_bytes"] = max(int(s.get("peak_bytes_in_use", 0))
                                   for s in stats)

    say("digest:", json.dumps({"data": world.digest,
                               "queries": res["query_digest"]}))
    say("setup:", json.dumps({**world.phases, "setup_s": setup_s,
                              **res["setup_compiles"]}))
    say("window:", json.dumps(res["summary"]))

    # ---- correctness, once the window has closed
    rec = world.rec
    lims = checks.limits()
    numbers = {}
    if res["drive"] == "serve":
        numbers.update(checks.answers(
            world.db, res["comps"], res["attempted"],
            np.random.default_rng(harness.sub_seed(seed, 3))))
    if rec.policy_calls:
        numbers.update(checks.policy_checks(rec.policy_calls, rec.actors,
                                            tie=lims["logp_err"]))
    elif res["attempted"] and not res["raised"]:
        raise harness.MissingLayer("the window made no policy call")
    if world.tape is not None:
        numbers.update(checks.update_gaps(
            checks.program_updates(world.tape),
            checks.replay(world.tape, cfg["ppo"], "highest"),
            world.tape.before))
    compared = {k: v for k, v in numbers.items() if k in lims}
    correct = checks.verdict(compared, lims) and res["raised"] == 0
    say("checked:", json.dumps({k: v for k, v in numbers.items()
                                if k not in lims}))

    # ---- metrics
    record = {**res, "setup_s": setup_s, "policy_calls": rec.policy_calls,
              "learn_s": rec.learn_s, "ppo_s": rec.ppo_s,
              "dims": harness.policy_dims(world.agent),
              "peak": None, "trace": None, "notes": []}
    out = {"correct": bool(correct), "attempted": int(res["attempted"]),
           "failed": int(res["failed"]), "metrics": {}, "device": dev}
    if trace:
        record["peak"] = harness.peaks_for(dev["kind"])
        record["trace"] = trace_reduce.reduce(trace_reduce.extract(
            str(trace_dir)))
        record["work"] = work
        dev["busy_s"] = record["trace"]["busy_s"]
        dev["window_s"] = record["trace"]["window_s"]
        wanted = plan["per_layer"]
    else:
        wanted = plan["end_to_end"]
    for m in wanted:
        value = harness.metric_reader(m["name"], per_layer=trace)(record)
        if value is not None:
            out["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    for note in record["notes"]:
        say("note:", note)
    if trace:
        out["breakdown"] = {"device_ops": record["trace"]["device_ops"],
                            "idle_gaps": record["trace"]["idle_gaps"]}
        say("idle by span:", json.dumps(record["trace"]["idle_by_span"]))
        self_s = record["trace"]["self_s"]
        say("program spans:", json.dumps({
            "self_s": self_s, "counts": record["trace"]["span_counts"],
            "self_share_of_window": sum(self_s.values())
            / record["trace"]["window_s"]}))
    out["checks"] = {k: {"value": v, "limit": lims[k]}
                     for k, v in compared.items()}
    for k, v in compared.items():
        print(f"check {k}: {v} (limit {lims[k]})", file=sys.stderr)
    sys.stderr.flush()
    return out


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench/run.py: the program (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    # the TPU runtime's logs stay in the checkout, not in a fixed /tmp path
    logs = ROOT / "bench_out" / "tpu_logs"
    logs.mkdir(parents=True, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", str(logs))
    import jax
    from bench import harness
    from repro.jax_cache import enable_compile_cache

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench/run.py: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 3
    plan = harness.cell_plan(harness.load_spec(ROOT), args.workload, ROOT)
    if len(devs) < plan["cell"]["chips"]:
        print(f"bench/run.py: {args.workload} needs "
              f"{plan['cell']['chips']} chips, JAX found {len(devs)}",
              file=sys.stderr)
        return 3
    say("compile cache:", enable_compile_cache())
    out = run(plan, args.seed, args.seconds, bool(args.trace), T_PROCESS)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
