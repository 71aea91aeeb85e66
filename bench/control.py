"""Readings of the numbers `correct` compares, for the program and for the
control, on several seeds of one cell in one process.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> ...

For each seed it runs the cell's window as `bench/run.py` does (set-up
once, then one window per seed) and prints one JSON line: the program's
readings (`logp_err`, `greedy_misses`, `wrong_answers`,
`missing_answers`) and the control's - the float32 reference computed at
the next
precision down (`high`: the backend's three-pass bfloat16 matmul, and
`bf16x3`: the same product written out) in the program's place. The
limits in `bench/limits.json` are set between the two. It needs a TPU,
as the cell does.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(plan, seeds, seconds, controls=("high", "bf16x3")):
    import jax
    import numpy as np
    from bench import checks, harness

    cfg, traffic = plan["config"], plan["traffic"]
    clock = harness.CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(clock)
    world = harness.build_world(cfg, tape=3 if traffic["drive"] == "train"
                                else 0)
    tie = checks.limits()["logp_err"]
    drive = harness.DRIVES[traffic["drive"]]
    for seed in seeds:
        res = drive(world, cfg, traffic, seed, seconds, clock)
        rec = world.rec
        line = {"seed": seed, "decisions": sum(c["lanes"]
                                               for c in rec.policy_calls)}
        if res["drive"] == "serve":
            line.update(checks.answers(
                world.db, res["comps"], res["attempted"],
                np.random.default_rng(harness.sub_seed(seed, 3))))
        line.update(checks.policy_checks(rec.policy_calls, rec.actors,
                                         tie=tie))
        for c in controls:
            got = checks.policy_checks(rec.policy_calls, rec.actors,
                                       tie=tie, control=c)
            line[f"control_{c}"] = got["logp_err"]
            line[f"control_{c}_greedy_misses"] = got["greedy_misses"]
        tape = world.tape
        if tape is not None and tape.after_last is not None:
            ref = checks.replay(tape, cfg["ppo"], "highest")
            line.update(checks.update_gaps(checks.program_updates(tape), ref,
                                           tape.before))
            for c in controls:
                got = checks.update_gaps(checks.replay(tape, cfg["ppo"], c),
                                         ref, tape.before)
                line.update({f"control_{c}_{k}": v for k, v in got.items()
                             if k != "leaves_left_out"})
            # the next seed's window starts with a tape of its own
            world.tape = harness.UpdateTape(world.agent._agent, tape.n)
            world.tape.fn = tape.fn
        yield line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    # the TPU runtime's logs stay in the checkout, not in a fixed /tmp path
    logs = ROOT / "bench_out" / "tpu_logs"
    logs.mkdir(parents=True, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", str(logs))
    import jax
    from bench import harness
    from repro.jax_cache import enable_compile_cache
    if jax.devices()[0].platform != "tpu":
        print("bench/control.py: needs a TPU", file=sys.stderr)
        return 3
    enable_compile_cache()
    plan = harness.cell_plan(harness.load_spec(ROOT), args.workload, ROOT)
    for line in readings(plan, args.seeds, args.seconds):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
