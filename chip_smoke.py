#!/usr/bin/env python3
"""Bring-up smoke: the re-optimizer's train-and-serve path on one TPU chip.

Drives the paper's system once through its normal entry points, at the
agent's full width (`AgentConfig()`) and at `JOB_SPEC`'s full documented
scale (`make_job_like(scale=1.0)`):

  1. device  - stop unless JAX's first device is a TPU (no CPU fallback);
  2. train   - `train_agent`: lockstep rollouts plus the jitted, donating
               PPO update; both losses finite, actor parameters moved;
  3. serve   - `QueryService` on async lanes with the online learning loop
               (harvest, background PPO on a clone, gated hot-swap); every
               arrival completes, and every successful result has the row
               count Spark's default plan gives on a separately generated
               copy of the same tables;
  4. kernel  - an agent with the fused Pallas tree-CNN and the same
               parameters: compiled by Mosaic (`tpu_custom_call`), the same
               greedy actions and near-equal logits as the unfused agent on
               the chip, and the unfused chip logits against the same jitted
               function on the host CPU;
  5. summary - phase wall times, compile seconds, decisions, failures.

    python chip_smoke.py [--out chiprun_out/chip_smoke]

The last line of stdout is `{"ok": true, "device": {...}}`. Every failed
check raises before it, and nothing catches. Times printed are smoke wall
times, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.baselines import run_spark_default  # noqa: E402
from repro.checkpoint import agent_state, install_agent_state  # noqa: E402
from repro.core.agent import AgentConfig, AqoraAgent  # noqa: E402
from repro.core.encoding import WorkloadMeta  # noqa: E402
from repro.core.train_loop import train_agent  # noqa: E402
from repro.jax_cache import enable_compile_cache  # noqa: E402
from repro.learn import make_online_loop  # noqa: E402
from repro.serve.driver import open_loop_stream  # noqa: E402
from repro.serve.service import QueryService  # noqa: E402
from repro.sql import datagen, workloads  # noqa: E402
from repro.sql.cbo import Estimator  # noqa: E402

# Logits are f32 sums of at most a few hundred products of O(1) terms, so
# two correct f32 evaluations differ by ~1e-6; 1e-4 leaves reordering room
# and still catches a pass computed in bf16 (~1e-2).
LOGIT_ATOL = 1e-4


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ------------------------------------------------------------------ train
def phase_train(scale: float = 1.0, episodes: int = 16) -> dict:
    """Build JOB-like data and workload; train the full-width agent."""
    t0 = time.perf_counter()
    db = datagen.make_job_like(scale=scale, seed=0)
    wl = workloads.make_workload("job", n_train=100, n_test_per_template=1)
    est = Estimator(db, db.stats)
    t_data = time.perf_counter() - t0

    agent = AqoraAgent(WorkloadMeta.from_workload(wl), AgentConfig(), seed=0)
    # host copy: the PPO update donates (and on the TPU deletes) these
    before = jax.tree_util.tree_map(np.array, agent.actor)
    t1 = time.perf_counter()
    agent, logs = train_agent(db, wl, episodes=episodes, seed=0, est=est,
                              agent=agent, batch_size=8)
    t_train = time.perf_counter() - t1

    check(len(logs) == episodes, f"{len(logs)} of {episodes} episodes ran")
    losses = np.array([[l.actor_loss, l.critic_loss] for l in logs])
    check(bool(np.isfinite(losses).all()), f"non-finite losses {losses}")
    moved = max(float(np.abs(np.asarray(a) - b).max()) for a, b in zip(
        jax.tree_util.tree_leaves(agent.actor),
        jax.tree_util.tree_leaves(before)))
    check(moved > 0.0, "actor parameters did not move")
    return {"db": db, "wl": wl, "est": est, "agent": agent,
            "data_s": t_data, "train_s": t_train,
            "n_params": agent.param_count(),
            "decisions": sum(len(l.actions) for l in logs),
            "last_actor_loss": float(losses[-1, 0]),
            "last_critic_loss": float(losses[-1, 1]),
            "max_param_move": moved,
            "failed": sum(l.failed for l in logs)}


# ------------------------------------------------------------------ serve
def reference_rows(queries, scale: float) -> dict:
    """Query name -> result row count of Spark's default plan (None where
    that plan failed), on the same tables generated afresh so that no
    stage cache is shared with the served run."""
    db = datagen.make_job_like(scale=scale, seed=0)
    est = Estimator(db, db.stats)
    ref = {}
    for q in queries:
        r = run_spark_default(db, q, est)
        ref[q.name] = None if r.failed else r.stages[-1].out_rows
    return ref


def check_rows(comps, ref: dict) -> tuple:
    """Every completion that did not fail has the reference's row count
    (join order cannot change a result's cardinality). Returns (checked,
    unreferenced): the latter are queries whose reference plan failed."""
    n_checked = n_unref = 0
    for c in comps:
        if c.result.failed:
            continue
        want = ref[c.query.name]
        if want is None:
            n_unref += 1
            continue
        got = c.result.stages[-1].out_rows
        check(got == want, f"{c.query.name}: {got} rows, Spark default "
                           f"gives {want}")
        n_checked += 1
    check(n_checked > 0, "no completion could be checked")
    return n_checked, n_unref


def phase_serve(db, wl, est, agent, out_dir: Path,
                scale: float = 1.0) -> dict:
    """Serve an open-loop stream with online learning; check every result's
    row count against Spark's default plan on an independent data copy."""
    t0 = time.perf_counter()
    harvester, learner = make_online_loop(
        agent, probe=wl.test[:4], store_dir=str(out_dir / "policy_store"),
        update_every=8, sample_size=8, gate_every=2, seed=0)
    svc = QueryService(db, agent, est=est, n_lanes=8, policy="async",
                       explore=True, hooks=[harvester, learner])
    stream = open_loop_stream(wl.test, rate=2.0, n_queries=3 * len(wl.test),
                              seed=1)
    comps, stats = svc.run(stream)
    t_serve = time.perf_counter() - t0

    check(sorted(c.seq for c in comps) == list(range(len(stream))),
          f"{len(comps)} completions for {len(stream)} arrivals")
    t1 = time.perf_counter()
    ref = reference_rows(wl.test, scale)
    t_ref = time.perf_counter() - t1
    n_checked, n_unref = check_rows(comps, ref)
    return {"comps": comps, "serve_s": t_serve, "ref_s": t_ref,
            "n_arrivals": len(stream), "n_completed": len(comps),
            "n_failed": stats.n_failed, "n_checked": n_checked,
            "n_unreferenced": n_unref,
            "decisions": sum(len(c.traj.actions) for c in comps),
            "ticks": stats.ticks, "learn": learner.stats.as_dict()}


# ----------------------------------------------------------------- kernel
def _decision_batch(comps, agent, batch: int = 8):
    """Up to `batch` real (state, action-mask) pairs from served queries,
    node dimension trimmed to the agent's bucket as `act_batch` does."""
    pairs = [(s, m) for c in comps
             for s, m in zip(c.traj.states, c.traj.masks)][:batch]
    check(len(pairs) > 0, "no decision states to replay")
    feat, left, right, mask = (np.stack([p[0][i] for p in pairs])
                               for i in range(4))
    n = agent._nodes
    check(int(mask.sum(axis=1).max()) < n, "state wider than node bucket")
    amask = np.stack([p[1] for p in pairs]).astype(np.float32)
    keys = np.stack([np.asarray(jax.random.PRNGKey(i), np.uint32)
                     for i in range(len(pairs))])
    return (feat[:, :n], left[:, :n], right[:, :n], mask[:, :n]), amask, keys


def phase_kernel(agent, comps) -> dict:
    """Fused vs unfused tree-CNN on the default device, and the unfused
    logits on the default device vs the host CPU."""
    t0 = time.perf_counter()
    fused = AqoraAgent(agent.meta,
                       dataclasses.replace(agent.cfg, fused_treecnn=True),
                       seed=0)
    install_agent_state(fused, agent_state(agent), copy=True)
    (feat, left, right, mask), amask, keys = _decision_batch(comps, agent)
    state = tuple(jnp.asarray(x) for x in (feat, left, right, mask))

    text = fused._act_batch_jit.lower(
        fused.actor, *state, amask, keys, explore=False).compile().as_text()
    mosaic = "tpu_custom_call" in text

    a_u, lp_u, _ = agent.act_batch(feat, left, right, mask, amask, keys,
                                   explore=False)
    a_f, lp_f, _ = fused.act_batch(feat, left, right, mask, amask, keys,
                                   explore=False)
    lg_u = np.asarray(agent._logits_b(agent.actor, *state))
    lg_f = np.asarray(fused._logits_b(fused.actor, *state))
    cpu = jax.devices("cpu")[0]
    lg_c = np.asarray(agent._logits_b(jax.device_put(agent.actor, cpu),
                                      *jax.device_put(state, cpu)))
    greedy = lambda lg: np.argmax(np.where(amask > 0, lg, -1e9), axis=-1)
    out = {"kernel_s": time.perf_counter() - t0, "mosaic_kernel": mosaic,
           "batch": int(feat.shape[0]), "nodes": int(feat.shape[1]),
           "max_logit_diff_fused": float(np.abs(lg_f - lg_u).max()),
           "max_logp_diff_fused": float(np.abs(lp_f - lp_u).max()),
           "max_logit_diff_cpu": float(np.abs(lg_u - lg_c).max()),
           "actions_equal_fused": bool((a_u == a_f).all()),
           "actions_equal_cpu": bool((greedy(lg_u) == greedy(lg_c)).all())}
    print("kernel:", json.dumps(out), flush=True)
    check(out["actions_equal_fused"], f"fused actions {a_f} != {a_u}")
    check(out["actions_equal_cpu"], "greedy actions differ from the CPU's")
    check(out["max_logit_diff_fused"] <= LOGIT_ATOL,
          f"fused logits differ by {out['max_logit_diff_fused']}")
    check(out["max_logit_diff_cpu"] <= LOGIT_ATOL,
          f"chip logits differ from the CPU's by {out['max_logit_diff_cpu']}")
    return out


# ------------------------------------------------------------------- main
class _CompileClock:
    """Backend compiles reported through jax.monitoring: their seconds (a
    load from the persistent cache included) and how many were such
    loads."""

    def __init__(self):
        self.seconds, self.count, self.cache_hits = 0.0, 0, 0

    def __call__(self, event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.cache_hits += 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "chip_smoke"),
                    help="directory for the smoke's own output")
    args = ap.parse_args(argv)

    dev = device_info()
    print("device:", json.dumps(dev), flush=True)
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev['platform']}",
              file=sys.stderr)
        return 1
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    cache = enable_compile_cache()
    clock = _CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    print("compile cache:", cache, flush=True)

    t0 = time.perf_counter()
    episodes = 16
    tr = phase_train(scale=1.0, episodes=episodes)
    print(f"train: data {tr['data_s']}s, {episodes} episodes "
          f"{tr['train_s']}s, {tr['n_params']} params, decisions "
          f"{tr['decisions']}, failed {tr['failed']}, "
          f"last losses actor {tr['last_actor_loss']} critic "
          f"{tr['last_critic_loss']}, max param move "
          f"{tr['max_param_move']}", flush=True)
    sv = phase_serve(tr["db"], tr["wl"], tr["est"], tr["agent"], out_dir)
    print(f"serve: {sv['n_completed']}/{sv['n_arrivals']} completed in "
          f"{sv['serve_s']}s, failed {sv['n_failed']}, "
          f"rows checked {sv['n_checked']} (unreferenced "
          f"{sv['n_unreferenced']}, reference {sv['ref_s']}s), "
          f"decisions {sv['decisions']}, ticks {sv['ticks']}, "
          f"learn {json.dumps(sv['learn'])}", flush=True)
    kn = phase_kernel(tr["agent"], sv["comps"])
    check(kn["mosaic_kernel"], "fused kernel was not compiled by Mosaic")

    summary = {
        "device": dev, "total_s": time.perf_counter() - t0,
        "phase_s": {"data": tr["data_s"], "train": tr["train_s"],
                    "serve": sv["serve_s"], "reference": sv["ref_s"],
                    "kernel": kn["kernel_s"]},
        "compile_s": clock.seconds, "compiles": clock.count,
        "compile_cache_hits": clock.cache_hits,
        "decisions": tr["decisions"] + sv["decisions"],
        "failed_queries": {"train": tr["failed"], "serve": sv["n_failed"]},
        "kernel": kn}
    (out_dir / "summary.json").write_text(json.dumps(summary, indent=1))
    print("summary:", json.dumps(summary), flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
