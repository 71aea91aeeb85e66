"""The benchmark's general engine: cells are data, this runs any of them.

A cell (`BENCHMARK.json` `workloads`) names a configuration and a traffic
mix. Each is a JSON file found by name:

* `bench/configs/<config>.json` - the deployment: schema and scale, the
  query templates, the agent, lanes, cluster model, the set-up training
  and the seeds that fix the data, the policy and the query pool;
* `bench/traffic/<mix>.json` - what the window drives: `"drive":
  "serve"` (open-loop chunks through `QueryService.run`, optionally with
  the online learning loop) or `"drive": "train"` (`train_agent` calls).
  The window's work is fixed: as many chunks (calls) as last `--seconds`
  at the mix's nominal `chunk_seconds` (`call_seconds`);
* `bench/end_to_end/<metric>.py`, `bench/layer_metrics/<metric>.py` -
  one metric each, read from the run's record.

A configuration names the program's data generator (`"data"`, a
function of `repro.sql.datagen`) and its workload (`"workload"."name"`,
a template set of `repro.sql.workloads`), so a new one is a file.
`--seed` draws the answer check's sample and the training episodes; the
data, the set-up policy, the query pool, its arrivals and each query's
PRNG key come from the configuration's own seeds, so that every seed
does the same work and the end-to-end numbers of two seeds differ by
the system's noise, not by the draw.
"""
from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import math
import resource
import shutil
import sys
import time
import traceback
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / "bench_out"
TRACE_SETTLE_S = 1.0        # after start_trace, before the window opens


# ----------------------------------------------------------------- lookup
def load_json(path: Path) -> Dict:
    return json.loads(Path(path).read_text())


def load_spec(root: Path = ROOT) -> Dict:
    return load_json(root / "BENCHMARK.json")


def find(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell_plan(spec: Dict, workload: str, root: Path = ROOT) -> Dict:
    """Everything one run needs to know, found by name."""
    cell = find(spec["workloads"], workload, "workload")
    conf_entry = find(spec["configs"], cell["config"], "config")
    return {
        "cell": cell,
        "config": load_json(root / conf_entry["file"]),
        "traffic": load_json(root / "bench" / "traffic"
                             / f"{cell['traffic']}.json"),
        "end_to_end": [m for m in spec["end_to_end"]
                       if workload in m.get("workloads", [workload])],
        "per_layer": [m for m in spec["per_layer"]
                      if workload in m.get("workloads", [workload])],
    }


def metric_reader(name: str, per_layer: bool,
                  root: Path = ROOT) -> Callable:
    """`read(record)` of one metric: `bench/layer_metrics/<name>.py` for a
    per-layer metric, `bench/end_to_end/<name>.py` for an end-to-end one."""
    kind = "layer_metrics" if per_layer else "end_to_end"
    path = root / "bench" / kind / f"{name}.py"
    mod_name = f"bench_{kind}_" + name.replace(".", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_for(kind: str, root: Path = ROOT) -> Dict:
    """The chip's published peaks (`bench/peaks.json`); a device kind that
    is not in the table is an error, never a default."""
    peaks = load_json(root / "bench" / "peaks.json")
    if kind not in peaks or kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json")
    return peaks[kind]


def sub_seed(seed: int, *tags: int) -> int:
    """An independent 31-bit seed derived from `seed` and `tags`."""
    ss = np.random.SeedSequence([int(seed) % (2 ** 63)] + list(tags))
    return int(ss.generate_state(1)[0] >> 1)


# ------------------------------------------------------------------ spans
class MissingLayer(RuntimeError):
    """A run did work but a layer it must have passed through left no
    trace: the run fails rather than report a 0."""


class Recorder:
    """Host-clock spans the benchmark takes around calls into the program,
    each also written into the profiler's trace as `bench.<kind>`."""

    def __init__(self):
        self.policy_calls: List[Dict] = []
        self.learn_s: List[float] = []
        self.ppo_s: List[float] = []
        self.sched = None
        self.live = False            # only the window's calls are kept
        self.actors: Dict[int, object] = {}

    def clear(self) -> None:
        self.policy_calls.clear()
        self.learn_s.clear()
        self.ppo_s.clear()
        self.actors.clear()


def annotate(name: str):
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


def _host_actor(actor) -> Dict:
    import jax
    return jax.tree_util.tree_map(
        np.asarray, jax.device_get({"enc": actor["enc"],
                                    "head": actor["head"]}))


class TimedAgent:
    """The agent as the program sees it, with the benchmark's clock on the
    batched policy call and the PPO update. Every other attribute, read or
    written (a hot swap installs new parameters), is the agent's own."""

    def __init__(self, agent, rec: Recorder):
        object.__setattr__(self, "_agent", agent)
        object.__setattr__(self, "_rec", rec)

    def __getattr__(self, name):
        return getattr(self._agent, name)

    def __setattr__(self, name, value):
        setattr(self._agent, name, value)

    def act_batch(self, feat, left, right, mask, amask, keys, explore=True):
        rec, agent = self._rec, self._agent
        actor = agent.actor
        t0 = time.perf_counter()
        with annotate("bench.policy_call"):
            out = agent.act_batch(feat, left, right, mask, amask, keys,
                                  explore=explore)
        dt = time.perf_counter() - t0
        if rec.live:
            real = np.asarray(mask).sum(axis=1) > 0
            lanes = int(real.sum())
            if rec.sched is not None:
                sizes = rec.sched.decide_sizes
                if not sizes or sizes[-1] != lanes:
                    raise MissingLayer("a policy call was not paired with "
                                       "the scheduler's decision batch")
            if id(actor) not in rec.actors:
                # a host copy now: the PPO update may donate these buffers.
                # The dict itself is kept so that its id is never reused.
                rec.actors[id(actor)] = (actor, _host_actor(actor))
            rec.policy_calls.append({
                "s": dt, "lanes": lanes, "actor": id(actor), "real": real,
                "explore": bool(explore),
                "inputs": tuple(np.array(x) for x in
                                (feat, left, right, mask, amask)),
                "action": np.array(out[0]), "logp": np.array(out[1])})
        return out

    def ppo_update_batch(self, trajs):
        t0 = time.perf_counter()
        with annotate("bench.ppo_update"):
            out = self._agent.ppo_update_batch(trajs)
        if self._rec.live:
            self._rec.ppo_s.append(time.perf_counter() - t0)
        return out


class TimingHook:
    """Attached after the program's own hooks: remembers the scheduler and
    puts the benchmark's clock around every completion callback the
    learning hooks registered."""

    def __init__(self, rec: Recorder):
        self.rec = rec

    def attach(self, scheduler) -> None:
        self.rec.sched = scheduler
        scheduler.on_complete[:] = [self._timed(cb)
                                    for cb in scheduler.on_complete]

    def _timed(self, cb):
        rec = self.rec

        def timed(comp):
            t0 = time.perf_counter()
            with annotate("bench.learn"):
                cb(comp)
            if rec.live:
                rec.learn_s.append(time.perf_counter() - t0)
        return timed


class UpdateTape:
    """Records the agent's first `n` PPO updates as the program made them:
    the state before the first, each update's batch and returned losses,
    the optimizer state after the first and the parameters after the
    last. It wraps the jitted update the agent calls, so the batch is the
    one the program built and the results are the program's own."""

    def __init__(self, agent, n: int = 3):
        self.fn = agent._update_epochs
        self.n = n
        self.before = None
        self.steps: List[Dict] = []
        self.after_first = None
        self.after_last = None
        agent._update_epochs = self

    def __call__(self, actor, critic, aopt, copt, batch, sbatch):
        if len(self.steps) >= self.n:
            return self.fn(actor, critic, aopt, copt, batch, sbatch)
        if self.before is None:
            self.before = _host({"actor": actor, "critic": critic,
                                 "aopt": aopt, "copt": copt})
        step = {"batch": _host(batch), "sbatch": _host(sbatch)}
        out = self.fn(actor, critic, aopt, copt, batch, sbatch)
        step["actor_loss"], step["critic_loss"] = float(out[4]), float(out[5])
        self.steps.append(step)
        if len(self.steps) == 1:
            self.after_first = _host({"aopt": out[2], "copt": out[3]})
        if len(self.steps) == self.n:
            self.after_last = _host({"actor": out[0], "critic": out[1]})
        return out


def _host(tree):
    import jax
    return jax.tree_util.tree_map(np.array, jax.device_get(tree))


class CompileCounter:
    """Backend compiles and persistent-cache loads, via jax.monitoring."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

    def __call__(self, event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.cache_hits += 1

    def snapshot(self) -> Dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits}


# ------------------------------------------------------------------ world
@dataclasses.dataclass
class World:
    db: object
    wl: object
    est: object
    cluster: object
    agent: TimedAgent
    rec: Recorder
    digest: str
    phases: Dict[str, float]
    tape: Optional[UpdateTape] = None


def _digest_tables(db, h) -> None:
    for name in sorted(db.tables):
        h.update(name.encode())
        for col, arr in sorted(db.tables[name].columns.items()):
            h.update(col.encode())
            h.update(np.ascontiguousarray(arr).tobytes())


def query_digest(queries, h) -> None:
    for q in queries:
        h.update(repr((q.name, q.relations, q.conds)).encode())


def build_world(cfg: Dict, tape: int = 0) -> World:
    """Data, workload, agent and set-up training, from the config alone.
    With `tape`, the agent's first `tape` PPO updates are recorded."""
    from repro.core.agent import AgentConfig, AqoraAgent
    from repro.core.encoding import WorkloadMeta
    from repro.core.train_loop import train_agent
    from repro.sql import datagen, workloads
    from repro.sql.cbo import Estimator
    from repro.sql.cluster import ClusterModel

    phases = {}
    t0 = time.perf_counter()
    db = getattr(datagen, cfg["data"])(scale=cfg["scale"],
                                       seed=cfg["data_seed"])
    w = cfg["workload"]
    wl = workloads.make_workload(w["name"], n_train=w["n_train"],
                                 n_test_per_template=w["n_test_per_template"],
                                 seed=w["seed"])
    est = Estimator(db, db.stats)
    cluster = ClusterModel(**cfg.get("cluster", {}))
    phases["data_s"] = time.perf_counter() - t0

    rec = Recorder()
    agent = TimedAgent(AqoraAgent(WorkloadMeta.from_workload(wl),
                                  AgentConfig(**cfg.get("agent", {})),
                                  seed=cfg["agent_seed"]), rec)
    recorder = UpdateTape(agent._agent, tape) if tape else None
    t1 = time.perf_counter()
    tr = cfg["setup_training"]
    train_agent(db, wl, episodes=tr["episodes"], seed=tr["seed"], est=est,
                cluster=cluster, agent=agent, batch_size=tr["batch_size"])
    phases["setup_training_s"] = time.perf_counter() - t1
    h = hashlib.sha256()
    _digest_tables(db, h)
    return World(db, wl, est, cluster, agent, rec, h.hexdigest()[:16],
                 phases, recorder)


# ---------------------------------------------------------------- warm-up
def node_buckets(agent) -> List[int]:
    """Every trimmed node width `act_batch` and the PPO update can use."""
    return list(range(16, agent._nodes + 1, 16))


def warm_policy(agent, batch: int, explore: bool) -> None:
    """Compile the batched policy call at every node bucket."""
    inner = agent._agent if isinstance(agent, TimedAgent) else agent
    F, d = inner.meta.feat_dim, inner.space.d
    for n in node_buckets(inner):
        mask = np.zeros((batch, 64), np.float32)
        mask[:, 1:n] = 1.0
        amask = np.zeros((batch, d), np.float32)
        amask[:, inner.space.noop_idx] = 1.0
        inner.act_batch(np.zeros((batch, 64, F), np.float32),
                        np.zeros((batch, 64), np.int32),
                        np.zeros((batch, 64), np.int32), mask, amask,
                        np.zeros((batch, 2), np.uint32), explore=explore)


def warm_update(agent, batch: int) -> None:
    """Compile the PPO update at every node bucket, then put the agent's
    parameters and optimizer state back as they were. An `UpdateTape` on
    the agent does not see these updates."""
    from repro.checkpoint import agent_state, install_agent_state
    from repro.core.rollout import Trajectory
    inner = agent._agent if isinstance(agent, TimedAgent) else agent
    saved = _host(agent_state(inner))
    taped = inner._update_epochs
    if isinstance(taped, UpdateTape):
        inner._update_epochs = taped.fn
    F, d = inner.meta.feat_dim, inner.space.d
    try:
        for n in node_buckets(inner):
            trajs = []
            for _ in range(batch):
                mask = np.zeros(64, np.float32)
                mask[1:n] = 1.0
                st = (np.zeros((64, F), np.float32), np.zeros(64, np.int32),
                      np.zeros(64, np.int32), mask)
                am = np.zeros(d, np.float32)
                am[inner.space.noop_idx] = 1.0
                trajs.append(Trajectory(
                    states=[st], actions=[inner.space.noop_idx], logps=[0.0],
                    masks=[am], rewards=[0.0], t_execute=1.0))
            inner.ppo_update_batch(trajs)
    finally:
        inner._update_epochs = taped
    install_agent_state(inner, saved, copy=True)


# ---------------------------------------------------------------- serving
def window_units(seconds: float, nominal_s: float) -> int:
    """The window's fixed work: as many chunks (or calls) as last
    `seconds` at the mix's nominal seconds for one."""
    return max(1, math.ceil(seconds / nominal_s - 1e-9))


def query_pool(cfg: Dict, n_chunks: int, chunk: int) -> List[List]:
    """Chunks of distinct template instances, cycling the templates, in
    the order the configuration's pool seed draws them; each query
    carries its own PRNG seed."""
    from repro.sql import workloads
    gen = workloads.query_stream(cfg["workload"]["name"],
                                 seed=cfg["pool_seed"])
    rng = np.random.default_rng(cfg["pool_seed"])
    return [[(next(gen), int(rng.integers(2 ** 31))) for _ in range(chunk)]
            for _ in range(n_chunks)]


def pool_streams(cfg: Dict, traffic: Dict, n_chunks: int) -> List[List]:
    """The run's chunks of arrivals on one modelled clock, with Poisson
    gaps drawn from the pool seed, the same in every run: arrivals drawn
    from `--seed` changed the work (their interleaving decides what the
    stage cache evicts), and so did the queries' PRNG keys, which a
    frozen greedy policy never reads, by the host's speed alone."""
    pool = query_pool(cfg, n_chunks, traffic["chunk_queries"])
    rng = np.random.default_rng(sub_seed(cfg["pool_seed"], 1))
    out, t = [], 0.0
    for chunk in pool:
        stream, t = chunk_stream(chunk, rng, t, traffic["rate_qps"])
        out.append(stream)
    return out


def chunk_stream(pool_chunk, rng: np.random.Generator, t0: float,
                 rate: float):
    """One chunk in the pool's order with Poisson gaps from `rng` at `rate`
    queries per modelled second, continuing the modelled clock from `t0`
    (the arithmetic of `serve/driver.open_loop_stream`)."""
    from repro.serve.scheduler import Arrival
    out, t = [], t0
    for q, s in pool_chunk:
        t += float(rng.exponential(1.0 / rate))
        out.append(Arrival(t, query=q, seed=s))
    return out, t


def make_service(world: World, traffic: Dict, cfg: Dict, hooks=()):
    """The service as deployed: its default stage cache, carried across
    every chunk of the run."""
    from repro.serve.service import QueryService
    return QueryService(world.db, world.agent, est=world.est,
                        cluster=world.cluster, n_lanes=cfg["lanes"],
                        policy="async", explore=traffic["explore"],
                        hooks=list(hooks) + [TimingHook(world.rec)])


def online_hooks(world: World, traffic: Dict, store_dir: Path):
    from repro.learn import make_online_loop
    on = traffic["online"]
    shutil.rmtree(store_dir, ignore_errors=True)
    harvester, learner = make_online_loop(
        world.agent, probe=world.wl.test[:on["probe_queries"]],
        store_dir=str(store_dir), update_every=on["update_every"],
        sample_size=on["sample_size"], gate_every=on["gate_every"],
        seed=on["seed"])
    return [harvester, learner], learner


def _usage():
    """This process's user and system CPU seconds so far."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return ru.ru_utime, ru.ru_stime


def p95_ms(seconds: List[float]) -> Optional[float]:
    """The 95th percentile, in ms, linear between order statistics."""
    return float(np.percentile(np.asarray(seconds) * 1e3, 95)) \
        if len(seconds) else None


def serve(world: World, cfg: Dict, traffic: Dict, seed: int,
          seconds: float, clock: CompileCounter, trace_dir=None,
          on_start: Callable = lambda: None) -> Dict:
    """Warm up with the pool's first chunk, then serve the next
    `window_units(seconds, chunk_seconds)` chunks as the window."""
    import jax
    rec = world.rec
    n_window = window_units(seconds, traffic["chunk_seconds"])
    streams = pool_streams(cfg, traffic, n_window + 1)
    hooks, learner = [], None
    if traffic.get("online"):
        hooks, learner = online_hooks(world, traffic,
                                      OUT / "policy_store" / cfg["name"])
    svc = make_service(world, traffic, cfg, hooks)

    t0 = time.perf_counter()
    warm_policy(world.agent, cfg["lanes"], traffic["explore"])
    if learner is not None:
        warm_update(learner.agent, traffic["online"]["sample_size"])
    raised = []
    svc.run(streams[0])
    world.phases["warmup_s"] = time.perf_counter() - t0
    learn_warm = None if learner is None else learner.stats.as_dict()
    cache_before = svc.cache.stats.as_dict()

    comps, attempted, decide_sizes, chunk_s = [], 0, [], []
    rec.clear()
    if trace_dir is not None:
        _start_trace(trace_dir)
    before = clock.snapshot()
    on_start()
    rec.live = True
    t_start = time.perf_counter()
    with annotate("bench.window"):
        for stream in streams[1:]:
            attempted += len(stream)
            t_chunk, u_chunk = time.perf_counter(), _usage()
            try:
                with annotate("bench.chunk"):
                    got, _ = svc.run(stream)
                comps += got
            except Exception:     # the program raised: count, keep serving
                raised.append(traceback.format_exc())
            decide_sizes += list(svc.scheduler.decide_sizes)
            chunk_s.append((time.perf_counter() - t_chunk,
                            *(b - a for a, b in zip(u_chunk, _usage()))))
    window_s = time.perf_counter() - t_start
    rec.live = False
    if trace_dir is not None:
        jax.profiler.stop_trace()
    after = clock.snapshot()
    for tb in raised:
        print(tb, file=sys.stderr)
    cache = {k2: v - cache_before[k2]
             for k2, v in svc.cache.stats.as_dict().items()
             if k2 in ("hits", "misses", "evictions")}
    h = hashlib.sha256()
    for stream in streams:
        query_digest([a.query for a in stream], h)
    calls = rec.policy_calls
    summary = {
        "window_s": window_s, "chunks": n_window,
        "chunk_s": [round(w, 3) for w, *_ in chunk_s],
        "chunk_cpu_s": [round(u + s, 3) for _, u, s in chunk_s],
        "chunk_sys_s": [round(s, 3) for _, _, s in chunk_s],
        "attempted": attempted,
        "completed": len(comps), "raised_chunks": len(raised),
        "modelled_failures": sum(c.result.failed for c in comps),
        "policy_calls": len(calls),
        "decisions": sum(c["lanes"] for c in calls),
        "decision_p95_ms": p95_ms([c["s"] for c in calls
                                   for _ in range(c["lanes"])]),
        "call_p95_ms": p95_ms([c["s"] for c in calls]),
        "ticks": len(decide_sizes), "stage_cache": cache,
        "compiles_in_window": after["compiles"] - before["compiles"],
        "cache_loads_in_window": after["cache_hits"] - before["cache_hits"]}
    if learner is not None:
        summary["learning"] = {
            k2: learner.stats.as_dict()[k2] - learn_warm[k2]
            for k2 in ("completions", "updates", "gates", "swaps",
                       "rejects")}
    return {"drive": "serve", "window_s": window_s, "attempted": attempted,
            "failed": summary["modelled_failures"] + attempted - len(comps),
            "comps": comps, "raised": len(raised),
            "decide_sizes": decide_sizes, "online": learner is not None,
            "query_digest": h.hexdigest()[:16], "summary": summary,
            "setup_compiles": before}


# --------------------------------------------------------------- training
def train(world: World, cfg: Dict, traffic: Dict, seed: int,
          seconds: float, clock: CompileCounter, trace_dir=None,
          on_start: Callable = lambda: None) -> Dict:
    """Call `train_agent` on the set-up agent
    `window_units(seconds, call_seconds)` times, each call a fixed number
    of episodes."""
    import jax
    from repro.core.train_loop import train_agent
    rec = world.rec
    eps, bs = traffic["episodes_per_call"], traffic["batch_size"]
    n_window = window_units(seconds, traffic["call_seconds"])
    t0 = time.perf_counter()
    warm_policy(world.agent, bs, True)
    warm_update(world.agent, bs)
    tape = world.tape
    while tape is not None and len(tape.steps) < tape.n:
        # the first steps the reference follows go through the window's
        # own call, on episodes of their own
        train_agent(world.db, world.wl, episodes=bs,
                    seed=sub_seed(seed, 4, len(tape.steps)), est=world.est,
                    cluster=world.cluster, agent=world.agent, batch_size=bs)
    world.phases["warmup_s"] = time.perf_counter() - t0

    logs, raised = [], []
    rec.clear()
    if trace_dir is not None:
        _start_trace(trace_dir)
    before = clock.snapshot()
    on_start()
    rec.live = True
    t_start = time.perf_counter()
    with annotate("bench.window"):
        for call in range(n_window):
            try:
                with annotate("bench.train_call"):
                    _, got = train_agent(
                        world.db, world.wl, episodes=eps,
                        seed=sub_seed(seed, 2, call), est=world.est,
                        cluster=world.cluster, agent=world.agent,
                        batch_size=bs)
                logs += got
            except Exception:
                raised.append(traceback.format_exc())
    window_s = time.perf_counter() - t_start
    rec.live = False
    if trace_dir is not None:
        jax.profiler.stop_trace()
    after = clock.snapshot()
    for tb in raised:
        print(tb, file=sys.stderr)
    bad = sum(not (np.isfinite(l.actor_loss) and np.isfinite(l.critic_loss))
              for l in logs)
    h = hashlib.sha256()
    query_digest(world.wl.train, h)
    summary = {
        "window_s": window_s, "calls": n_window, "episodes": len(logs),
        "raised_calls": len(raised), "nonfinite_losses": int(bad),
        "modelled_failures": sum(l.failed for l in logs),
        "policy_calls": len(rec.policy_calls),
        "ppo_updates": len(rec.ppo_s),
        "compiles_in_window": after["compiles"] - before["compiles"],
        "cache_loads_in_window": after["cache_hits"] - before["cache_hits"]}
    return {"drive": "train", "window_s": window_s,
            "attempted": n_window * eps,
            "failed": len(raised) * eps + int(bad), "logs": logs,
            "raised": len(raised), "online": False,
            "query_digest": h.hexdigest()[:16], "summary": summary,
            "setup_compiles": before}


def policy_dims(agent) -> Dict:
    """The widths the policy call's work function needs."""
    import jax
    inner = agent._agent if isinstance(agent, TimedAgent) else agent
    return {"feat": inner.meta.feat_dim, "hidden": inner.cfg.hidden,
            "head_hidden": inner.cfg.head_hidden,
            "actions": inner.space.d,
            "param_bytes": sum(int(x.size) * x.dtype.itemsize for x in
                               jax.tree_util.tree_leaves(inner.actor))}


def _start_trace(trace_dir) -> None:
    """Start the profiler and give the device tracer time to come up, so
    that the window's first programs are in the trace."""
    import jax
    jax.profiler.start_trace(str(trace_dir),
                             profiler_options=_profile_options())
    time.sleep(TRACE_SETTLE_S)


def _profile_options():
    from jax.profiler import ProfileOptions
    o = ProfileOptions()
    o.python_tracer_level = 0         # the benchmark's annotations suffice
    o.host_tracer_level = 2
    return o


DRIVES = {"serve": serve, "train": train}
