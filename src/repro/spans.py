"""Named wall-clock spans at the served path's layer boundaries.

Each span is a `jax.profiler.TraceAnnotation`: while a profiler trace is
being taken (`jax.profiler.trace` / `start_trace`) it lands on the host
plane of the `.xplane.pb`, on the same clock as the device planes, so an
idle gap on the chip can be put down to what the host was doing. With no
profiler running a span costs about a microsecond and records nothing.
JAX is not imported for a span: until something else has loaded it no
profiler can be running, and `span` returns a no-op, so the SQL engine
stays importable without the accelerator runtime.

Counts ride on the span as event stats: those known when it opens are
passed to `span(...)`; those known only at its end are added with
`set_metadata(...)`. Each count is in brackets after its span's meaning:

- `queries`: the stream's length, the denominator of per-query times;
- `lanes`: lanes decided in one batch (0 on the loop's last pass);
- `seq`: the query's `Completion.seq`, which follows one query through
  its admission, decisions, resumes and finish, so a long span or an
  idle gap is put down to the query that caused it;
- `nodes`: the node bucket the policy program ran at, its work's size;
- `hit`: 1 when the stage cache served the stage (the cache's own
  `hits` counter moved), else 0;
- `rows`, `method`: the stage's output rows and join method, which say
  why one executor span is long;
- `probe`: how a join that the stage cache did not serve matched its
  keys ("unique", "dense" or "sorted", `sql.executor._join_indices`);
  absent on a stage-cache hit;
- `cols`, `dropped`: on a join that the stage cache did not serve, the
  key columns its output carries (those a later join of the query reads,
  `sql.executor._live_cols`; 0 at the query's last join) and the input
  columns it left out; absent on a stage-cache hit.

The names below are part of the program's interface: a profile reader
finds the layers by them. Every name starts with `lqrs.`, and spans of one
name never nest inside each other. None is held open across the executor's
suspension at a stage boundary (the `yield` in `AdaptiveRun._drive`), so a
span never ends during another lane's work.
"""
from __future__ import annotations

import sys

SPANS = (
    ("lqrs.serve", "QueryService.run: build and attach the scheduler, "
     "serve the stream, compute the stats [queries]"),
    ("lqrs.tick", "LaneScheduler.run: one loop pass, i.e. admission, "
     "hedging and one batched decision [lanes]"),
    ("lqrs.admit", "LaneScheduler._start: plan, AdaptiveRun, the lane's "
     "PRNG key (a device round trip), the run up to its first boundary "
     "[seq]"),
    ("lqrs.decide", "LaneScheduler._decide: from the first encode to the "
     "return of the batched policy call [lanes]"),
    ("lqrs.encode", "LaneScheduler._decide: encode_state and action_mask "
     "of one lane [seq]"),
    ("lqrs.apply", "LaneScheduler._decide: apply_action and the "
     "trajectory appends of one lane [seq]"),
    ("lqrs.resume", "AdaptiveRun.resume of one lane: the executor up to "
     "the next stage boundary or the end [seq]"),
    ("lqrs.finish", "LaneScheduler._finish: finalize_trajectory, the "
     "completion, the on_complete callbacks [seq]"),
    ("lqrs.policy", "AqoraAgent.act_batch: the batched policy call "
     "[nodes]"),
    ("lqrs.policy.feed", "AqoraAgent.act_batch: node trim, host-to-device "
     "copies, dispatch of the policy program"),
    ("lqrs.policy.fetch", "AqoraAgent.act_batch: the device_get, waiting "
     "for the program and copying back"),
    ("lqrs.exec.scan", "AdaptiveRun: one base-table scan, or a stage-cache "
     "hit, and its charge [rows, hit]"),
    ("lqrs.exec.join", "AdaptiveRun: one join stage, or a stage-cache "
     "hit, and its charge [rows, hit, method, probe, cols, dropped]"),
)

_NAMES = frozenset(name for name, _ in SPANS)


class _Off:
    """What `span` returns before JAX is loaded: records nothing."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **counts) -> None:
        pass

    @staticmethod
    def is_enabled() -> bool:
        return False


_OFF = _Off()
_TraceAnnotation = None


def span(name: str, **counts):
    """A context manager that records `name` (one of `SPANS`) with `counts`
    as its stats while a profiler trace is being taken."""
    global _TraceAnnotation
    assert name in _NAMES, name
    if _TraceAnnotation is None:
        if "jax" not in sys.modules:
            return _OFF
        from jax.profiler import TraceAnnotation as _TraceAnnotation
    return _TraceAnnotation(name, **counts)
