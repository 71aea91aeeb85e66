"""From a profiler trace to device busy time, program times and idle gaps.

`extract` reads the `.xplane.pb` that `jax.profiler` writes and keeps
what the benchmark needs, in plain lists of [name, start_ns, duration_ns]:

* `ops` - every event on a device's "XLA Ops" line (one HLO operation
  running on the chip), per device;
* `programs` - every event on a device's "XLA Modules" line (one run of a
  compiled program), per device, with the program's fingerprint suffix
  `(...)` cut off so that a name survives recompilation;
* `spans` - the benchmark's own host annotations (names starting with
  `bench.`), which share the trace's clock;
* `program_spans` - the program's own host spans (names starting with
  `lqrs.`, `repro.spans`), as [name, start_ns, duration_ns, {stat:
  value}]: the counts the span carries (`rows`, `hit`, `lanes`, ...).

`reduce` turns that into the numbers the metrics read. Everything is
clipped to the `bench.window` span, the measured window. A span's self
time is its duration less the time of the program spans inside it: the
served path runs on one host thread, so its spans nest.
"""
from __future__ import annotations

import bisect
import glob
import json
import re
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

WINDOW_SPAN = "bench.window"
POLICY_SPAN = "bench.policy_call"
POLICY_PROGRAM = "jit_act_batch_fn"     # the jitted `act_batch` body
PROGRAM_PREFIX = "lqrs."                # the program's spans, `repro.spans`
NEST_SLACK_NS = 1.0     # a child may end this far past its parent: the
#                         trace's times are rounded
_FINGERPRINT = re.compile(r"\(\d+\)$")


def program_name(event_name: str) -> str:
    return _FINGERPRINT.sub("", event_name)


def extract(path: str) -> Dict:
    """Read an `.xplane.pb`, or the newest one `jax.profiler` wrote under
    the log directory `path`."""
    from jax.profiler import ProfileData
    if not str(path).endswith(".xplane.pb"):
        paths = sorted(glob.glob(str(Path(path) / "plugins" / "profile"
                                     / "*" / "*.xplane.pb")))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = paths[-1]
    data = ProfileData.from_file(str(path))
    out = {"ops": {}, "programs": {}, "spans": [], "program_spans": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = plane.name.rsplit(":", 1)[1]
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out["ops"][dev] = [[e.name[:80], e.start_ns,
                                        e.duration_ns] for e in line.events]
                elif line.name == "XLA Modules":
                    out["programs"][dev] = [
                        [program_name(e.name), e.start_ns, e.duration_ns]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        out["spans"].append([e.name, e.start_ns,
                                             e.duration_ns])
                    elif e.name.startswith(PROGRAM_PREFIX):
                        out["program_spans"].append(
                            [e.name, e.start_ns, e.duration_ns,
                             dict(e.stats)])
    return out


def _union(intervals: Sequence[Tuple[float, float]], lo: float,
           hi: float) -> List[Tuple[float, float]]:
    """Merged (start, end) intervals clipped to [lo, hi]."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def window_of(trace: Dict) -> Tuple[float, float]:
    spans = [s for s in trace["spans"] if s[0] == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"{len(spans)} '{WINDOW_SPAN}' spans in the trace")
    _, start, dur = spans[0]
    return float(start), float(start + dur)


class _Spans:
    """Host spans, the benchmark's and the program's, by name, for lookups
    by time. Spans of one name never overlap; spans of different names may
    nest."""

    def __init__(self, spans):
        by_name: Dict[str, List[Tuple[float, float]]] = {}
        for name, s, d, *_ in spans:
            if name != WINDOW_SPAN:
                by_name.setdefault(name, []).append((s, s + d))
        self._by = {n: (sorted(iv), [a for a, _ in sorted(iv)])
                    for n, iv in by_name.items()}

    def holding(self, name: str, t: float):
        """The span named `name` that holds time t, or None."""
        if name not in self._by:
            return None
        spans, starts = self._by[name]
        i = bisect.bisect_right(starts, t) - 1
        return spans[i] if i >= 0 and t <= spans[i][1] else None

    def innermost(self, t: float) -> str:
        """Name of the shortest span that holds t, or 'outside any span'."""
        best = None
        for name in self._by:
            iv = self.holding(name, t)
            if iv is not None and (best is None or iv[1] - iv[0] < best[1]):
                best = (name, iv[1] - iv[0])
        return best[0] if best else "outside any span"


def _in_spans(trace: Dict, spans: _Spans,
              span: str) -> Dict[str, Dict[str, float]]:
    """Per program, the runs (and their device seconds) that started
    inside a host span named `span`."""
    out: Dict[str, Dict[str, float]] = {}
    for evs in trace["programs"].values():
        for name, s, d in evs:
            if spans.holding(span, s) is not None:
                p = out.setdefault(name, {"runs": 0, "seconds": 0.0})
                p["runs"] += 1
                p["seconds"] += d * 1e-9
    return out


def program_time(trace: Dict, lo: float, hi: float):
    """Per program span name, clipped to [lo, hi]: the self seconds (the
    spans' time less that of the program spans nested in them), and the
    number of spans that start in the window with the sum of each of their
    numeric counts."""
    self_s: Dict[str, float] = {}
    counts: Dict[str, Dict[str, float]] = {}
    open_: List[List] = []          # the spans holding the current one:
    #                                 [name, end, clipped ns, children ns]

    def close(entry):
        name, _, clipped, children = entry
        if clipped > 0:             # a span wholly outside adds no name
            self_s[name] = self_s.get(name, 0.0) + (clipped - children) * 1e-9

    for name, s, d, stats in sorted(trace["program_spans"],
                                    key=lambda x: (x[1], -x[2])):
        e = s + d
        while open_ and open_[-1][1] <= s:
            close(open_.pop())
        clipped = max(0.0, min(e, hi) - max(s, lo))
        if open_:
            if e > open_[-1][1] + NEST_SLACK_NS:
                raise ValueError(f"span {name} at {s} overlaps "
                                 f"{open_[-1][0]} without nesting in it")
            open_[-1][3] += clipped
        open_.append([name, e, clipped, 0.0])
        if lo <= s <= hi:
            c = counts.setdefault(name, {"spans": 0})
            c["spans"] += 1
            for k, v in stats.items():
                if isinstance(v, (int, float)):
                    c[k] = c.get(k, 0) + v
    while open_:
        close(open_.pop())
    return self_s, counts


def reduce(trace: Dict, top: int = 10) -> Dict:
    """Busy and window seconds (busy averaged over the devices traced),
    per-program device seconds and run counts, the program spans' self
    seconds and counts, and the longest idle gaps with the innermost host
    span, the benchmark's or the program's, each fell in."""
    lo, hi = window_of(trace)
    window_s = (hi - lo) * 1e-9
    if not trace["ops"]:
        raise ValueError("the trace holds no device operations")
    busy, gaps = [], []
    for dev, ops in sorted(trace["ops"].items()):
        merged = _union([(s, s + d) for _, s, d in ops], lo, hi)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s, dev))
    programs: Dict[str, Dict[str, float]] = {}
    for dev, evs in trace["programs"].items():
        for name, s, d in evs:
            if lo <= s <= hi:
                p = programs.setdefault(name, {"runs": 0, "seconds": 0.0})
                p["runs"] += 1
                p["seconds"] += d * 1e-9
    n_dev = len(trace["ops"])
    spans = _Spans(trace["spans"] + trace["program_spans"])
    by_span: Dict[str, float] = {}
    for length, s, _ in gaps:
        key = spans.innermost(s + length / 2)
        by_span[key] = by_span.get(key, 0.0) + length * 1e-9 / n_dev
    gaps.sort(reverse=True)
    self_s, span_counts = program_time(trace, lo, hi)
    return {
        "busy_s": sum(busy) / n_dev,
        "window_s": window_s,
        "programs": programs,
        "in_policy_calls": _in_spans(trace, spans, POLICY_SPAN),
        "self_s": self_s,
        "span_counts": span_counts,
        "idle_by_span": by_span,
        "device_ops": sorted(([n, p["seconds"] / n_dev]
                              for n, p in programs.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": [[spans.innermost(s + length / 2), length * 1e-9]
                      for length, s, _ in gaps[:top]],
    }


def load(path: str) -> Dict:
    return json.loads(Path(path).read_text())


def policy_runs(record: Dict):
    """(runs, device seconds) of the policy program inside the window's
    policy-call spans. A window that made policy calls and shows no such
    run on the device fails the run."""
    from bench.harness import MissingLayer
    calls = len(record["policy_calls"])
    p = record["trace"]["in_policy_calls"].get(POLICY_PROGRAM)
    if not p or not p["runs"]:
        raise MissingLayer(f"no {POLICY_PROGRAM} run on the device inside "
                           f"the window's {calls} policy calls")
    if p["runs"] != calls:
        record["notes"].append(f"{p['runs']} {POLICY_PROGRAM} runs in the "
                               f"trace for {calls} policy calls")
    return p["runs"], p["seconds"]


def program_spans(record: Dict, names: Sequence[str]):
    """The window's self seconds and counts (`program_time`) of the
    program spans `names`, by name. A window in which one of them neither
    started nor ran fails the run: a layer the served path must pass
    through left no trace."""
    from bench.harness import MissingLayer
    tr = record["trace"]
    missing = [n for n in names
               if n not in tr["self_s"] or n not in tr["span_counts"]]
    if missing:
        raise MissingLayer(f"no {', '.join(missing)} spans in the window "
                           f"of {len(record.get('comps') or [])} completed "
                           f"queries")
    return ({n: tr["self_s"][n] for n in names},
            {n: tr["span_counts"][n] for n in names})


def self_ms_per(record: Dict, names: Sequence[str],
                per: str | None = None):
    """The self milliseconds of the program spans `names` in a serving
    window, summed, per completed query, or per span of the name `per`
    where it is given. None off a serving window that completed queries."""
    comps = record.get("comps")
    if record["drive"] != "serve" or not comps:
        return None
    self_s, counts = program_spans(record,
                                   tuple(names) + ((per,) if per else ()))
    n = counts[per]["spans"] if per else len(comps)
    return sum(self_s[k] for k in names) / n * 1e3
