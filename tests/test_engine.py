"""Engine invariants: exact join correctness vs brute force, Alg. 2 plan
transformations preserve semantics and never create cross joins, AQE
operator switching, OOM/timeout semantics, shuffle accounting.

Property-style tests use seeded sweeps (hypothesis is not installed in this
offline container — see DESIGN.md §Testing note)."""
import itertools
import types

import numpy as np
import pytest

from repro.core.actions import ActionSpace, action_mask, apply_action
from repro.serve.cache import StageCache
from repro.sql import datagen, workloads
from repro.sql.catalog import Database, Table, analyze
from repro.sql.cbo import Estimator, cbo_plan, dp_join_order, greedy_join_order
from repro.sql.cluster import ClusterModel
from repro.sql import executor
from repro.sql.executor import (AdaptiveRun, Executor, MaterializedRel,
                                QueryFailure, StageRecord, annotate_methods,
                                run_adaptive, RuntimeState, planned_shuffles)
from repro.sql.plans import (BHJ, SMJ, apply_broadcast, apply_lead,
                             apply_swap, build_left_deep, is_bushy, joins,
                             leaves, syntactic_plan)
from repro.sql.query import Filter, JoinCond, Query, Relation


def _brute_force_count(db, query):
    """Nested-loop join cardinality via pandas-free numpy (small tables)."""
    rels = list(query.relations)
    rows = None
    for r in rels:
        t = db.table(r.table)
        mask = np.ones(t.nrows, bool)
        for f in r.filters:
            mask &= f.apply(t.columns[f.column])
        idx = np.flatnonzero(mask)
        cols = {(r.alias, c): (t.columns[c][idx] if c in t.columns
                               else idx.astype(np.int64))
                for c in set(
                    [x for cond in query.conds for x in
                     ([cond.lcol] if cond.left == r.alias else []) +
                     ([cond.rcol] if cond.right == r.alias else [])] or ["id"])}
        if rows is None:
            rows = cols
            n = len(idx)
            continue
        # cartesian then filter by all applicable conds
        m = len(idx)
        newrows = {k: np.repeat(v, m) for k, v in rows.items()}
        newrows.update({k: np.tile(v, n) for k, v in cols.items()})
        keep = np.ones(n * m, bool)
        done_aliases = {a for (a, _) in rows.keys()} | {r.alias}
        for c in query.conds:
            if c.left in done_aliases and c.right in done_aliases and (
                    (c.left, c.lcol) in newrows and (c.right, c.rcol) in newrows):
                keep &= newrows[(c.left, c.lcol)] == newrows[(c.right, c.rcol)]
        rows = {k: v[keep] for k, v in newrows.items()}
        n = int(keep.sum())
    return n


def _tiny_db(seed=0):
    rng = np.random.default_rng(seed)
    t = {"a": Table("a", {"id": np.arange(30, dtype=np.int64),
                          "x": rng.integers(0, 5, 30).astype(np.int64)}),
         "b": Table("b", {"a_id": rng.integers(0, 30, 60).astype(np.int64),
                          "c_id": rng.integers(0, 10, 60).astype(np.int64)}),
         "c": Table("c", {"id": np.arange(10, dtype=np.int64)}),
         "d": Table("d", {"a_id": rng.integers(0, 30, 40).astype(np.int64)})}
    db = Database("tiny", t)
    db.stats = analyze(db)
    return db


def _tiny_query(with_filter=True):
    f = (Filter("x", "<=", (2,)),) if with_filter else ()
    return Query("q", (Relation("a", "a", f), Relation("b", "b"),
                       Relation("c", "c"), Relation("d", "d")),
                 (JoinCond("a", "id", "b", "a_id"),
                  JoinCond("b", "c_id", "c", "id"),
                  JoinCond("a", "id", "d", "a_id")))


@pytest.mark.parametrize("seed", range(6))
def test_join_cardinality_matches_brute_force(seed):
    db = _tiny_db(seed)
    q = _tiny_query()
    expected = _brute_force_count(db, q)
    est = Estimator(db, db.stats)
    res = run_adaptive(db, q, syntactic_plan(q), est, ClusterModel())
    assert not res.failed
    assert res.stages[-1].out_rows == expected


@pytest.mark.parametrize("seed", range(8))
def test_plan_transforms_preserve_cardinality(seed):
    """ANY order produced by swap/lead yields the same final cardinality
    (join semantics are order-independent) — the engine's core invariant."""
    db = _tiny_db(seed + 100)
    q = _tiny_query()
    est = Estimator(db, db.stats)
    base = run_adaptive(db, q, syntactic_plan(q), est, ClusterModel())
    rng = np.random.default_rng(seed)
    plan = syntactic_plan(q)
    for _ in range(4):
        n = len(leaves(plan))
        if rng.random() < 0.5:
            i, j = sorted(rng.choice(np.arange(1, n + 1), 2, replace=False))
            new = apply_swap(q, plan, int(i), int(j))
        else:
            new = apply_lead(q, plan, int(rng.integers(2, n + 1)))
        if new is not None:
            plan = new
    res = run_adaptive(db, q, plan, est, ClusterModel())
    assert res.stages[-1].out_rows == base.stages[-1].out_rows


def test_alg2_never_creates_cross_join(job_workload):
    """Every join in every transformed plan must have >= 1 condition."""
    rng = np.random.default_rng(0)
    for q in job_workload.test[:8]:
        plan = syntactic_plan(q)
        for _ in range(6):
            n = len(leaves(plan))
            i = int(rng.integers(2, n + 1))
            new = apply_lead(q, plan, i)
            if new is not None:
                plan = new
            for j in joins(plan):
                assert len(j.conds) >= 1


def test_lead_moves_leaf_to_front(job_workload):
    q = job_workload.test[5]
    plan = syntactic_plan(q)
    lvs = leaves(plan)
    n = len(lvs)
    for i in range(2, n + 1):
        new = apply_lead(q, plan, i)
        if new is not None:
            assert leaves(new)[0].aliases == lvs[i - 1].aliases


def test_swap_is_an_involution_on_feasible_pairs(job_workload):
    q = job_workload.test[3]
    plan = syntactic_plan(q)
    n = len(leaves(plan))
    for i in range(1, n):
        new = apply_swap(q, plan, i, i + 1)
        if new is None:
            continue
        back = apply_swap(q, new, i, i + 1)
        if back is not None:
            assert [l.aliases for l in leaves(back)] == \
                [l.aliases for l in leaves(plan)]


def test_aqe_switches_small_side_to_bhj(job_db, estimator, job_workload):
    """With actual bytes below BJT, the executed method must be BHJ even if
    the planner said SMJ (and vice versa above BJT)."""
    q = job_workload.test[0]
    plan = syntactic_plan(q)
    for j in joins(plan):
        j.method = SMJ
    res = run_adaptive(job_db, q, plan, estimator, ClusterModel())
    cl = ClusterModel()
    for rec in res.stages:
        if rec.method == BHJ:
            return            # at least one promotion happened
    # tiny scale: every stage should have had a small side
    assert any(r.method == BHJ for r in res.stages)


def test_oom_on_exploding_join():
    rng = np.random.default_rng(0)
    n = 4000
    db = Database("boom", {
        "l": Table("l", {"k": np.zeros(n, np.int64)}),
        "r": Table("r", {"k": np.zeros(n, np.int64)})})
    db.stats = analyze(db)
    q = Query("boom", (Relation("l", "l"), Relation("r", "r")),
              (JoinCond("l", "k", "r", "k"),))
    res = run_adaptive(db, q, syntactic_plan(q), Estimator(db, db.stats),
                       ClusterModel(materialize_cap=1_000_000))
    assert res.failed and res.failure_kind == "oom"
    assert res.latency == ClusterModel().timeout


def _zipf_keys(rng, n, domain, a=1.2):
    return ((rng.zipf(a, n) - 1) % domain).astype(np.int64)


def _probe_case(name):
    """(lkey, rkey, the probe path `_join_plan` must take)."""
    rng = np.random.default_rng(PROBE_CASES.index(name))
    if name == "unique":            # key-to-id join
        return (_zipf_keys(rng, 5000, 900), rng.permutation(800), "unique")
    if name == "zipf_dup":
        return (_zipf_keys(rng, 3000, 400), _zipf_keys(rng, 4000, 500),
                "dense")
    if name == "left_outside_right":
        return (rng.integers(0, 5000, 2000), _zipf_keys(rng, 700, 100),
                "dense")
    if name == "unique_wide":       # domain above 2**16, position table
        return (rng.integers(0, 150_000, 50_000),
                rng.choice(150_000, 60_000, replace=False), "unique")
    if name == "dup_wide":          # domain above 2**16: two radix passes
        return (_zipf_keys(rng, 50_000, 150_000, a=1.05),
                rng.integers(0, 150_000, 60_000), "dense")
    if name == "int32_uint16":
        return (rng.integers(0, 300, 900).astype(np.int32),
                rng.integers(0, 300, 700).astype(np.uint16), "dense")
    if name == "empty_left":
        return np.zeros(0, np.int64), _zipf_keys(rng, 500, 50), "sorted"
    if name == "empty_right":
        return _zipf_keys(rng, 500, 50), np.zeros(0, np.int64), "sorted"
    if name == "sparse":            # domain far above the row counts
        return (rng.integers(0, 40, 600) * 10**9,
                rng.integers(0, 40, 500) * 10**9, "sorted")
    if name == "negative":
        return (rng.integers(-20, 20, 600), rng.integers(-20, 20, 500),
                "sorted")
    if name == "float":
        return (rng.integers(0, 50, 600).astype(np.float64),
                rng.integers(0, 50, 500).astype(np.float64), "sorted")
    raise KeyError(name)


PROBE_CASES = ("unique", "zipf_dup", "left_outside_right", "unique_wide",
               "dup_wide", "int32_uint16", "empty_left", "empty_right",
               "sparse", "negative", "float")


@pytest.mark.parametrize("case", PROBE_CASES)
def test_join_probe_equals_sort_and_search(case):
    """The direct-address probe returns the reference's row indices
    element for element, in the reference's order."""
    lkey, rkey, path = _probe_case(case)
    want_l, want_r = executor._join_indices_sorted(lkey, rkey, 10**9)
    lidx, ridx, probe = executor._join_indices(lkey, rkey, 10**9)
    assert probe == path
    assert np.array_equal(lidx, want_l) and np.array_equal(ridx, want_r)
    assert lidx.dtype == want_l.dtype and ridx.dtype == want_r.dtype


@pytest.mark.parametrize("case", ("unique", "zipf_dup", "dup_wide",
                                  "empty_left", "sparse"))
def test_join_probe_raises_oom_where_the_reference_does(case):
    """The cap admits exactly `total` matched rows on every path."""
    lkey, rkey, path = _probe_case(case)
    total = len(executor._join_indices_sorted(lkey, rkey, 10**9)[0])
    lidx, _, probe = executor._join_indices(lkey, rkey, total)
    assert probe == path and len(lidx) == total
    for fn in (executor._join_indices, executor._join_indices_sorted):
        with pytest.raises(QueryFailure) as err:
            fn(lkey, rkey, total - 1)
        assert err.value.kind == "oom"
        assert f"join output {total} rows" in str(err.value)


@pytest.mark.parametrize("need_right", (False, True))
@pytest.mark.parametrize("case", PROBE_CASES)
def test_join_plan_builds_the_reference_columns(case, need_right):
    """The columns `_join_plan` builds equal those gathered through the
    reference's row-index pairs, dtype and all, with the same total and
    the same blowup one row under it; right row indices are built only
    for an output that reads the right side."""
    lkey, rkey, path = _probe_case(case)
    want_l, want_r = executor._join_indices_sorted(lkey, rkey, 10**9)
    total = len(want_l)
    m = executor._join_plan(lkey, rkey, total, need_right)
    assert (m.total, m.probe, m.built) == (total, path, int(need_right))
    built = [(m.left(c), c[want_l]) for c in
             (lkey, np.arange(len(lkey), dtype=np.int32) * 7)]
    if need_right:
        built += [(m.right(c), c[want_r]) for c in
                  (rkey, np.linspace(0.0, 1.0, len(rkey)))]
    for got, want in built:
        assert np.array_equal(got, want) and got.dtype == want.dtype
    with pytest.raises(QueryFailure) as err:
        executor._join_plan(lkey, rkey, total - 1, need_right)
    assert err.value.kind == "oom"
    assert f"join output {total} rows" in str(err.value)


@pytest.mark.parametrize("case", ("empty_left", "empty_right"))
def test_join_plan_sorts_nothing_for_an_empty_input(case, monkeypatch):
    """An empty input matches nothing at once: the other side is neither
    sorted nor searched."""
    lkey, rkey, _ = _probe_case(case)

    def refuse(*args, **kwargs):
        raise AssertionError("an empty join sorted its other side")

    monkeypatch.setattr(np, "argsort", refuse)
    monkeypatch.setattr(np, "searchsorted", refuse)
    m = executor._join_plan(lkey, rkey, 10**9, True)
    assert (m.total, m.probe, m.built) == (0, "sorted", 1)
    assert len(m.left(lkey)) == len(m.right(rkey)) == 0
    assert m.left(lkey).dtype == lkey.dtype


def _sorted_pairs_plan(lkey, rkey, cap, need_right):
    """A `_join_plan` that gathers both sides through the sort-and-search
    reference's full row-index pairs, as every join stage did before it
    read match counts."""
    lidx, ridx = executor._join_indices_sorted(lkey, rkey, cap)
    return types.SimpleNamespace(
        total=len(lidx), probe="pairs", built=2, ridx=ridx,
        left=lambda col: col[lidx], right=lambda col: col[ridx])


def test_join_probe_changes_no_run(job_db, estimator, job_workload,
                                   monkeypatch):
    """JOB-like queries run through the executor give the same stages,
    rows and latency with the probe as with sort-and-search alone."""
    queries = job_workload.test[:6]

    def runs():
        return [run_adaptive(job_db, q, syntactic_plan(q), estimator,
                             ClusterModel())
                for q in queries]

    seen = []
    plan = executor._join_plan

    def spy(*args):
        out = plan(*args)
        seen.append(out.probe)
        return out

    monkeypatch.setattr(executor, "_join_plan", spy)
    fast = runs()
    assert {"unique", "dense"} <= set(seen)
    monkeypatch.setattr(executor, "_join_plan", _sorted_pairs_plan)
    for a, b in zip(fast, runs()):
        assert a.stages == b.stages
        assert a.latency == b.latency and a.failed == b.failed
        assert a.final_plan == b.final_plan


def test_join_probe_is_named_on_cache_misses_only():
    """`Executor.probe` names the path of the join it ran, and is None
    when the stage cache served the join."""
    db = _tiny_db(3)
    q = _tiny_query()
    ex = Executor(db, cache=StageCache())
    a, _ = ex.scan(q, "a")
    b, _ = ex.scan(q, "b")
    first, _ = ex.join(q, b, a, q.conds[:1], SMJ)     # b.a_id = a.id
    assert ex.probe == "unique"
    again, _ = ex.join(q, b, a, q.conds[:1], SMJ)
    assert ex.probe is None and again.nrows == first.nrows


def test_residual_conditions_join_through_row_index_pairs(monkeypatch):
    """A join with a residual condition reads both sides' rows at every
    matched pair: it builds both row indices and keeps the pairs that
    meet the condition, and the query still counts the rows a brute
    force gives."""
    db = _tiny_db(4)
    q = Query("two", (Relation("a", "a"), Relation("b", "b"),
                      Relation("c", "c")),
              (JoinCond("a", "id", "b", "a_id"),
               JoinCond("a", "x", "b", "c_id"),
               JoinCond("b", "c_id", "c", "id")))
    ex = Executor(db)
    a, _ = ex.scan(q, "a")
    b, _ = ex.scan(q, "b")
    out, _ = ex.join(q, a, b, q.conds[:2], SMJ)
    assert (ex.probe, ex.built) == ("dense", 2)
    lidx, ridx = executor._join_indices_sorted(
        a.columns[("a", "id")], b.columns[("b", "a_id")], 10**9)
    keep = a.columns[("a", "x")][lidx] == b.columns[("b", "c_id")][ridx]
    assert 0 < out.nrows == int(keep.sum()) < len(lidx)
    assert np.array_equal(out.columns[("b", "c_id")],
                          b.columns[("b", "c_id")][ridx[keep]])
    built, join = [], Executor.join

    def spy(self, *args):
        out = join(self, *args)
        built.append(self.built)
        return out

    monkeypatch.setattr(Executor, "join", spy)
    res = run_adaptive(db, q, syntactic_plan(q), Estimator(db, db.stats),
                       ClusterModel())
    assert res.stages[-1].out_rows == _brute_force_count(db, q)
    assert built == [2, 0]      # then the last join, a count


def _full_gather_join(self, query, left, right, conds, method):
    """`Executor.join` as it was before joins kept only the live key
    columns: every column of both inputs is gathered into the output, and
    the stage-cache signature names no columns."""
    cl = self.cluster
    c0 = conds[0]
    if c0.left in left.aliases:
        key_l, key_r = (c0.left, c0.lcol), (c0.right, c0.rcol)
    else:
        key_l, key_r = (c0.right, c0.rcol), (c0.left, c0.lcol)
    self.probe = None
    sig = None
    if self._cache is not None and left.sig is not None \
            and right.sig is not None:
        sig = ("j", left.sig, right.sig, tuple(conds))
    hit = self._cache.get(sig) if sig is not None else None
    if hit is not None:
        out_cols, nrows, pre_total = hit
        if pre_total > cl.materialize_cap:
            raise QueryFailure(
                "oom", f"join output {pre_total} rows exceeds cap")
        out = MaterializedRel(left.aliases | right.aliases,
                              dict(out_cols), nrows,
                              left.width + right.width, sig=sig)
    else:
        lkey = left.columns[key_l]
        rkey = right.columns[key_r]
        lidx, ridx, self.probe = executor._join_indices(
            lkey, rkey, cl.materialize_cap)
        pre_total = len(lidx)
        keep = np.ones(len(lidx), bool)
        for c in conds[1:]:
            if c.left in left.aliases:
                la, ra = (c.left, c.lcol), (c.right, c.rcol)
            else:
                la, ra = (c.right, c.rcol), (c.left, c.lcol)
            keep &= left.columns[la][lidx] == right.columns[ra][ridx]
        if not keep.all():
            lidx, ridx = lidx[keep], ridx[keep]
        out_cols = {k: v[lidx] for k, v in left.columns.items()}
        out_cols.update({k: v[ridx] for k, v in right.columns.items()})
        out = MaterializedRel(left.aliases | right.aliases, out_cols,
                              len(lidx), left.width + right.width,
                              sig=sig)
        if sig is not None:
            nbytes = sum(v.nbytes for v in out_cols.values())
            self._cache.put(sig, (dict(out_cols), len(lidx), pre_total),
                            nbytes)
    shuffles = 0
    shuffle_bytes = 0.0
    if method == SMJ:
        t = cl.stage_overhead
        for side, key in ((left, key_l), (right, key_r)):
            if side.partitioned_on != key:
                shuffles += 1
                shuffle_bytes += side.bytes
                t += cl.shuffle_time(side.bytes)
        t += cl.smj_cpu(left.nrows, right.nrows, out.nrows)
        out.partitioned_on = key_l
    else:
        build, probe = ((left, right) if left.bytes <= right.bytes
                        else (right, left))
        if cl.broadcast_oom(build.bytes):
            raise QueryFailure("oom",
                               f"broadcast build {build.bytes/1e6:.1f} MB")
        t = cl.stage_overhead + cl.broadcast_time(build.bytes)
        t += cl.bhj_cpu(build.nrows, probe.nrows, out.nrows)
        out.partitioned_on = probe.partitioned_on
    rec = StageRecord(out.aliases, method, out.nrows, out.bytes,
                      shuffles, shuffle_bytes, t)
    return out, rec


POOL = 24


@pytest.fixture(scope="module", params=["job", "stack"])
def pool(request, job_db, stack_db):
    """The first queries of a configuration's serving pool, on its
    database."""
    db = job_db if request.param == "job" else stack_db
    queries = list(itertools.islice(
        workloads.query_stream(request.param, seed=1009), POOL))
    return db, Estimator(db, db.stats), queries


def _rewriting_hook(queries):
    """A deterministic hook that rewrites the remaining plan at every
    stage boundary: a legal cbo/lead/swap/broadcast action picked from the
    step and the query's name."""
    space = ActionSpace(max(q.n_relations for q in queries),
                        families=("cbo", "lead", "swap", "broadcast"))

    def hook(state):
        legal = np.flatnonzero(action_mask(space, state))
        pick = (3 * state.step + len(state.query.name)) % len(legal)
        return apply_action(space, state, int(legal[pick]))[0]
    return hook


def _serve_pool(db, est, queries, hook, passes=2):
    """Every query run `passes` times on one shared stage cache, so the
    later passes are served from it; the runs' results in order."""
    cache = StageCache()
    out = []
    for _ in range(passes):
        for q in queries:
            run = AdaptiveRun(db, q, syntactic_plan(q), est, ClusterModel(),
                              max_hook_steps=q.n_relations if hook else 0,
                              cache=cache)
            st = run.start()
            while st is not None:
                st = run.resume(hook(st))
            out.append(run.result)
    return out


@pytest.mark.parametrize("plans", ["syntactic", "rewritten"])
def test_live_column_joins_change_no_run(pool, plans, monkeypatch):
    """Joins that carry only the key columns a later join reads give the
    same stages, latency and failures as joins that carry every column,
    on syntactic plans and on plans a hook rewrites at each boundary."""
    db, est, queries = pool
    hook = _rewriting_hook(queries) if plans == "rewritten" else None
    live = _serve_pool(db, est, queries, hook)
    monkeypatch.setattr(Executor, "join", _full_gather_join)
    full = _serve_pool(db, est, queries, hook)
    assert sum(len(r.stages) for r in live) > 2 * len(queries)
    for a, b in zip(live, full):
        assert a.stages == b.stages
        assert a.latency == b.latency
        assert (a.failed, a.failure_kind) == (b.failed, b.failure_kind)
        assert a.final_plan == b.final_plan


def test_match_count_joins_change_no_run(pool, monkeypatch):
    """A serving pool, on plans a hook rewrites at each boundary and with
    a stage cache, builds the same intermediates (columns, dtypes, rows)
    and gives the same stages, latency and final plans with joins built
    from match counts as with the sort-and-search reference's pairs
    forced for every stage. Joins built from match counts build no left row index, and a
    right one only for an output that reads the right side."""
    db, est, queries = pool
    join = Executor.join

    def runs():
        outs = []

        def spy(self, query, left, right, conds, method):
            out, rec = join(self, query, left, right, conds, method)
            reads_right = any(a in right.aliases for a, _ in out.columns)
            outs.append((out.nrows, out.columns, self.probe, self.built,
                         2 if len(conds) > 1 else int(reads_right)))
            return out, rec

        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(Executor, "join", spy)
            results = _serve_pool(db, est, queries, _rewriting_hook(queries))
        return results, outs

    fast, fast_outs = runs()
    monkeypatch.setattr(executor, "_join_plan", _sorted_pairs_plan)
    pairs, pairs_outs = runs()
    for a, b in zip(fast, pairs):
        assert a.stages == b.stages
        assert a.latency == b.latency
        assert (a.failed, a.failure_kind) == (b.failed, b.failure_kind)
        assert a.final_plan == b.final_plan
    assert len(fast_outs) == len(pairs_outs)
    for (n, cols, probe, built, want), (pn, pcols, pprobe, *_) in zip(
            fast_outs, pairs_outs):
        assert n == pn and cols.keys() == pcols.keys()
        for k in cols:
            assert np.array_equal(cols[k], pcols[k])
            assert cols[k].dtype == pcols[k].dtype
        assert (probe is None) == (pprobe is None)
        assert built == (None if probe is None else want)
    assert {o[3] for o in fast_outs} >= {0, 1}


def _read_later(query, aliases):
    """The (alias, column) ends of the query's conditions that lie inside
    `aliases` while the other end lies outside."""
    ends = set()
    for c in query.conds:
        for (a, col), other in (((c.left, c.lcol), c.right),
                                ((c.right, c.rcol), c.left)):
            if a in aliases and other not in aliases:
                ends.add((a, col))
    return ends


def test_joins_carry_exactly_the_live_columns(pool, monkeypatch):
    """Every intermediate, computed or served from the stage cache, holds
    exactly the key columns a later join of its query reads; a query's
    last stage holds none."""
    db, est, queries = pool
    outs = []
    join = Executor.join

    def spy(self, query, left, right, conds, method):
        out, rec = join(self, query, left, right, conds, method)
        outs.append((query, out, rec))
        return out, rec

    monkeypatch.setattr(Executor, "join", spy)
    _serve_pool(db, est, queries, _rewriting_hook(queries))
    last = 0
    for q, out, rec in outs:
        assert set(out.columns) == _read_later(q, out.aliases)
        assert all(len(v) == out.nrows for v in out.columns.values())
        assert out.nrows == rec.out_rows
        if len(out.aliases) == q.n_relations:
            assert out.columns == {}
            last += 1
    assert last >= len(queries)


def test_stage_cache_keeps_apart_joins_that_feed_other_columns(job_db,
                                                               estimator):
    """Two queries share a join's inputs and conditions, but the next
    join reads `t.id` in one and `mc.movie_id` in the other: the second
    must compute the join, not take the first's entry, and both must
    count the rows a direct count gives."""
    rels = (Relation("t", "title", (Filter("production_year", ">=",
                                           (2005,)),)),
            Relation("mc", "movie_companies"), Relation("mi", "movie_info"))
    first = JoinCond("t", "id", "mc", "movie_id")
    q1 = Query("by_t", rels, (first, JoinCond("t", "id", "mi", "movie_id")))
    q2 = Query("by_mc", rels, (first,
                               JoinCond("mc", "movie_id", "mi", "movie_id")))
    t = job_db.table("title")
    ids = t.columns["id"][t.columns["production_year"] >= 2005]
    n = lambda name: np.bincount(job_db.table(name).columns["movie_id"],
                                 minlength=t.nrows)
    expected = int((n("movie_companies") * n("movie_info"))[ids].sum())
    cache = StageCache()
    probes = []
    join = Executor.join

    def spy(self, *args):
        out = join(self, *args)
        probes.append(self.probe)
        return out

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(Executor, "join", spy)
        for q in (q1, q2, q1):
            run = AdaptiveRun(job_db, q, syntactic_plan(q), estimator,
                              ClusterModel(), max_hook_steps=0, cache=cache)
            run.start()
            assert not run.result.failed
            assert run.result.stages[-1].out_rows == expected > 0
    # q2 computes both of its joins; q1 run again is served both
    assert probes[2] is not None and probes[3] is not None
    assert probes[4:] == [None, None]


def test_partitioning_reuse_reduces_shuffles(job_db, estimator):
    """Consecutive SMJs on the same key reuse partitioning (1 shuffle, not
    2, for the pre-partitioned side)."""
    q = Query("p", (Relation("at", "aka_title"),
                    Relation("cc", "complete_cast"),
                    Relation("ml", "movie_link")),
              (JoinCond("at", "movie_id", "cc", "movie_id"),
               JoinCond("at", "movie_id", "ml", "movie_id")))
    cl = ClusterModel(bjt=1.0)           # force SMJ everywhere
    res = run_adaptive(job_db, q, syntactic_plan(q), estimator, cl)
    assert not res.failed
    # join1: 2 shuffles; join2: intermediate already partitioned on
    # movie_id -> only cast_info shuffles
    assert [s.shuffles for s in res.stages] == [2, 1]


def test_cbo_beats_worst_syntactic_on_average(job_db, estimator, job_workload):
    wins = ties = 0
    for q in job_workload.test[:10]:
        r0 = run_adaptive(job_db, q, syntactic_plan(q), estimator, ClusterModel())
        p1, _ = cbo_plan(q, estimator)
        r1 = run_adaptive(job_db, q, p1, estimator, ClusterModel())
        if r1.latency <= r0.latency * 1.05:
            wins += 1
    assert wins >= 7, f"CBO should rarely lose badly; wins={wins}/10"


def test_dp_join_order_optimal_on_small_query():
    """DP must match exhaustive search on a 4-relation query (C_out)."""
    db = _tiny_db(3)
    q = _tiny_query()
    est = Estimator(db, db.stats)
    plan, secs, n_sub = dp_join_order(q, est)
    assert plan is not None and n_sub > 0
    assert frozenset(a for l in leaves(plan) for a in l.aliases) == \
        frozenset(r.alias for r in q.relations)


def test_planned_shuffles_decreases_with_broadcast_hint(job_db, estimator,
                                                        job_workload):
    q = job_workload.test[2]
    plan = syntactic_plan(q)
    st = RuntimeState(q, plan, {}, estimator, 0, 0.0, 0)
    before = planned_shuffles(plan, st)
    hinted = apply_broadcast(plan, 1)
    after = planned_shuffles(hinted, st)
    assert after <= before


def test_workloads_connected_and_sized():
    for bench, lo, hi in (("job", 4, 17), ("extjob", 3, 10), ("stack", 4, 12)):
        wl = workloads.make_workload(bench, n_train=16, n_test_per_template=1)
        for q in wl.train + wl.test:
            assert q.is_connected(), q.name
            assert lo <= q.n_relations <= hi, (q.name, q.n_relations)


def test_dynamic_snapshot_filters_years():
    full = datagen.make_job_like(scale=0.1, seed=0)
    old = datagen.make_job_like(scale=0.1, seed=0, year_max=1950)
    assert 0 < old.tables["title"].nrows < 0.6 * full.tables["title"].nrows
    assert old.tables["cast_info"].nrows < full.tables["cast_info"].nrows


def test_sql_imports_nothing_above_it():
    """The engine stands alone: no module under `repro/sql` imports the
    serving, decision-loop or learning packages (a stage cache and every
    other collaborator is handed in by its owner)."""
    import ast
    import pathlib
    above = ("repro.serve", "repro.core", "repro.learn")
    root = pathlib.Path(executor.__file__).parent
    found = []
    for path in sorted(root.rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                names = [node.module or ""]
            else:
                continue
            found += [(path.name, n) for n in names
                      if n == "repro" or n.startswith(above)]
    assert len(list(root.rglob("*.py"))) > 5
    assert found == []
